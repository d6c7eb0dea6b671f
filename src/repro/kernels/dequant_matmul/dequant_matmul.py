"""Fused dequant-matmul Pallas TPU kernels (weight-only int8 / packed int4).

HBM traffic for the weight operand is the *packed* bytes: the kernel reads
int8 (or nibble-packed uint8) tiles plus their scales and dequantizes
in-register, so decode weight streaming moves 2x (int8) or 4x (int4) fewer
bytes than bf16 — exactly the byte reduction the paper's 4-bit IPW headline
rides on.

Both kernels run a (m, n, k) grid with the contraction innermost (the
`repro.kernels.moe_gemm` pattern): an f32 VMEM accumulator is zeroed at
``k == 0`` and written out at the last k step.

* int8 (per-out-channel scales): the scale folds out of the k-sum exactly, so
  raw integer products accumulate and one multiply by ``scale[n]`` happens at
  write-out.
* int4 (group-wise scales): each grid step covers a K-block of whole scale
  groups (a multiple of 256 input rows, so the half-width x tiles stay
  128-lane aligned for Mosaic). The packed rows stay packed — the even input
  rows multiply the low nibbles and the odd rows the high nibbles, which
  avoids materializing an interleaved unpacked tile; each packed row is
  scaled by its group's scale before the matmul (dequantize, then matmul):

      acc += x_even @ (lo * scale[g(r)]) + x_odd @ (hi * scale[g(r)])

Inputs are padded to block multiples (outputs sliced back).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _int8_kernel(x_ref, qw_ref, scale_ref, o_ref, acc_ref):
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)            # (bm, bk)
    w = qw_ref[...].astype(jnp.float32)           # (bk, bn) dequant sans scale
    acc_ref[...] += jax.lax.dot(x, w)

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def dequant_matmul_int8_pallas(x: jnp.ndarray, qw: jnp.ndarray,
                               scale: jnp.ndarray, *, block_m: int = 128,
                               block_n: int = 128, block_k: int = 128,
                               interpret: bool = False) -> jnp.ndarray:
    """x (M, K) float, qw (K, N) int8, scale (N,) f32 -> (M, N) in x.dtype."""
    M, K = x.shape
    N = qw.shape[1]
    bm = min(block_m, max(M, 8))
    bn = min(block_n, max(N, 128))
    bk = min(block_k, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    qwp = jnp.pad(qw, ((0, pk), (0, pn)))
    sp = jnp.pad(scale.astype(jnp.float32), (0, pn)).reshape(1, -1)
    grid = ((M + pm) // bm, (N + pn) // bn, (K + pk) // bk)
    out = pl.pallas_call(
        _int8_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp, qwp, sp)
    return out[:M, :N]


def _int4_kernel(xe_ref, xo_ref, qw_ref, scale_ref, o_ref, acc_ref):
    k, nk = pl.program_id(2), pl.num_programs(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    p = qw_ref[...].astype(jnp.int32)             # (bk//2, bn) packed bytes
    lo = (((p & 0xF) ^ 8) - 8).astype(jnp.float32)
    hi = (((p >> 4) ^ 8) - 8).astype(jnp.float32)
    s = scale_ref[0]                              # (ng, bn) groups in block
    ng, bn = s.shape
    rows = lo.shape[0] // ng                      # packed rows per group
    s_rows = jnp.broadcast_to(s[:, None, :], (ng, rows, bn)).reshape(
        ng * rows, bn)
    acc_ref[...] += (
        jax.lax.dot(xe_ref[...].astype(jnp.float32), lo * s_rows) +
        jax.lax.dot(xo_ref[...].astype(jnp.float32), hi * s_rows))

    @pl.when(k == nk - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "interpret"))
def dequant_matmul_int4_pallas(x: jnp.ndarray, packed: jnp.ndarray,
                               scale: jnp.ndarray, *, block_m: int = 128,
                               block_n: int = 128,
                               interpret: bool = False) -> jnp.ndarray:
    """x (M, K) float, packed (K//2, N) uint8, scale (G, N) f32 -> (M, N).

    The group size ``K // G`` is implied by the shapes; it must be even (the
    quantizer guarantees this — two rows pack per byte).

    The even and odd input columns of ``x`` are split here, once, so the
    kernel needs no lane shuffle. Each grid step covers ``bk`` input rows:
    the least common multiple of 256 and the group size, so that the
    half-width x blocks are 128-lane multiples and a block holds whole
    groups. K is zero-padded to ``bk`` (inert: zero nibbles decode to 0).
    """
    M, K = x.shape
    N = packed.shape[1]
    G = scale.shape[0]
    gs = K // G
    bk = math.lcm(256, gs)
    bm = min(block_m, max(M, 8))
    bn = min(block_n, max(N, 128))
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk)))
    # zero nibbles decode to 0 ((0 ^ 8) - 8 == 0), so padding is inert
    qp = jnp.pad(packed, ((0, pk // 2), (0, pn)))
    ng = bk // gs
    sp = jnp.pad(scale.astype(jnp.float32), ((0, pk // gs), (0, pn)))
    sp = sp.reshape(-1, ng, N + pn)               # (K-blocks, ng, N)
    grid = ((M + pm) // bm, (N + pn) // bn, (K + pk) // bk)
    out = pl.pallas_call(
        _int4_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bk // 2), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, ng, bn), lambda i, j, k: (k, 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M + pm, N + pn), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xp[:, 0::2], xp[:, 1::2], qp, sp)
    return out[:M, :N]
