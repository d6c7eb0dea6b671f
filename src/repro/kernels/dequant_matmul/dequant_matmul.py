"""Fused dequant-matmul Pallas TPU kernels (weight-only int8 / packed int4).

HBM traffic for the weight operand is the *packed* bytes: the kernel reads
int8 (or nibble-packed uint8) tiles plus their scales and dequantizes
in-register, so decode weight streaming moves 2x (int8) or 4x (int4) fewer
bytes than bf16 — exactly the byte reduction the paper's 4-bit IPW headline
rides on.

* int8 (per-out-channel scales) runs an (m, n) grid with the contraction
  whole inside each step, n innermost. A row block of x holds all of the
  call's rows, or as few blocks as fit, so at decode x is fetched once and
  stays resident while the weight streams past it in wide ``(K, bn)``
  column tiles (1-2 MiB at the served shapes), and every block fits the
  default scoped VMEM. Inside a step the int8 tile is cast to x's dtype
  512 rows at a time (every int8 value is exact in bf16) and fed to the
  MXU with f32 accumulation; the scale folds out of the k-sum exactly, so
  one multiply by ``scale[n]`` happens at write-out. Edge blocks of M and
  N are partial (rows and columns are independent and out-of-bounds
  writes are dropped), so no operand is padded or copied. A layer scan
  passes the whole ``(L, K, N)`` weight stack and the layer's index,
  which is prefetched to scalar memory for the weight's index map: the
  kernel streams that layer's rows from where they lie, where a scan
  over the stack would have XLA copy each layer's weight out first.
* int4 (group-wise scales) runs an (m, n, k) grid with the contraction
  innermost (the `repro.kernels.moe_gemm` pattern): an f32 VMEM
  accumulator is zeroed at ``k == 0`` and written out at the last k step.
  Each grid step covers a K-block of whole scale groups (a multiple of 256
  input rows, so the half-width x tiles stay 128-lane aligned for Mosaic).
  The packed rows stay packed — the even input rows multiply the low
  nibbles and the odd rows the high nibbles, which avoids materializing an
  interleaved unpacked tile; each packed row is scaled by its group's scale
  before the matmul (dequantize, then matmul):

      acc += x_even @ (lo * scale[g(r)]) + x_odd @ (hi * scale[g(r)])

  Its inputs are padded to block multiples (outputs sliced back).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Every block fits the default scoped VMEM (16 MiB on v5e), which the
# kernel never raises: a larger request runs over what the rest of an XLA
# program keeps there. Inputs are double-buffered: at most 2 x 2 MiB of
# weight tile (2.6 us of DMA at 819 GB/s, long beside a grid step's fixed
# cost) and 2 x 3 MiB of x; the tile is cast 512 rows at a time.
_W_TILE_BYTES = 2 << 20
_X_BLOCK_BYTES = 3 << 20
_MAX_BLOCK = 512          # rows of x, columns of the weight
_CHUNK = 512


def _int8_tiles(M: int, K: int, N: int, x_bytes: int):
    """(bm, bn) for an int8 call: as few row blocks as fit the x budget,
    evenly split, and the widest weight tile the tile budget holds."""
    rows = max(16, min(_MAX_BLOCK, _X_BLOCK_BYTES // (K * x_bytes)) // 16
               * 16)
    bm = M if M <= rows else -(-pl.cdiv(M, pl.cdiv(M, rows)) // 16) * 16
    bn = max(128, min(_MAX_BLOCK, _W_TILE_BYTES // K) // 128 * 128)
    return bm, min(bn, N)


def _int8_kernel(layer_ref, x_ref, qw_ref, scale_ref, o_ref):
    del layer_ref                                 # read by the index maps
    K = qw_ref.shape[0]
    chunk = _CHUNK if K % 128 == 0 else K
    acc = None
    for k0 in range(0, K, chunk):
        c = min(chunk, K - k0)
        w = qw_ref[k0:k0 + c, :].astype(x_ref.dtype)  # exact: |q| <= 127
        part = jnp.dot(x_ref[:, k0:k0 + c], w,
                       preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    o_ref[...] = (acc * scale_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_matmul_int8_pallas(x: jnp.ndarray, qw: jnp.ndarray,
                               scale: jnp.ndarray, layer=None, *,
                               interpret: bool = False) -> jnp.ndarray:
    """x (M, K) float, qw (K, N) int8, scale (N,) f32 -> (M, N) in x.dtype.

    With ``layer``, ``qw`` is a stack (L, K, N) of a scanned model's
    weights, and the kernel streams row ``layer`` of it where it lies
    (the index is prefetched to scalar memory for the index maps), so no
    per-layer copy of the weight is made outside the kernel.
    The MXU runs in x's dtype with f32 accumulation; x is never rounded.
    """
    if qw.ndim == 2:
        qw, layer = qw[None], 0                   # a bitcast, not a copy
    M, K = x.shape
    N = qw.shape[2]
    bm, bn = _int8_tiles(M, K, N, x.dtype.itemsize)
    return pl.pallas_call(
        _int8_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(M, bm), pl.cdiv(N, bn)),
            in_specs=[
                pl.BlockSpec((bm, K), lambda i, j, l: (i, 0)),
                pl.BlockSpec((None, K, bn), lambda i, j, l: (l[0], 0, j)),
                pl.BlockSpec((1, bn), lambda i, j, l: (0, j)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, l: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), x, qw,
      scale.astype(jnp.float32).reshape(1, N))


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
