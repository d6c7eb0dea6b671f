"""Mamba-2 SSD intra-chunk Pallas TPU kernel.

Computes, per (batch*head, chunk) grid cell, the two dense-matmul halves of the
state-space-dual form (DESIGN.md §6 — the MXU-friendly reformulation that
replaces Mamba-1's GPU-style parallel scan):

  Y_diag[q, p]  = sum_{s<=q} (C[q]·B[s]) * exp(segsum(dA))[q,s] * dt[s] * x[s, p]
  state[p, n]   = sum_q  B[q, n] * exp(dAcs[-1] - dAcs[q]) * dt[q] * x[q, p]

The inter-chunk O(1) recurrence stays a lax.scan outside the kernel (it is a
latency-trivial carry; fusing it would serialize the grid).

Block layout: one (chunk Q x headdim P) x (Q x N) working set per grid cell —
Q=128/256, P=64, N=128 keeps everything comfortably in VMEM and the three
matmuls (C@B^T: QxNxQ, scores@x: QxQxP, (x*w)^T@B: PxQxN) MXU-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, dt_row_ref, cs_ref, cs_row_ref, b_ref,
                      c_ref, y_ref, st_ref):
    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q, 1)
    dt_row = dt_row_ref[0, 0].astype(jnp.float32)  # (1, Q)
    cs = cs_ref[0, 0].astype(jnp.float32)        # (Q, 1) cumsum(dA)
    cs_row = cs_row_ref[0, 0].astype(jnp.float32)  # (1, Q)
    B = b_ref[0, 0].astype(jnp.float32)          # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)          # (Q, N)
    Q = x.shape[0]

    # decay matrix L[q, s] = exp(sum_{s<k<=q} dA[k]) for s<=q, else 0
    diff = cs - cs_row                           # (Q, Q)
    tri = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(tri, jnp.exp(diff), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (Q, Q)
    scores = scores * L * dt_row
    y_ref[0, 0] = jax.lax.dot(scores, x).astype(y_ref.dtype)      # (Q, P)

    decay = jnp.exp(cs[Q - 1:, :] - cs) * dt                      # (Q, 1)
    xw = x * decay                                                # (Q, P)
    st = jax.lax.dot_general(xw, B, (((0,), (0,)), ((), ())))     # (P, N)
    st_ref[0, 0] = st.astype(st_ref.dtype)


def ssd_chunk_pallas(x, dt, dA, dAcs, B, C, *, interpret: bool = True):
    """All inputs laid out (BH, nc, Q, ...): x (BH,nc,Q,P); dt/dA/dAcs
    (BH,nc,Q,1); B/C (BH,nc,Q,N). Returns (Y_diag (BH,nc,Q,P),
    states (BH,nc,P,N)) in f32.

    ``dA`` enters only through its cumulative sum ``dAcs``. The kernel takes
    dt and dAcs both as columns (Q, 1) and as rows (1, Q) — free reshapes
    here — so it needs no in-kernel transpose."""
    del dA
    BH, nc, Q, P = x.shape
    N = B.shape[-1]
    grid = (BH, nc)
    col = pl.BlockSpec((1, 1, Q, 1), lambda i, c: (i, c, 0, 0))
    row = pl.BlockSpec((1, 1, 1, Q), lambda i, c: (i, c, 0, 0))
    y, st = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, c: (i, c, 0, 0)),
            col, row, col, row,
            pl.BlockSpec((1, 1, Q, N), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, c: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, c: (i, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, nc, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
        ],
        interpret=interpret,
    )(x, dt, dt.reshape(BH, nc, 1, Q), dAcs, dAcs.reshape(BH, nc, 1, Q), B, C)
    return y, st
