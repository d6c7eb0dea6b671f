"""Decode attention Pallas TPU kernels (single-token query vs. KV cache).

This is the paper's memory-bound hot spot (Formalism 5: decode intensity ~1,
route to bandwidth-optimal hardware). The kernels stream the KV cache exactly
once per step — the bytes term the MLA latent cache and sliding-window variants
shrink in the §Perf hillclimbs.

Design:
  * grid (batch, kv_blocks); kv innermost so the flash-style running
    (m, l, acc) scratch carries across cache tiles.
  * One grid step serves every query head: q is viewed as (B, Hkv, group, D)
    and each KV head's ``group`` query rows attend to it together. The cache's
    KV-head axis is taken whole, so a KV tile is fetched once per step, not
    once per query head.
  * Mosaic tiles the last two dims of every block by (8, 128) unless a block
    dim equals the array's own, so the wrapper reshapes for free (contiguous
    merges only): caches (…, W, Hkv, D) -> (…, W, Hkv*D) and positions
    (…, W) -> (…, 1, W). The current positions ``q_pos`` (and the paged block
    table) ride in SMEM through scalar prefetch.
  * The per-slot validity mask comes from the absolute-position array the
    cache maintains (pos >= 0, pos <= q_pos, window); accumulation in f32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attend_tile(q_pos, q_ref, k_ref, v_ref, pos_ref, o_ref,
                 m_scr, l_scr, acc_scr, *, scale: float,
                 window: Optional[int], n_kv: int, d: int, dv: int):
    """One KV tile of online softmax for every (kv head, group) query row.
    q_ref (1, Hkv, G, D); k_ref (1, bk, Hkv*D); v_ref (1, bk, Hkv*Dv);
    pos_ref (1, 1, bk); scratch m/l (Hkv, G, 1), acc (Hkv, G, Dv)."""
    j = pl.program_id(1)
    nj = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    slot_pos = pos_ref[0]                             # (1, bk) absolute
    valid = (slot_pos >= 0) & (slot_pos <= q_pos)
    if window is not None:
        valid &= slot_pos > q_pos - window
    k_all = k_ref[0].astype(jnp.float32)              # (bk, Hkv*D)
    v_all = v_ref[0].astype(jnp.float32)              # (bk, Hkv*Dv)

    for h in range(n_kv):
        q = q_ref[0, h].astype(jnp.float32)           # (G, D)
        k = k_all[:, h * d:(h + 1) * d]               # (bk, D)
        v = v_all[:, h * dv:(h + 1) * dv]             # (bk, Dv)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        s = jnp.where(valid, s, NEG_INF)              # (G, bk)

        m_prev = m_scr[h]                             # (G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.where(valid, jnp.exp(s - m_safe), 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot(p, v)
        m_scr[h] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        for h in range(n_kv):
            o_ref[0, h] = (acc_scr[h] / jnp.maximum(l_scr[h], 1e-20)
                           ).astype(o_ref.dtype)


def _dense_kernel(qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref, *scratch,
                  **kw):
    _attend_tile(qpos_ref[pl.program_id(0)], q_ref, k_ref, v_ref, pos_ref,
                 o_ref, *scratch, **kw)


def _paged_kernel(tab_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref, o_ref,
                  *scratch, **kw):
    del tab_ref                       # consumed by the index_maps
    _attend_tile(qpos_ref[pl.program_id(0)], q_ref, k_ref, v_ref, pos_ref,
                 o_ref, *scratch, **kw)


def _scratch(n_kv: int, group: int, dv: int):
    return [pltpu.VMEM((n_kv, group, 1), jnp.float32),    # running max m
            pltpu.VMEM((n_kv, group, 1), jnp.float32),    # running denom l
            pltpu.VMEM((n_kv, group, dv), jnp.float32)]   # accumulator


def decode_attention_pallas(q: jnp.ndarray, k_cache: jnp.ndarray,
                            v_cache: jnp.ndarray, pos: jnp.ndarray,
                            q_pos: jnp.ndarray, *,
                            scale: Optional[float] = None,
                            window: Optional[int] = None,
                            block_k: int = 128,
                            interpret: bool = True) -> jnp.ndarray:
    """q (B, 1, H, D); k_cache/v_cache (B, W, Hkv, D); pos (B, W) absolute
    positions per cache slot (-1 = empty); q_pos (B,) current positions.
    Returns (B, 1, H, Dv)."""
    B, S1, H, D = q.shape
    assert S1 == 1, "decode kernel is single-token"
    _, W, Hkv, Dv = v_cache.shape
    group = H // Hkv
    if scale is None:
        scale = D ** -0.5

    bk = min(block_k, max(W, 8))
    pad = (-W) % bk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    Wp = k_cache.shape[1]

    kernel = functools.partial(_dense_kernel, scale=scale, window=window,
                               n_kv=Hkv, d=D, dv=Dv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Wp // bk),
        in_specs=[
            pl.BlockSpec((1, Hkv, group, D), lambda b, j, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, bk, Hkv * D), lambda b, j, qp: (b, j, 0)),
            pl.BlockSpec((1, bk, Hkv * Dv), lambda b, j, qp: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, j, qp: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, group, Dv),
                               lambda b, j, qp: (b, 0, 0, 0)),
        scratch_shapes=_scratch(Hkv, group, Dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dv), q.dtype),
        interpret=interpret,
    )(q_pos.astype(jnp.int32), q.reshape(B, Hkv, group, D),
      k_cache.reshape(B, Wp, Hkv * D), v_cache.reshape(B, Wp, Hkv * Dv),
      pos.astype(jnp.int32).reshape(B, 1, Wp))
    return out.reshape(B, 1, H, Dv)


def paged_decode_attention_pallas(q: jnp.ndarray, k_pool: jnp.ndarray,
                                  v_pool: jnp.ndarray, pos_pool: jnp.ndarray,
                                  block_table: jnp.ndarray,
                                  q_pos: jnp.ndarray, *,
                                  scale: Optional[float] = None,
                                  interpret: bool = True) -> jnp.ndarray:
    """Paged decode attention: q (B, 1, H, D); pools (P, bs, Hkv, D[v]) of
    fixed-size KV blocks; pos_pool (P, bs) absolute positions per pool slot
    (-1 = empty); block_table (B, nb) physical block per logical block;
    q_pos (B,). Returns (B, 1, H, Dv).

    The grid's kv axis walks a sequence's *logical* blocks; the table rides
    in as a scalar-prefetch operand (`pltpu.PrefetchScalarGridSpec`) so the
    index_maps — which run ahead of the kernel body to schedule DMA — redirect
    each fetch to the physical pool block. The k repeats of one prompt stream
    their shared prefix blocks from the same HBM locations, and no dense
    (B, W) copy of the cache is ever materialized. Math is identical to
    `decode_attention_pallas`."""
    B, S1, H, D = q.shape
    assert S1 == 1, "decode kernel is single-token"
    P, bs, Hkv, Dv = v_pool.shape
    nb = block_table.shape[1]
    group = H // Hkv
    if scale is None:
        scale = D ** -0.5

    kernel = functools.partial(_paged_kernel, scale=scale, window=None,
                               n_kv=Hkv, d=D, dv=Dv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nb),
        in_specs=[
            pl.BlockSpec((1, Hkv, group, D),
                         lambda b, j, tab, qp: (b, 0, 0, 0)),
            pl.BlockSpec((1, bs, Hkv * D),
                         lambda b, j, tab, qp: (tab[b, j], 0, 0)),
            pl.BlockSpec((1, bs, Hkv * Dv),
                         lambda b, j, tab, qp: (tab[b, j], 0, 0)),
            pl.BlockSpec((1, 1, bs), lambda b, j, tab, qp: (tab[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, group, Dv),
                               lambda b, j, tab, qp: (b, 0, 0, 0)),
        scratch_shapes=_scratch(Hkv, group, Dv),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, Dv), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), q_pos.astype(jnp.int32),
      q.reshape(B, Hkv, group, D), k_pool.reshape(P, bs, Hkv * D),
      v_pool.reshape(P, bs, Hkv * Dv),
      pos_pool.astype(jnp.int32).reshape(P, 1, bs))
    return out.reshape(B, 1, H, Dv)
