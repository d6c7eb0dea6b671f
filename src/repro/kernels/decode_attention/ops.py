"""Jitted wrappers for decode attention over the dense ring cache and over the
paged block pools (the serving hot path), and for absorbed MLA decode over
the paged latent pools. All take positions, not masks: ``pos`` per cache
slot and ``q_pos`` per sequence."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention import paged_latent
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref,
                                                paged_latent_decode_ref)
from repro.kernels.platform import interpret_mode
from repro.obs.profiling import kernel_scope


@functools.partial(jax.jit, static_argnames=("scale", "window", "block_k"))
def decode_attention_cache(q, k_cache, v_cache, pos, q_pos, *,
                           scale: Optional[float] = None,
                           window: Optional[int] = None,
                           block_k: int = 128) -> jnp.ndarray:
    with kernel_scope("decode_attention"):
        return decode_attention_pallas(q, k_cache, v_cache, pos, q_pos,
                                       scale=scale, window=window,
                                       block_k=block_k,
                                       interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("scale",))
def paged_decode_attention(q, k_pool, v_pool, pos_pool, block_table, q_pos, *,
                           scale: Optional[float] = None) -> jnp.ndarray:
    """Paged (block-table) decode attention over fixed-size KV pools — the
    serving hot path when the backend runs a paged cache."""
    with kernel_scope("paged_decode_attention"):
        return paged_decode_attention_pallas(q, k_pool, v_pool, pos_pool,
                                             block_table, q_pos, scale=scale,
                                             interpret=interpret_mode())


def paged_latent_pool_view(c_kv, k_rope, pos, block_table):
    """Stacks of paged latent pools (L, P, bs, ...) as the paged latent
    decode kernel reads them (`latent_pool_view`): made once for a decode
    step's layers."""
    with kernel_scope("paged_latent_decode"):
        return paged_latent.latent_pool_view(
            c_kv, k_rope, pos, block_table, chunk=paged_latent.CHUNK_BLOCKS)


def paged_latent_decode_attention(ql, qr, c_new, kr_new, view, layer,
                                  block_table, q_pos, *,
                                  scale: float) -> jnp.ndarray:
    """Absorbed MLA decode over layer ``layer`` of a `paged_latent_pool_
    view` of the pools before the step wrote its token (``c_new``,
    ``kr_new``), read in place through the block table: the latent context
    (B, H, r)."""
    with kernel_scope("paged_latent_decode"):
        return paged_latent.paged_latent_decode_pallas(
            ql, qr, c_new, kr_new, view, layer, block_table, q_pos,
            scale=scale, chunk=paged_latent.CHUNK_BLOCKS,
            interpret=interpret_mode())


__all__ = ["decode_attention_cache", "decode_attention_ref",
           "paged_decode_attention", "paged_decode_attention_ref",
           "paged_latent_decode_attention", "paged_latent_decode_ref",
           "paged_latent_pool_view"]
