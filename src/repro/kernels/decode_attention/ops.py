"""Jitted wrappers for decode attention over the dense ring cache and over the
paged block pools (the serving hot path). Both take positions, not masks:
``pos`` per cache slot and ``q_pos`` per sequence."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import (
    decode_attention_pallas, paged_decode_attention_pallas)
from repro.kernels.decode_attention.ref import (decode_attention_ref,
                                                paged_decode_attention_ref)
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


__all__ = ["decode_attention_cache", "decode_attention_ref",
           "paged_decode_attention", "paged_decode_attention_ref"]
