"""Jitted public wrapper for the flash attention kernel.

On CPU the Pallas kernel executes in interpret mode (the kernel body runs in
Python/XLA for correctness validation); on TPU the same call compiles to Mosaic
(`repro.kernels.platform.interpret_mode`).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.platform import interpret_mode
from repro.obs.profiling import kernel_scope


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    with kernel_scope("flash_attention"):
        return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                      scale=scale, block_q=block_q,
                                      block_k=block_k,
                                      interpret=interpret_mode())


__all__ = ["flash_attention", "flash_attention_ref"]
