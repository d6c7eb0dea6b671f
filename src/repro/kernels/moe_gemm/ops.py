"""Jitted wrapper for the grouped expert GEMM."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gemm.moe_gemm import moe_gemm_pallas
from repro.kernels.moe_gemm.ref import moe_gemm_ref
from repro.kernels.platform import interpret_mode
from repro.obs.profiling import kernel_scope


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d"))
def moe_gemm(x, w, *, block_c: int = 128, block_f: int = 128,
             block_d: int = 128) -> jnp.ndarray:
    with kernel_scope("moe_gemm"):
        return moe_gemm_pallas(x, w, block_c=block_c, block_f=block_f,
                               block_d=block_d, interpret=interpret_mode())


__all__ = ["moe_gemm", "moe_gemm_ref"]
