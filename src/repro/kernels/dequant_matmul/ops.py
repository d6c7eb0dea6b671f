"""Jitted dispatch for the fused dequant-matmul.

Format is discriminated by the quantized-weight dtype (no side metadata, so
the dispatch survives `jax.lax.scan` over stacked per-layer params): ``int8``
means per-out-channel int8, ``uint8`` means nibble-packed group-wise int4.

On TPU the fused Pallas kernel runs (HBM moves the packed bytes); on CPU the
jnp oracle runs directly — unlike the attention ops wrappers this does *not*
interpret the kernel there, because the oracle's dequantize-then-matmul
rounding is the semantics the serving bit-parity test pins and
interpret-mode parity is covered by ``tests/test_quant.py``. Any other
backend raises (`repro.kernels.platform.interpret_mode`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dequant_matmul.dequant_matmul import (
    dequant_matmul_int4_pallas, dequant_matmul_int8_pallas)
from repro.kernels.dequant_matmul.ref import (dequant_matmul_int4_ref,
                                              dequant_matmul_int8_ref,
                                              dequantize_int4,
                                              dequantize_int8, unpack_int4)
from repro.kernels.platform import interpret_mode
from repro.obs.profiling import kernel_scope


@jax.jit
def dequant_matmul(x: jnp.ndarray, qw: jnp.ndarray, scale: jnp.ndarray,
                   layer=None) -> jnp.ndarray:
    """``x (..., K) @ dequantize(qw, scale) -> (..., N)`` in x.dtype.

    With ``layer``, ``qw`` stacks a scanned model's weights and
    ``qw[layer]`` is the one used; the int8 kernel reads it in place.
    """
    int4 = qw.dtype == jnp.uint8
    with kernel_scope("dequant_matmul"):
        if layer is not None and (int4 or interpret_mode()):
            qw, layer = qw[layer], None
        if interpret_mode():
            return (dequant_matmul_int4_ref if int4
                    else dequant_matmul_int8_ref)(x, qw, scale)
        x2 = x.reshape(-1, x.shape[-1])
        y = (dequant_matmul_int4_pallas(x2, qw, scale) if int4
             else dequant_matmul_int8_pallas(x2, qw, scale, layer))
        return y.reshape(*x.shape[:-1], y.shape[-1])


__all__ = ["dequant_matmul", "dequant_matmul_int8_pallas",
           "dequant_matmul_int4_pallas", "dequant_matmul_int8_ref",
           "dequant_matmul_int4_ref", "dequantize_int8", "dequantize_int4",
           "unpack_int4"]
