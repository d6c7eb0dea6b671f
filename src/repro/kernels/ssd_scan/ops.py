"""Jitted wrapper bridging the model layout (B, nc, Q, H, ...) to the kernel
layout (B*H, nc, Q, ...)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.platform import interpret_mode
from repro.kernels.ssd_scan.ref import ssd_chunk_ref
from repro.kernels.ssd_scan.ssd_scan import ssd_chunk_pallas
from repro.obs.profiling import kernel_scope


def ssd_chunk(xc, dtc, dA, dA_cs, Bc, Cc):
    """Model layout: xc (B,nc,Q,H,P); dtc/dA/dA_cs (B,nc,Q,H);
    Bc/Cc (B,nc,Q,H,N). Returns (Y_diag (B,nc,Q,H,P), states (B,nc,H,P,N))."""
    B, nc, Q, H, P = xc.shape
    N = Bc.shape[-1]

    def to_bh(a, width):
        a = jnp.moveaxis(a, 3, 1)                 # (B,H,nc,Q,...)
        return a.reshape((B * H, nc, Q, width))

    x_k = to_bh(xc, P)
    dt_k = to_bh(dtc[..., None], 1)
    dA_k = to_bh(dA[..., None], 1)
    cs_k = to_bh(dA_cs[..., None], 1)
    b_k = to_bh(Bc, N)
    c_k = to_bh(Cc, N)

    with kernel_scope("ssd_scan"):
        y, st = ssd_chunk_pallas(x_k, dt_k, dA_k, cs_k, b_k, c_k,
                                 interpret=interpret_mode())
    y = jnp.moveaxis(y.reshape(B, H, nc, Q, P), 1, 3)        # (B,nc,Q,H,P)
    st = st.reshape(B, H, nc, P, N).transpose(0, 2, 1, 3, 4)  # (B,nc,H,P,N)
    return y, st


__all__ = ["ssd_chunk", "ssd_chunk_ref"]
