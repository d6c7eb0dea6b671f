"""Primitive layers: norms, MLPs, rotary position embeddings, embeddings.

All layers are pure functions over parameter pytrees (dicts of jnp arrays). Parameter
*specs* (shape/dtype, no allocation) are produced by the matching ``*_spec`` helpers so
the multi-pod dry-run can lower models without touching device memory.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, jnp.ndarray]

# --------------------------------------------------------------------------- init utils

def _dense_spec(d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    spec = {"w": jax.ShapeDtypeStruct((d_in, d_out), dtype)}
    if bias:
        spec["b"] = jax.ShapeDtypeStruct((d_out,), dtype)
    return spec


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    scale = 1.0 / np.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    if "qw" in p:
        # weight-only quantized layer (repro.quant): fused dequant-matmul
        from repro.quant.quantize import qdense
        return qdense(p, x)
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


dense_spec = _dense_spec


def wide_dot(op, a: jnp.ndarray, b: jnp.ndarray, **kw) -> jnp.ndarray:
    """``op(a, b, **kw)`` (an einsum or a grouped matmul) on the inputs'
    own dtype with float32 accumulation. XLA's CPU runtime has no bf16 x
    bf16 -> f32 dot for some of these shapes, so there the inputs are
    widened first, which is exact; the branch is chosen when the program
    is lowered for its platform."""
    f32 = jnp.float32

    def widened(a, b):
        return op(a.astype(f32), b.astype(f32), **kw)

    def accumulated(a, b):
        return op(a, b, preferred_element_type=f32, **kw)

    if a.dtype == f32 and b.dtype == f32:
        return widened(a, b)
    return jax.lax.platform_dependent(a, b, cpu=widened, default=accumulated)


def einsum_f32(spec: str, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """``einsum(spec, a, b)`` accumulated in float32 (`wide_dot`)."""
    return wide_dot(lambda x, y, **kw: jnp.einsum(spec, x, y, **kw), a, b)


# --------------------------------------------------------------------------- norms

def rmsnorm_spec(d: int, dtype) -> Params:
    return {"scale": jax.ShapeDtypeStruct((d,), dtype)}


def rmsnorm_init(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------- MLPs

def mlp_spec(d_model: int, d_ff: int, variant: str, dtype) -> Params:
    if variant == "swiglu":
        return {
            "gate": _dense_spec(d_model, d_ff, dtype),
            "up": _dense_spec(d_model, d_ff, dtype),
            "down": _dense_spec(d_ff, d_model, dtype),
        }
    return {
        "fc_in": _dense_spec(d_model, d_ff, dtype, bias=True),
        "fc_out": _dense_spec(d_ff, d_model, dtype, bias=True),
    }


def mlp_init(key, d_model: int, d_ff: int, variant: str, dtype) -> Params:
    ks = jax.random.split(key, 3)
    if variant == "swiglu":
        return {
            "gate": dense_init(ks[0], d_model, d_ff, dtype),
            "up": dense_init(ks[1], d_model, d_ff, dtype),
            "down": dense_init(ks[2], d_ff, d_model, dtype),
        }
    return {
        "fc_in": dense_init(ks[0], d_model, d_ff, dtype, bias=True),
        "fc_out": dense_init(ks[1], d_ff, d_model, dtype, bias=True),
    }


def mlp(p: Params, x: jnp.ndarray, variant: str) -> jnp.ndarray:
    if variant == "swiglu":
        return dense(p["down"], jax.nn.silu(dense(p["gate"], x)) * dense(p["up"], x))
    return dense(p["fc_out"], jax.nn.gelu(dense(p["fc_in"], x)))


# --------------------------------------------------------------------------- RoPE

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * np.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_range(rotary_dim: int, theta: float, scaling) -> Tuple[int, int]:
    """The dimensions [low, high] over which YaRN blends interpolated into
    original frequencies: those that turn ``beta_fast`` and ``beta_slow``
    times over the original context."""
    def dim(turns):
        return (rotary_dim * np.log(scaling.original_max_position
                                    / (turns * 2 * np.pi))
                / (2 * np.log(theta)))
    low = max(int(np.floor(dim(scaling.beta_fast))), 0)
    high = min(int(np.ceil(dim(scaling.beta_slow))), rotary_dim - 1)
    return low, high


def rope_freqs(rotary_dim: int, theta: float, scaling=None) -> jnp.ndarray:
    """Inverse frequencies for half the rotary dim; with ``scaling`` (a
    `repro.models.config.RopeScaling`), YaRN's: interpolated by ``factor``
    below the correction range, original above it, linear between."""
    half = rotary_dim // 2
    if scaling is None:
        return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    base = 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) / half))
    low, high = yarn_range(rotary_dim, theta, scaling)
    ramp = np.clip((np.arange(half) - low)
                   / (high - low if high > low else 0.001), 0.0, 1.0)
    keep = 1.0 - ramp                # 1 where the original frequency stays
    return jnp.asarray(base / scaling.factor * (1.0 - keep) + base * keep,
                       jnp.float32)


def rope_mscale(scaling) -> float:
    """Factor on cos and sin (YaRN: mscale over mscale_all_dim)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               rope_fraction: float = 1.0,
               mrope_sections: Tuple[int, ...] = (),
               scaling=None) -> jnp.ndarray:
    """Rotate ``x`` (..., seq, heads, head_dim) by position embeddings.

    positions: (..., seq) int32 for standard rope, or (..., seq, 3) for M-RoPE
    (temporal/height/width coordinates, qwen2-vl style). ``scaling``: YaRN
    frequencies and cos/sin factor (`rope_freqs`, `rope_mscale`).
    """
    hd = x.shape[-1]
    rot = int(hd * rope_fraction)
    rot -= rot % 2
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv = rope_freqs(rot, theta, scaling)  # (rot/2,)

    if mrope_sections:
        assert positions.shape[-1] == 3 and sum(mrope_sections) == rot // 2
        # each frequency f uses one of the 3 position kinds (t/h/w sections)
        sec_id = np.repeat(np.arange(3), np.asarray(mrope_sections))
        pos_sel = jnp.take(positions, jnp.asarray(sec_id), axis=-1)  # (..., seq, rot/2)
        ang = pos_sel.astype(jnp.float32) * inv
    else:
        ang = positions[..., None].astype(jnp.float32) * inv  # (..., seq, rot/2)

    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    if scaling is not None:
        cos, sin = cos * rope_mscale(scaling), sin * rope_mscale(scaling)
    x1, x2 = jnp.split(x_rot.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return jnp.concatenate([y.astype(x.dtype), x_pass], axis=-1)


# --------------------------------------------------------------------------- embeddings

def embed_spec(vocab: int, d_model: int, dtype, n_codebooks: int = 1) -> Params:
    if n_codebooks > 1:
        return {"table": jax.ShapeDtypeStruct((n_codebooks, vocab, d_model), dtype)}
    return {"table": jax.ShapeDtypeStruct((vocab, d_model), dtype)}


def embed_init(key, vocab: int, d_model: int, dtype, n_codebooks: int = 1) -> Params:
    shape = (n_codebooks, vocab, d_model) if n_codebooks > 1 else (vocab, d_model)
    return {"table": (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)}


def embed(p: Params, tokens: jnp.ndarray) -> jnp.ndarray:
    """tokens: (B, S) int32, or (B, S, K) for multi-codebook (summed)."""
    table = p["table"]
    if table.ndim == 3:  # multi-codebook: sum_k table[k, tokens[...,k]]
        outs = [table[k][tokens[..., k]] for k in range(table.shape[0])]
        return sum(outs)
    return table[tokens]


def lm_head_spec(d_model: int, vocab: int, dtype, n_codebooks: int = 1) -> Params:
    if n_codebooks > 1:
        return {"w": jax.ShapeDtypeStruct((n_codebooks, d_model, vocab), dtype)}
    return {"w": jax.ShapeDtypeStruct((d_model, vocab), dtype)}


def lm_head_init(key, d_model: int, vocab: int, dtype, n_codebooks: int = 1) -> Params:
    shape = (n_codebooks, d_model, vocab) if n_codebooks > 1 else (d_model, vocab)
    scale = 1.0 / np.sqrt(d_model)
    return {"w": (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)}


def lm_head(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    w = p["w"]
    if w.ndim == 3:  # (K, D, V) -> logits (B,S,K,V)
        return jnp.einsum("bsd,kdv->bskv", x, w)
    return x @ w
