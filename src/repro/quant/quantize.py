"""Weight-only quantization: symmetric per-channel int8 + group-wise int4.

The paper's headline IPW (1.024 at 54.8 W) comes from 4-bit Llama-3.1-8B;
before this subsystem the repo priced that as an abstract ``quant_factor``
scalar while serving bf16 weights. Here the bytes become real:

* **int8** — symmetric per-out-channel: ``scale[n] = absmax(w[:, n]) / 127``,
  ``qw = round(w / scale)`` stored as int8 ``(K, N)`` + f32 ``(N,)`` scales.
* **int4** — symmetric group-wise along the input dim: groups of
  ``group_size`` consecutive rows share ``scale[g, n] = absmax / 7``; values
  in [-7, 7] pack two-per-byte into uint8 ``(K//2, N)`` + f32 ``(G, N)``
  scales (packing convention in `repro.kernels.dequant_matmul.ref`).

A quantized dense dict replaces ``"w"`` with ``"qw"`` + ``"scale"`` (bias
rides along untouched); the format is recoverable from ``qw.dtype`` alone
(int8 vs uint8), which keeps the pytree `jax.lax.scan`-compatible — stacked
super-block leaves quantize with their leading axis intact because every
routine here operates on the trailing two dims.

`repro.models.layers.dense` dispatches on the ``"qw"`` key, so every linear
layer (attention projections, MLPs, SSM projections) serves through the
fused dequant-matmul kernel with no call-site changes.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.decomposition import Workload

# a quantized Model params pytree: same nesting as Model.init's, with every
# quantized dense dict carrying "qw" + "scale" instead of "w"
QuantizedParams = Dict[str, Any]

EPS = 1e-8
DEFAULT_GROUP_SIZE = 32
QUANT_FORMATS = ("bf16", "int8", "int4")       # serving-path formats
BYTES_PER_PARAM = {"fp32": 4.0, "fp16": 2.0, "bf16": 2.0, "fp8": 1.0,
                   "int8": 1.0, "int4": 0.5}
# dense dicts whose raw "w" is read outside `dense` (MLA absorbed decode
# reshapes these directly) — they stay full-precision
RAW_WEIGHT_KEYS = frozenset({"w_uk", "w_uv"})


def _check_format(fmt: str) -> str:
    if fmt not in QUANT_FORMATS:
        raise ValueError(f"unknown quant format {fmt!r} "
                         f"(supported: {', '.join(QUANT_FORMATS)})")
    return fmt


# ============================================================== pack / unpack

def pack_int4(q: jnp.ndarray) -> jnp.ndarray:
    """(..., K, N) ints in [-8, 7] -> (..., K//2, N) uint8; row ``r`` packs
    original row ``2r`` (low nibble) and ``2r + 1`` (high nibble)."""
    nib = q.astype(jnp.int32) & 0xF
    return (nib[..., 0::2, :] | (nib[..., 1::2, :] << 4)).astype(jnp.uint8)


def group_size_for(d_in: int, group_size: int) -> int:
    """Largest even divisor of ``d_in`` that is <= ``group_size`` — the
    group the int4 quantizer actually uses (packing needs pairs of rows)."""
    if d_in % 2:
        raise ValueError(f"int4 packing needs an even input dim (got {d_in})")
    gs = min(group_size, d_in)
    while d_in % gs or gs % 2:
        gs -= 1
    return gs


# ================================================================== quantize

def quantize_int8(w: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., K, N) float -> (qw int8 (..., K, N), scale f32 (..., N))."""
    wf = jnp.asarray(w, jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf), axis=-2), EPS) / 127.0
    q = jnp.clip(jnp.round(wf / scale[..., None, :]), -127, 127)
    return q.astype(jnp.int8), scale


def quantize_int4(w: jnp.ndarray,
                  group_size: int = DEFAULT_GROUP_SIZE
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., K, N) float -> (packed uint8 (..., K//2, N),
    scale f32 (..., G, N)) with G = K // adjusted group size."""
    wf = jnp.asarray(w, jnp.float32)
    K, N = wf.shape[-2], wf.shape[-1]
    gs = group_size_for(K, group_size)
    grouped = wf.reshape(*wf.shape[:-2], K // gs, gs, N)
    scale = jnp.maximum(jnp.max(jnp.abs(grouped), axis=-2), EPS) / 7.0
    q = jnp.clip(jnp.round(grouped / scale[..., :, None, :]), -7, 7)
    return pack_int4(q.reshape(*wf.shape[:-2], K, N)), scale


def quantize_dense(p: Dict, fmt: str,
                   group_size: int = DEFAULT_GROUP_SIZE) -> Dict:
    """Quantize one dense param dict: ``{"w", ["b"]}`` -> ``{"qw", "scale",
    ["b"]}``. The bias stays in the model dtype."""
    _check_format(fmt)
    out = {k: v for k, v in p.items() if k != "w"}
    if fmt == "int8":
        out["qw"], out["scale"] = quantize_int8(p["w"])
    elif fmt == "int4":
        out["qw"], out["scale"] = quantize_int4(p["w"], group_size)
    else:
        return dict(p)                       # bf16: identity
    return out


def dequantize_dense(p: Dict, dtype=jnp.float32) -> Dict:
    """Inverse of `quantize_dense` (lossy): ``{"qw", "scale"}`` -> ``{"w"}``.
    The reconstruction uses the same dequantize math as the matmul oracle,
    so ``dense(dequantize_dense(qp), x)`` == ``qdense(qp, x)`` bit-for-bit
    on the reference path."""
    from repro.kernels.dequant_matmul.ref import (dequantize_int4,
                                                  dequantize_int8)
    w = (dequantize_int4(p["qw"], p["scale"]) if p["qw"].dtype == jnp.uint8
         else dequantize_int8(p["qw"], p["scale"]))
    out = {k: v for k, v in p.items() if k not in ("qw", "scale")}
    out["w"] = w.astype(dtype)
    return out


def is_quantized_dense(p: Any) -> bool:
    return isinstance(p, dict) and "qw" in p


def qdense(p: Dict, x: jnp.ndarray) -> jnp.ndarray:
    """Quantized counterpart of `repro.models.layers.dense`: fused
    dequant-matmul plus the (full-precision) bias. A dict bound by
    `bind_int8_stacks` carries the whole weight stack and its ``"layer"``."""
    from repro.kernels.dequant_matmul import ops as dq_ops
    y = dq_ops.dequant_matmul(x, p["qw"], p["scale"], p.get("layer"))
    if "b" in p:
        y = y + p["b"]
    return y


def split_int8_stacks(node: Any) -> Tuple[Any, Dict]:
    """Scanned super-block params -> (the rest, the stacked int8 weights).

    A scan slices its inputs layer by layer, and XLA copies each slice of
    a weight that a kernel reads: a whole layer's int8 weights, every
    step. The ``(L, K, N)`` int8 weights therefore stay out of what the
    scan slices; `bind_int8_stacks` hands each stack whole to `qdense`
    with the layer index, and the kernel reads the layer's rows in place.
    """
    if not isinstance(node, dict):
        return node, {}
    if (is_quantized_dense(node) and node["qw"].dtype == jnp.int8
            and node["qw"].ndim == 3):
        return ({k: v for k, v in node.items() if k != "qw"},
                {"qw": node["qw"]})
    rest, stacks = {}, {}
    for k, v in node.items():
        rest[k], sub = split_int8_stacks(v)
        if sub:
            stacks[k] = sub
    return rest, stacks


def bind_int8_stacks(node: Dict, stacks: Dict, layer) -> Dict:
    """One layer's slice of `split_int8_stacks`'s rest, with each int8
    dense dict given its whole stack and ``"layer"`` (see `qdense`)."""
    if "qw" in stacks:
        return {**node, "qw": stacks["qw"], "layer": layer}
    return {**node, **{k: bind_int8_stacks(node[k], sub, layer)
                       for k, sub in stacks.items()}}


# ============================================================ whole-model API

def _walk(node: Any, fmt: str, group_size: int) -> Any:
    if isinstance(node, dict):
        if "w" in node and getattr(node["w"], "ndim", 0) >= 2:
            if fmt == "int4" and node["w"].shape[-2] % 2:
                return dict(node)            # unpackable odd input dim
            return quantize_dense(node, fmt, group_size)
        return {k: (dict(v) if isinstance(v, dict) and k in RAW_WEIGHT_KEYS
                    else _walk(v, fmt, group_size))
                for k, v in node.items()}
    if isinstance(node, (list, tuple)):
        return type(node)(_walk(v, fmt, group_size) for v in node)
    return node


def quantize_model(params: Dict, fmt: str = "int8",
                   group_size: int = DEFAULT_GROUP_SIZE) -> QuantizedParams:
    """Quantize every dense weight in a Model params tree.

    Embedding table, lm_head and norms stay full-precision (standard
    weight-only practice: they are small and quantization-sensitive), as do
    the MLA latent decompression weights the absorbed-decode path reads raw
    (`RAW_WEIGHT_KEYS`). Stacked scanned super-blocks quantize in place —
    the leading stack axis broadcasts through the per-layer math.
    """
    if _check_format(fmt) == "bf16":
        return params
    keep = {"embed", "lm_head", "final_norm"}
    return {k: (v if k in keep else _walk(v, fmt, group_size))
            for k, v in params.items()}


def dequantize_model(params: QuantizedParams, dtype=jnp.float32) -> Dict:
    """Reconstruct a full-precision params tree (lossy — quantization error
    is baked in). Used by the bit-parity tests and quality probes."""
    def walk(node: Any) -> Any:
        if is_quantized_dense(node):
            return dequantize_dense(node, dtype)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return node
    return walk(params)


# ======================================================= accounting / routing

def params_quant_format(params: Dict) -> str:
    """Recover the serving format from a params tree ("bf16" when no leaf is
    quantized) — backends stamp telemetry records with this."""
    fmt = "bf16"
    for leaf in jax.tree.leaves(params):
        if leaf.dtype == jnp.uint8:
            return "int4"
        if leaf.dtype == jnp.int8:
            fmt = "int8"
    return fmt


def param_bytes(params: Dict) -> int:
    """Actual resident weight bytes of a (possibly quantized) params tree —
    the measured side of the bytes->energy coupling."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += leaf.size * leaf.dtype.itemsize
    return total


def bytes_per_param_for(fmt: str) -> float:
    try:
        return BYTES_PER_PARAM[fmt.lower()]
    except KeyError:
        raise ValueError(f"unknown quant format {fmt!r} "
                         f"(supported: {', '.join(sorted(BYTES_PER_PARAM))})")


def quant_workload(w: Workload, fmt: str,
                   kv_format: str = "bf16") -> Workload:
    """Re-price a `Workload` for a quantized serving variant: weight bytes
    from the weight format, KV-cache bytes from the cache format — the
    knobs `repro.core.decomposition` turns into DASI/CPQ shifts and
    ``plan_costs(model="v2")`` turns into energy."""
    return dataclasses.replace(
        w, bytes_per_param=bytes_per_param_for(fmt),
        bytes_per_kv=1.0 if kv_format == "int8" else None)
