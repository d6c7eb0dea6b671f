"""Mixture-of-Experts FFN with sort-based capacity dispatch.

Why sort-based (vs. GShard one-hot einsum dispatch): the one-hot dispatch tensor is
O(tokens x experts x capacity), which is ~2e14 elements at prefill_32k on
deepseek-v2-lite. The sort-based path costs O(tokens log tokens) for routing plus the
unavoidable O(E x C x d x ff) expert compute, and shards cleanly with experts on the
"model" mesh axis (XLA inserts the all-to-all around the gather/scatter).

Load-balancing auxiliary loss (Switch-style) is returned for the training loop.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.layers import (Params, dense, dense_init, dense_spec,
                                 einsum_f32, mlp, mlp_init, mlp_spec,
                                 wide_dot)


def moe_spec(cfg: ArchConfig, dtype) -> Params:
    m = cfg.moe
    d, ff = cfg.d_model, cfg.expert_ff()
    spec = {
        # the router scores every routed expert of the model
        "router": dense_spec(d, m.n_experts, dtype),
        # stacked SwiGLU weights of the experts this device holds
        "gate": jax.ShapeDtypeStruct((m.held, d, ff), dtype),
        "up": jax.ShapeDtypeStruct((m.held, d, ff), dtype),
        "down": jax.ShapeDtypeStruct((m.held, ff, d), dtype),
    }
    if m.n_shared:
        spec["shared"] = mlp_spec(d, ff * m.n_shared, "swiglu", dtype)
    return spec


def moe_init(key, cfg: ArchConfig, dtype) -> Params:
    m = cfg.moe
    d, ff = cfg.d_model, cfg.expert_ff()
    ks = jax.random.split(key, 5)
    s_in, s_ff = 1.0 / np.sqrt(d), 1.0 / np.sqrt(ff)
    E = m.held
    p = {
        "router": dense_init(ks[0], d, m.n_experts, dtype),
        "gate": (jax.random.normal(ks[1], (E, d, ff), jnp.float32) * s_in).astype(dtype),
        "up": (jax.random.normal(ks[2], (E, d, ff), jnp.float32) * s_in).astype(dtype),
        "down": (jax.random.normal(ks[3], (E, ff, d), jnp.float32) * s_ff).astype(dtype),
    }
    if m.n_shared:
        p["shared"] = mlp_init(ks[4], d, ff * m.n_shared, "swiglu", dtype)
    return p


# Up to this many tokens the dropless layer runs every held expert over every
# token (one pass over the held weights, no dispatch); above it, grouped
# dispatch computes only the routed pairs, ROW_BLOCK tokens at a time so its
# buffers stay bounded at long prefills.
DENSE_TOKENS = 512
ROW_BLOCK = 4096


def _route(p: Params, cfg: ArchConfig, xf: jnp.ndarray):
    """float32 softmax router over all routed experts, then top-k:
    (probs (T, E), gates (T, K), expert ids (T, K))."""
    m = cfg.moe
    logits = dense(p["router"], xf.astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
    return probs, gate_vals * m.routed_scaling_factor, expert_idx


def _held_dense(p: Params, xf, gates, local) -> jnp.ndarray:
    """Every held expert over every token, weighted by its gate (zero where
    the token did not pick it): (T, d) float32."""
    E = p["gate"].shape[0]
    w = jnp.einsum("tk,tke->te", gates,
                   jax.nn.one_hot(local, E, dtype=jnp.float32))
    h = jax.nn.silu(einsum_f32("td,edf->tef", xf, p["gate"])) * \
        einsum_f32("td,edf->tef", xf, p["up"])
    h = (h * w[:, :, None]).astype(xf.dtype)
    return einsum_f32("tef,efd->td", h, p["down"])


def _held_grouped(p: Params, xf, gates, local) -> jnp.ndarray:
    """Grouped dispatch of the (token, expert) pairs whose expert is held:
    pairs sorted by expert, one grouped matmul per weight over the
    contiguous groups, combined back by gate. Pairs of experts held
    elsewhere sort last, outside every group. (T, d) float32."""
    T, d = xf.shape
    E = p["gate"].shape[0]
    K = local.shape[1]
    flat_e = local.reshape(T * K)
    order = jnp.argsort(flat_e, stable=True)
    tok = (jnp.arange(T * K, dtype=jnp.int32) // K)[order]
    sizes = jnp.bincount(flat_e, length=E + 1)[:E].astype(jnp.int32)
    xs = xf[tok]

    def grouped(a, w):
        return wide_dot(jax.lax.ragged_dot, a, w, group_sizes=sizes)

    h = jax.nn.silu(grouped(xs, p["gate"])) * grouped(xs, p["up"])
    ys = grouped(h.astype(xf.dtype), p["down"])
    live = jnp.arange(T * K) < sizes.sum()
    g = gates.reshape(T * K)[order]
    ys = jnp.where(live[:, None], ys * g[:, None], 0.0)
    return jnp.zeros((T, d), jnp.float32).at[tok].add(ys)


def held_experts(p: Params, cfg: ArchConfig, xf: jnp.ndarray,
                 gates: jnp.ndarray, expert_idx: jnp.ndarray) -> jnp.ndarray:
    """Dropless: every (token, expert) pair whose expert this device holds,
    weighted by its gate; pairs of experts held elsewhere add nothing here.
    xf (T, d) -> (T, d) float32, the held experts' part of the output."""
    m = cfg.moe
    T, d = xf.shape
    E = m.held
    local = expert_idx - m.held_offset
    local = jnp.where((local >= 0) & (local < E), local, E)
    gates = jnp.where(local < E, gates, 0.0)
    with jax.named_scope("moe_experts"):
        if T <= DENSE_TOKENS:
            return _held_dense(p, xf, gates, local)
        if T <= ROW_BLOCK:
            return _held_grouped(p, xf, gates, local)
        pad = (-T) % ROW_BLOCK
        xb = jnp.pad(xf, ((0, pad), (0, 0))).reshape(-1, ROW_BLOCK, d)
        gb = jnp.pad(gates, ((0, pad), (0, 0))).reshape(
            -1, ROW_BLOCK, gates.shape[1])
        lb = jnp.pad(local, ((0, pad), (0, 0)), constant_values=E).reshape(
            -1, ROW_BLOCK, local.shape[1])
        y = jax.lax.map(lambda a: _held_grouped(p, *a), (xb, gb, lb))
        return y.reshape(-1, d)[:T]


def moe_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                use_kernel: bool = False, dropless: bool = False
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> (y, aux_loss).

    Routing: float32 softmax router over all ``n_experts``, top-k experts per
    token, gates renormalized where ``norm_topk_prob`` and scaled by
    ``routed_scaling_factor``. Only the held experts (``n_held`` from
    ``held_offset``) are computed here; their part of the output, plus the
    shared experts, is what goes on to the next layer.

    ``dropless`` (serving): every pair whose expert is held is computed
    (`held_experts`). Otherwise (training) sort-based dispatch with
    per-expert capacity C = ceil(T*k/E * capacity_factor); overflow pairs
    drop (standard capacity-based MoE semantics).
    """
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    E, K = m.held, m.top_k
    xf = x.reshape(T, d)

    probs, gate_vals, expert_idx = _route(p, cfg, xf)
    # ---- Switch aux loss over the router's experts: E * sum_e f_e * p_e
    me = probs.mean(axis=0)
    ce = jnp.zeros((m.n_experts,), jnp.float32).at[
        expert_idx.reshape(-1)].add(1.0) / (T * K)
    aux = m.n_experts * jnp.sum(me * ce) * m.router_aux_weight

    if dropless:
        y = held_experts(p, cfg, xf, gate_vals, expert_idx)
        y = y.astype(x.dtype).reshape(B, S, d)
        if m.n_shared:
            y = y + mlp(p["shared"], x, "swiglu")
        return y, aux

    local = expert_idx - m.held_offset
    held = (local >= 0) & (local < E)

    # ---- sort-based dispatch over the held experts
    TK = T * K
    flat_e = jnp.where(held, local, E).reshape(TK)
    flat_tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    flat_gate = gate_vals.reshape(TK)

    order = jnp.argsort(flat_e, stable=True)
    e_sorted = flat_e[order]
    tok_sorted = flat_tok[order]
    gate_sorted = flat_gate[order]

    counts = jax.ops.segment_sum(jnp.ones((TK,), jnp.int32), flat_e, E + 1)
    seg_start = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                 jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(TK, dtype=jnp.int32) - seg_start[e_sorted]

    C = int(np.ceil(TK / m.n_experts * m.capacity_factor))
    C = max(C, K)  # degenerate tiny-shape guard
    keep = (pos_in_e < C) & (e_sorted < E)
    dest = jnp.where(keep, e_sorted * C + pos_in_e, E * C)       # drop slot at end

    # gather tokens into (E*C, d) buffer
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[dest].set(xf[tok_sorted])
    xe = buf[: E * C].reshape(E, C, d)

    # ---- expert compute (stacked SwiGLU); the grouped-GEMM Pallas kernel
    # covers these three einsums on TPU (repro.kernels.moe_gemm)
    if use_kernel:
        from repro.kernels.moe_gemm import ops as mg_ops
        h = jax.nn.silu(mg_ops.moe_gemm(xe, p["gate"])) * \
            mg_ops.moe_gemm(xe, p["up"])
        ye = mg_ops.moe_gemm(h, p["down"])                       # (E, C, d)
    else:
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["gate"])) * \
            jnp.einsum("ecd,edf->ecf", xe, p["up"])
        ye = jnp.einsum("ecf,efd->ecd", h, p["down"])            # (E, C, d)

    # ---- combine back
    ye_flat = jnp.concatenate([ye.reshape(E * C, d),
                               jnp.zeros((1, d), ye.dtype)])
    contrib = ye_flat[jnp.where(keep, dest, E * C)] * gate_sorted[:, None].astype(ye.dtype)
    y = jnp.zeros((T, d), jnp.float32).at[tok_sorted].add(
        contrib.astype(jnp.float32))
    y = y.astype(x.dtype).reshape(B, S, d)

    if m.n_shared:
        y = y + mlp(p["shared"], x, "swiglu")
    return y, aux
