"""Decoder blocks: pre-norm transformer / mamba / hybrid super-blocks.

A *super-block* is one period of ``cfg.pattern`` (e.g. jamba's 7 mamba + 1 attention
layers). The model stacks ``cfg.n_super_blocks`` identical super-blocks and scans over
them, so the lowered HLO is O(pattern length), not O(n_layers).

Per-layer FFN kind (dense MLP vs MoE) is decided by ``cfg.is_moe_layer(abs_idx)``;
because ``moe_period`` divides the pattern length for every assigned arch, the kind of
each slot is identical across super-blocks and the scan stays homogeneous.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ArchConfig
from repro.models.layers import Params, mlp, mlp_init, mlp_spec, rmsnorm, rmsnorm_init, rmsnorm_spec


def _layer_kinds(cfg: ArchConfig, n_prefix: int):
    """[(mixer_kind, ffn_kind)] for one super-block, given prefix layer count."""
    out = []
    for i, mixer in enumerate(cfg.pattern):
        ffn = "moe" if cfg.is_moe_layer(n_prefix + i) else "mlp"
        out.append((mixer, ffn))
    return out


# --------------------------------------------------------------------------- specs

def _sublayer_spec(cfg: ArchConfig, mixer: str, ffn: str, dtype) -> Params:
    d = cfg.d_model
    spec: Params = {"ln1": rmsnorm_spec(d, dtype)}
    if mixer == "a":
        spec["attn"] = attn.attn_spec(cfg, dtype)
    else:
        spec["ssm"] = ssm_mod.ssm_spec(cfg, dtype)
    if cfg.cross_attention:
        spec["ln_x"] = rmsnorm_spec(d, dtype)
        spec["cross"] = attn.cross_attn_spec(cfg, dtype)
    if ffn == "moe":
        spec["ln2"] = rmsnorm_spec(d, dtype)
        spec["moe"] = moe_mod.moe_spec(cfg, dtype)
    elif cfg.d_ff > 0:  # pure mamba blocks (d_ff == 0) have no FFN
        spec["ln2"] = rmsnorm_spec(d, dtype)
        spec["mlp"] = mlp_spec(d, cfg.d_ff, cfg.mlp_variant, dtype)
    return spec


def super_block_spec(cfg: ArchConfig, n_prefix: int, dtype) -> Params:
    return {f"l{i}": _sublayer_spec(cfg, mx, ff, dtype)
            for i, (mx, ff) in enumerate(_layer_kinds(cfg, n_prefix))}


def _sublayer_init(key, cfg: ArchConfig, mixer: str, ffn: str, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p: Params = {"ln1": rmsnorm_init(d, dtype)}
    if mixer == "a":
        p["attn"] = attn.attn_init(ks[0], cfg, dtype)
    else:
        p["ssm"] = ssm_mod.ssm_init(ks[0], cfg, dtype)
    if cfg.cross_attention:
        p["ln_x"] = rmsnorm_init(d, dtype)
        p["cross"] = attn.cross_attn_init(ks[3], cfg, dtype)
    if ffn == "moe":
        p["ln2"] = rmsnorm_init(d, dtype)
        p["moe"] = moe_mod.moe_init(ks[1], cfg, dtype)
    elif cfg.d_ff > 0:
        p["ln2"] = rmsnorm_init(d, dtype)
        p["mlp"] = mlp_init(ks[1], d, cfg.d_ff, cfg.mlp_variant, dtype)
    return p


def super_block_init(key, cfg: ArchConfig, n_prefix: int, dtype) -> Params:
    kinds = _layer_kinds(cfg, n_prefix)
    keys = jax.random.split(key, len(kinds))
    return {f"l{i}": _sublayer_init(keys[i], cfg, mx, ff, dtype)
            for i, (mx, ff) in enumerate(kinds)}


# --------------------------------------------------------------------------- forward

def sublayer_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                     positions: jnp.ndarray, mixer: str,
                     cache: Optional[Dict], memory: Optional[jnp.ndarray],
                     use_kernel: bool,
                     block_table: Optional[jnp.ndarray] = None,
                     kv_len: Optional[int] = None,
                     decode: bool = False
                     ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    aux = jnp.zeros((), jnp.float32)
    S = x.shape[1]
    # cross-attention K/V cache entries ride in the attention sub-cache; pull
    # them out before the self-attention call (which rebuilds its dict).
    cross_kv = None
    if cfg.cross_attention and cfg.cross_kv_cache and cache is not None \
            and (S == 1 or decode):
        cross_kv = (cache.get("xk"), cache.get("xv"))
    h = rmsnorm(p["ln1"], x, cfg.norm_eps)
    if mixer == "a":
        if cfg.mla is not None:
            y, new_cache = attn.mla_forward(p["attn"], cfg, h, positions, cache,
                                            absorbed_decode=cfg.mla_absorbed,
                                            use_kernel=use_kernel,
                                            block_table=block_table,
                                            kv_len=kv_len, decode=decode)
        else:
            y, new_cache = attn.gqa_forward(p["attn"], cfg, h, positions, cache,
                                            use_kernel=use_kernel,
                                            block_table=block_table,
                                            kv_len=kv_len, decode=decode)
    else:
        y, new_cache = ssm_mod.ssm_forward(p["ssm"], cfg, h, cache,
                                           use_kernel=use_kernel)
    x = x + y
    if cfg.cross_attention and (memory is not None or cross_kv is not None):
        y, kv = attn.cross_forward(p["cross"], cfg,
                                   rmsnorm(p["ln_x"], x, cfg.norm_eps),
                                   memory, cached_kv=cross_kv)
        x = x + y
        if cfg.cross_kv_cache and new_cache is not None:
            new_cache["xk"], new_cache["xv"] = kv
    if "moe" in p:
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        # serving (a cache is threaded through) computes every routed pair;
        # training keeps capacity-based dispatch
        y, aux = moe_mod.moe_forward(p["moe"], cfg, h, use_kernel=use_kernel,
                                     dropless=cache is not None)
        x = x + y
    elif "mlp" in p:
        h = rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + mlp(p["mlp"], h, cfg.mlp_variant)
    return x, new_cache, aux


def super_block_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                        positions: jnp.ndarray,
                        cache: Optional[Dict], memory: Optional[jnp.ndarray],
                        use_kernel: bool,
                        block_table: Optional[jnp.ndarray] = None,
                        kv_len: Optional[int] = None,
                        decode: bool = False
                        ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
    """One period of the layer pattern. cache is {"l{i}": sub-cache} or None."""
    aux_total = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is not None else None
    for i, mixer in enumerate(cfg.pattern):
        key = f"l{i}"
        sub_cache = cache.get(key) if cache is not None else None
        x, nc, aux = sublayer_forward(p[key], cfg, x, positions, mixer,
                                      sub_cache, memory, use_kernel,
                                      block_table=block_table, kv_len=kv_len,
                                      decode=decode)
        if new_cache is not None:
            new_cache[key] = nc
        aux_total = aux_total + aux
    return x, new_cache, aux_total
