"""KV / state cache construction.

Cache kinds per sub-layer:
  * GQA attention:  k, v  (B, W, n_kv, hd) + per-slot absolute positions (B, W)
  * MLA attention:  c_kv (B, W, kv_lora) + k_rope (B, W, rope_hd) + pos (B, W)
  * Mamba-2 (SSM):  ssm state (B, H, P, N) f32 + conv tail (B, d_conv-1, Ch)

W = min(cache_len, cfg.attn_window or cache_len): a windowed arch never allocates
more than `window` slots — this is what makes long_500k decode sub-quadratic for
the sliding-window variants (DESIGN.md §4).

``spec_only=True`` mirrors the allocation with ShapeDtypeStructs for the dry-run.

Paged layout (``paged=PagedLayout(...)``): attention entries become block
*pools* — GQA k/v ``(n_blocks, block_size, n_kv, hd)``, or MLA's latent
``c_kv (n_blocks, block_size, kv_lora)`` + ``k_rope (n_blocks, block_size,
rope_hd)``, plus per-slot positions ``(n_blocks, block_size)`` — addressed
through a per-sequence block table the
serving backend builds (`repro.serving.backend.BlockAllocator`). One logical
block id addresses the same slot in every layer's pool, so a single table
serves the whole stack, and the k repeated samples of one prompt can share
physical prefix blocks (prefill once, copy-on-write at the first divergent
token). Paged caches are supported for pure-attention (GQA or MLA) stacks
without a sliding window (`paged_supported`); everything else keeps the
dense layout.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig


@dataclass(frozen=True)
class PagedLayout:
    """Physical geometry of a paged KV cache: ``n_blocks`` fixed-size blocks
    of ``block_size`` token slots, shared by every attention layer."""
    n_blocks: int
    block_size: int


def paged_supported(cfg: ArchConfig) -> bool:
    """Paged KV caching covers the ring-free attention case: every mixer is
    attention (GQA k/v or MLA latent pools), no sliding window (the ring
    buffer's slot recycling conflicts with block-granular sharing), no
    cross-attention conditioning memory riding in the cache."""
    return (all(m == "a" for m in cfg.pattern)
            and cfg.attn_window is None
            and not cfg.cross_attention)


def n_prefix_layers(cfg: ArchConfig) -> int:
    """Leading non-uniform layers excluded from the scan (e.g. deepseek's first
    dense layer before the MoE stack)."""
    if cfg.moe is not None and cfg.moe.first_dense:
        return cfg.moe.first_dense
    return 0


def n_scanned_super_blocks(cfg: ArchConfig) -> int:
    period = len(cfg.pattern)
    rest = cfg.n_layers - n_prefix_layers(cfg)
    assert rest % period == 0, (cfg.name, rest, period)
    return rest // period


def _attn_entry(cfg: ArchConfig, batch: int, cache_len: int, dtype, spec_only: bool,
                paged: Optional[PagedLayout] = None, kv_dtype=None):
    if paged is not None:
        el_dtype = dtype if kv_dtype is None else kv_dtype
        nb, bs = paged.n_blocks, paged.block_size
        if cfg.mla is not None:
            if kv_dtype is not None:
                raise ValueError("the latent (MLA) pool is stored in the "
                                 "model dtype; kv_dtype is for GQA pools")
            shapes = {
                "c_kv": ((nb, bs, cfg.mla.kv_lora_rank), dtype),
                "k_rope": ((nb, bs, cfg.mla.qk_rope_head_dim), dtype),
                "pos": ((nb, bs), jnp.int32),
            }
        else:
            shapes = {
                "k": ((nb, bs, cfg.n_kv_heads, cfg.hd), el_dtype),
                "v": ((nb, bs, cfg.n_kv_heads, cfg.hd), el_dtype),
                "pos": ((nb, bs), jnp.int32),
            }
        if el_dtype == jnp.int8:
            # int8 KV: per-(block, slot, kv-head) dequant scales
            shapes["k_scale"] = ((paged.n_blocks, paged.block_size,
                                  cfg.n_kv_heads), jnp.float32)
            shapes["v_scale"] = ((paged.n_blocks, paged.block_size,
                                  cfg.n_kv_heads), jnp.float32)
        if spec_only:
            return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
        return {k: (jnp.full(s, -1, d) if k == "pos" else jnp.zeros(s, d))
                for k, (s, d) in shapes.items()}
    if kv_dtype is not None:
        raise ValueError("kv_dtype (quantized KV) requires the paged layout")
    W = min(cache_len, cfg.attn_window) if cfg.attn_window else cache_len
    if cfg.mla is not None:
        m = cfg.mla
        shapes = {
            "c_kv": ((batch, W, m.kv_lora_rank), dtype),
            "k_rope": ((batch, W, m.qk_rope_head_dim), dtype),
            "pos": ((batch, W), jnp.int32),
        }
    else:
        shapes = {
            "k": ((batch, W, cfg.n_kv_heads, cfg.hd), dtype),
            "v": ((batch, W, cfg.n_kv_heads, cfg.hd), dtype),
            "pos": ((batch, W), jnp.int32),
        }
    if cfg.cross_attention and cfg.cross_kv_cache:
        shapes["xk"] = ((batch, cfg.n_cond_tokens, cfg.n_heads, cfg.hd), dtype)
        shapes["xv"] = ((batch, cfg.n_cond_tokens, cfg.n_heads, cfg.hd), dtype)
    if spec_only:
        return {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    out = {}
    for k, (s, d) in shapes.items():
        out[k] = jnp.full(s, -1, d) if k == "pos" else jnp.zeros(s, d)
    return out


def _ssm_entry(cfg: ArchConfig, batch: int, dtype, spec_only: bool):
    s = cfg.ssm
    conv_ch = cfg.d_inner + 2 * s.n_groups * s.d_state
    shapes = {
        "ssm": ((batch, cfg.ssm_heads, s.headdim, s.d_state), jnp.float32),
        "conv": ((batch, s.d_conv - 1, conv_ch), dtype),
    }
    if spec_only:
        return {k: jax.ShapeDtypeStruct(sh, d) for k, (sh, d) in shapes.items()}
    return {k: jnp.zeros(sh, d) for k, (sh, d) in shapes.items()}


def _entry(cfg: ArchConfig, mixer: str, batch: int, cache_len: int, dtype,
           spec_only: bool, paged: Optional[PagedLayout] = None, kv_dtype=None):
    if mixer == "a":
        return _attn_entry(cfg, batch, cache_len, dtype, spec_only, paged,
                           kv_dtype)
    return _ssm_entry(cfg, batch, dtype, spec_only)


def _super_block_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype,
                       spec_only: bool,
                       paged: Optional[PagedLayout] = None,
                       kv_dtype=None) -> Dict:
    return {f"l{i}": _entry(cfg, mixer, batch, cache_len, dtype, spec_only,
                            paged, kv_dtype)
            for i, mixer in enumerate(cfg.pattern)}


def _stack(tree, n: int, spec_only: bool):
    if spec_only:
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape).copy(), tree)


def make_cache(cfg: ArchConfig, batch: int, cache_len: int, dtype=jnp.bfloat16,
               spec_only: bool = False,
               paged: Optional[PagedLayout] = None, kv_dtype=None) -> Dict:
    """Full-model cache: {"prefix": [...], "blocks": (n_scanned, ...) stacked}.

    With ``paged`` the attention entries become block pools (see module
    docstring); ``batch``/``cache_len`` are then ignored — capacity lives in
    the block table the caller maintains. ``kv_dtype=jnp.int8`` stores paged
    k/v quantized (symmetric per-slot-per-head, scales in ``k_scale`` /
    ``v_scale``), halving pool bytes per token slot.
    """
    if paged is not None and not paged_supported(cfg):
        raise ValueError(f"paged KV cache unsupported for arch {cfg.name!r} "
                         "(needs all-attention pattern, no window, "
                         "no cross-attention)")
    period = len(cfg.pattern)
    prefix = [
        _entry(cfg, cfg.pattern[i % period], batch, cache_len, dtype,
               spec_only, paged, kv_dtype)
        for i in range(n_prefix_layers(cfg))
    ]
    blocks = _stack(_super_block_cache(cfg, batch, cache_len, dtype, spec_only,
                                       paged, kv_dtype),
                    n_scanned_super_blocks(cfg), spec_only)
    return {"prefix": prefix, "blocks": blocks}


def copy_cache_blocks(cache: Dict, src: jnp.ndarray, dst: jnp.ndarray) -> Dict:
    """Physically copy pool blocks ``src[i] -> dst[i]`` in every attention
    pool: the copy-on-write fan-out of a shared, partially-filled prefix
    block (each repeated sample of a prompt gets a private copy of the block
    its first divergent token will land in). Only valid on paged caches
    (every entry is a GQA or latent pool)."""
    def cp(entry: Dict, stacked: bool) -> Dict:
        # copy every pool leaf present — int8 pools carry k_scale/v_scale too
        out = dict(entry)
        for key, leaf in entry.items():
            out[key] = (leaf.at[:, dst].set(leaf[:, src]) if stacked
                        else leaf.at[dst].set(leaf[src]))
        return out

    return {"prefix": [cp(e, False) for e in cache["prefix"]],
            "blocks": {name: cp(e, True)
                       for name, e in cache["blocks"].items()}}


def reset_cache_block_positions(cache: Dict, gids: jnp.ndarray) -> Dict:
    """Invalidate the position slots of blocks ``gids`` in every attention
    pool. A resident pooled cache outlives batches, so a block returning
    from the free list still carries its previous occupant's positions —
    and a stale slot whose old position falls inside the new sequence's
    visible window would leak stale KV into attention (a partially filled
    tail block leaves exactly such slots). Only ``pos`` needs resetting:
    position ``-1`` masks the slot, so stale k/v bytes are unreachable."""
    def rp(entry: Dict, stacked: bool) -> Dict:
        out = dict(entry)
        pos = entry["pos"]
        out["pos"] = (pos.at[:, gids].set(-1) if stacked
                      else pos.at[gids].set(-1))
        return out

    return {"prefix": [rp(e, False) for e in cache["prefix"]],
            "blocks": {name: rp(e, True)
                       for name, e in cache["blocks"].items()}}


def kv_bytes_per_token(cfg: ArchConfig, bytes_per_el: int = 2) -> int:
    """KV-cache bytes one token position occupies across the whole stack
    (k + v + int32 position, summed over attention layers) — the unit that
    maps slot/block counts to real memory."""
    period = len(cfg.pattern)
    n_attn = sum(1 for i in range(cfg.n_layers)
                 if cfg.pattern[i % period] == "a")
    if cfg.mla is not None:
        per_layer = (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim) \
            * bytes_per_el + 4
    else:
        per_layer = 2 * cfg.n_kv_heads * cfg.hd * bytes_per_el + 4
    return n_attn * per_layer


def paged_cache_bytes(cfg: ArchConfig, n_blocks: int, block_size: int,
                      bytes_per_el: int = 2) -> int:
    """Real memory of a paged pool: the block budget the serving admission
    control prices requests against."""
    return n_blocks * block_size * kv_bytes_per_token(cfg, bytes_per_el)


def prefix_pool_bytes(cfg: ArchConfig, n_resident: int, block_size: int,
                      bytes_per_el: int = 2) -> int:
    """Bytes of cached KV the resident prefix pool currently indexes
    (`repro.serving.prefix_pool.PrefixPool.blocks_resident` blocks). The
    physical array is `paged_cache_bytes` of the whole budget regardless —
    this prices what the trie's residency is *worth*: prefill bytes the next
    hit on each chain does not have to move."""
    return n_resident * block_size * kv_bytes_per_token(cfg, bytes_per_el)


def cache_bytes(cfg: ArchConfig, batch: int, cache_len: int,
                bytes_per_el: int = 2) -> int:
    """Analytic cache size (used by the orchestrator's memory constraint)."""
    specs = make_cache(cfg, batch, cache_len, spec_only=True)
    total = 0
    for leaf in jax.tree.leaves(specs):
        el = 4 if leaf.dtype in (jnp.int32, jnp.float32) else bytes_per_el
        n = 1
        for s in leaf.shape:
            n *= s
        total += n * el
    return total
