"""Attention variants: GQA (llama/qwen/yi/chatglm), MLA (DeepSeek-V2), sliding-window,
cross-attention (musicgen), with unified train / prefill / decode entry points.

The jnp reference path here is the semantics oracle; the Pallas kernels in
``repro.kernels`` implement the same math for the TPU hot path (``use_kernel`` flag in
ops wrappers selects them; on CPU the reference path runs). Absorbed MLA decode over a
paged latent pool always runs its kernel (interpreted on the CPU).

Cache layouts (see repro.models.cache):
  * GQA:    k,v            (B, S_cache, n_kv, hd)        ring-buffered when windowed
  * MLA:    c_kv           (B, S_cache, kv_lora)  + k_rope (B, S_cache, rope_hd)
  * cross:  precomputed k,v over conditioning memory (immutable)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.models.layers import (Params, apply_rope, dense, dense_init,
                                 dense_spec, einsum_f32, rmsnorm,
                                 rmsnorm_init, rmsnorm_spec, yarn_mscale)

NEG_INF = -1e30


# =============================================================================
# parameter specs / init
# =============================================================================

def attn_spec(cfg: ArchConfig, dtype) -> Params:
    if cfg.mla is not None:
        return _mla_spec(cfg, dtype)
    hd = cfg.hd
    return {
        "wq": dense_spec(cfg.d_model, cfg.n_heads * hd, dtype, bias=cfg.qkv_bias),
        "wk": dense_spec(cfg.d_model, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wv": dense_spec(cfg.d_model, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wo": dense_spec(cfg.n_heads * hd, cfg.d_model, dtype),
    }


def attn_init(key, cfg: ArchConfig, dtype) -> Params:
    if cfg.mla is not None:
        return _mla_init(key, cfg, dtype)
    hd = cfg.hd
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * hd, dtype, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.n_heads * hd, cfg.d_model, dtype),
    }


def _mla_spec(cfg: ArchConfig, dtype) -> Params:
    m = cfg.mla
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq": dense_spec(cfg.d_model, cfg.n_heads * qd, dtype),
        "w_dkv": dense_spec(cfg.d_model, m.kv_lora_rank, dtype),
        "w_krope": dense_spec(cfg.d_model, m.qk_rope_head_dim, dtype),
        "w_uk": dense_spec(m.kv_lora_rank, cfg.n_heads * m.qk_nope_head_dim, dtype),
        "w_uv": dense_spec(m.kv_lora_rank, cfg.n_heads * m.v_head_dim, dtype),
        "wo": dense_spec(cfg.n_heads * m.v_head_dim, cfg.d_model, dtype),
        "kv_norm": rmsnorm_spec(m.kv_lora_rank, dtype),
    }


def _mla_init(key, cfg: ArchConfig, dtype) -> Params:
    m = cfg.mla
    qd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.n_heads * qd, dtype),
        "w_dkv": dense_init(ks[1], cfg.d_model, m.kv_lora_rank, dtype),
        "w_krope": dense_init(ks[2], cfg.d_model, m.qk_rope_head_dim, dtype),
        "w_uk": dense_init(ks[3], m.kv_lora_rank, cfg.n_heads * m.qk_nope_head_dim, dtype),
        "w_uv": dense_init(ks[4], m.kv_lora_rank, cfg.n_heads * m.v_head_dim, dtype),
        "wo": dense_init(ks[5], cfg.n_heads * m.v_head_dim, cfg.d_model, dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
    }


def cross_attn_spec(cfg: ArchConfig, dtype) -> Params:
    hd = cfg.hd
    return {
        "wq": dense_spec(cfg.d_model, cfg.n_heads * hd, dtype),
        "wk": dense_spec(cfg.d_model, cfg.n_heads * hd, dtype),
        "wv": dense_spec(cfg.d_model, cfg.n_heads * hd, dtype),
        "wo": dense_spec(cfg.n_heads * hd, cfg.d_model, dtype),
    }


cross_attn_init = attn_init  # same structure when n_kv == n_heads


# =============================================================================
# masking / core softmax attention
# =============================================================================

def causal_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                window: Optional[int]) -> jnp.ndarray:
    """Boolean mask (..., Sq, Sk): True = attend. Supports sliding window."""
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    ok &= k_pos[..., None, :] >= 0  # left-padding uses negative positions
    if window is not None:
        ok &= k_pos[..., None, :] > q_pos[..., :, None] - window
    return ok


def sdpa(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
         mask: Optional[jnp.ndarray], scale: float) -> jnp.ndarray:
    """q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D'), GQA by head-group broadcast."""
    B, Sq, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qg = q.reshape(B, Sq, Hkv, g, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if mask is not None:
        scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, v.shape[-1]).astype(v.dtype)


# Above this many float32 score elements a call, attention over a cache runs
# its queries in blocks: a tail prefill against pooled prefixes, or a cold
# prefill of a long prompt, holds one block's scores at a time.
CACHE_SCORES = 1 << 27


def in_query_blocks(attend, per_query: int, *qs) -> jnp.ndarray:
    """``attend(*qs)``, whose arguments share (B, S) leading axes and
    whose result is (B, S, ...), over blocks of the S queries when B * S *
    ``per_query`` score elements exceed `CACHE_SCORES`; padded queries are
    computed and dropped."""
    B, S = qs[0].shape[:2]
    bq = CACHE_SCORES // (B * per_query)
    if bq >= S:
        return attend(*qs)
    bq = max(8, bq - bq % 8)
    pad = (-S) % bq
    nb = (S + pad) // bq

    def blocks(a):
        a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        return jnp.moveaxis(a.reshape(B, nb, bq, *a.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: attend(*a), tuple(blocks(a) for a in qs))
    return jnp.moveaxis(out, 0, 1).reshape(B, nb * bq,
                                           *out.shape[3:])[:, :S]


# Above this many score elements per (batch, head), causal attention switches
# to the q-blocked path: O(S * block) memory instead of O(S^2), GSPMD-safe
# (pure jnp inside lax.map — XLA shards it like any other einsum chain).
BLOCKED_THRESHOLD = 4_194_304  # 2048^2
BLOCK_Q = 512


def sdpa_causal_blocked(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        positions: jnp.ndarray, window: Optional[int],
                        scale: float, block_q: int = BLOCK_Q) -> jnp.ndarray:
    """Causal attention without materializing (Sq, Sk) scores.

    Iterates q blocks with lax.map (scan-lowered: XLA keeps one block's
    scores live at a time, and remat recomputes them on the backward pass).
    positions: (B, S) absolute positions shared by q and k.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    pad = (-S) % block_q
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        positions_q = jnp.pad(positions, ((0, 0), (0, pad)),
                              constant_values=-(10 ** 9))
    else:
        positions_q = positions
    nb = q.shape[1] // block_q
    qb = q.reshape(B, nb, block_q, Hq, D)
    pq = positions_q.reshape(B, nb, block_q)

    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def one_block(args):
        qi, pqi = args                      # (B, bq, Hq, D), (B, bq)
        qg = qi.reshape(B, block_q, Hkv, g, D).astype(jnp.float32)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) * scale
        ok = positions[:, None, :] <= pqi[:, :, None]
        ok &= positions[:, None, :] >= 0
        if window is not None:
            ok &= positions[:, None, :] > pqi[:, :, None] - window
        s = jnp.where(ok[:, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
        return o.reshape(B, block_q, Hq, vf.shape[-1])

    out = jax.lax.map(one_block, (jnp.moveaxis(qb, 1, 0),
                                  jnp.moveaxis(pq, 1, 0)))
    out = jnp.moveaxis(out, 0, 1).reshape(B, nb * block_q, Hq, vf.shape[-1])
    return out[:, :S].astype(v.dtype)


# =============================================================================
# GQA attention — train / prefill / decode
# =============================================================================

def gqa_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                positions: jnp.ndarray,
                cache: Optional[Dict] = None,
                use_kernel: bool = False,
                block_table: Optional[jnp.ndarray] = None,
                kv_len: Optional[int] = None,
                decode: bool = False) -> Tuple[jnp.ndarray,
                                               Optional[Dict]]:
    """Unified GQA attention.

    train/prefill: x (B,S,D), positions (B,S[,3]); cache None (train) or an empty
      cache dict to fill (prefill).
    decode: x (B,1,D); cache holds k/v + per-slot absolute positions; ring-buffer
      writes when cfg.attn_window is set.
    paged: with ``block_table`` (B, n_blocks) the cache entries are block
      pools (repro.models.cache paged layout); position p lives in pool block
      ``table[b, p // bs]`` row ``p % bs``. ``kv_len`` statically bounds the
      logical sequence so the gathered reference path is element-for-element
      identical to the dense cache (bit-exact parity).
    speculative verify: ``decode=True`` forces the cache-attending decode
      branches even when S > 1 — the S query tokens (last committed token +
      drafts) are scattered into the cache and each attends over every cache
      position ``<=`` its own, which both decode branches already express
      position-generically. Only the S==1 fast kernels are gated off.
    """
    B, S, _ = x.shape
    hd = cfg.hd
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)

    if cfg.rope_variant not in ("none", "sinusoidal"):
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction,
                       cfg.mrope_sections)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction,
                       cfg.mrope_sections)

    scale = 1.0 / np.sqrt(hd)
    pos1d = positions[..., 0] if positions.ndim == 3 else positions

    # Routing is static: S > 1 means train/prefill (fresh cache), S == 1 means a
    # decode step against the cache. ``decode=True`` sends S > 1 queries through
    # the cache-attending branches too: speculative verify, tail prefill
    # against pooled prefix blocks, and chunked prefill (each chunk attends
    # over the chunks already written).
    if cache is None or (S > 1 and not decode):
        # ---- train / prefill over full (possibly windowed) sequence
        if use_kernel:
            from repro.kernels.flash_attention import ops as fa_ops
            out = fa_ops.flash_attention(q, k, v, causal=True,
                                         window=cfg.attn_window, scale=scale)
        elif S * S > BLOCKED_THRESHOLD:
            out = sdpa_causal_blocked(q, k, v, pos1d, cfg.attn_window, scale)
        else:
            mask = causal_mask(pos1d, pos1d, cfg.attn_window)
            out = sdpa(q, k, v, mask, scale)
        new_cache = None
        if cache is not None:
            new_cache = (_fill_cache_paged(cache, {"k": k, "v": v}, pos1d,
                                           block_table)
                         if block_table is not None
                         else _fill_cache(cfg, cache, k, v, pos1d))
        y = dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
        return y, new_cache

    if block_table is not None:
        # ---- paged decode: write through the block table (same scatter as
        # prefill), attend over the gathered (reference) or table-indexed
        # (kernel) pools
        new_cache = _fill_cache_paged(cache, {"k": k, "v": v}, pos1d,
                                      block_table)
        ck, cv, cpos = new_cache["k"], new_cache["v"], new_cache["pos"]
        quantized = ck.dtype == jnp.int8
        if use_kernel and not quantized and S == 1:
            from repro.kernels.decode_attention import ops as da_ops
            out = da_ops.paged_decode_attention(q, ck, cv, cpos, block_table,
                                                pos1d[:, 0], scale=scale)
        else:
            # gather the sequence's blocks in logical order and slice to the
            # exact cache length: element-for-element the dense decode path
            # (int8 pools dequantize here; the table-indexed kernel reads
            # bf16 pools only, so quantized caches take this path)
            kc = ck[block_table].reshape(B, -1, *ck.shape[2:])
            vc = cv[block_table].reshape(B, -1, *cv.shape[2:])
            pc = cpos[block_table].reshape(B, -1)
            if quantized:
                ksc = new_cache["k_scale"][block_table].reshape(B, -1, ck.shape[2])
                vsc = new_cache["v_scale"][block_table].reshape(B, -1, cv.shape[2])
                kc = kc.astype(jnp.float32) * ksc[..., None]
                vc = vc.astype(jnp.float32) * vsc[..., None]
                kc, vc = kc.astype(q.dtype), vc.astype(q.dtype)
            if kv_len is not None:
                kc, vc, pc = kc[:, :kv_len], vc[:, :kv_len], pc[:, :kv_len]
            ok = (pc[:, None, :] >= 0) & (pc[:, None, :] <= pos1d[:, :, None])
            out = in_query_blocks(
                lambda qb, okb: sdpa(qb, kc, vc, okb, scale),
                cfg.n_heads * kc.shape[1], q, ok)
        y = dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
        return y, new_cache

    # ---- decode: single (or few) new tokens against the cache
    ck, cv, cpos = cache["k"], cache["v"], cache["pos"]
    W = ck.shape[1]
    slot = (pos1d % W).astype(jnp.int32)  # (B, S)
    bidx = jnp.arange(B)[:, None]
    ck = ck.at[bidx, slot].set(k)
    cv = cv.at[bidx, slot].set(v)
    cpos = cpos.at[bidx, slot].set(pos1d.astype(jnp.int32))

    if use_kernel and S == 1:
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention_cache(q, ck, cv, cpos, pos1d[:, 0],
                                            scale=scale,
                                            window=cfg.attn_window)
    else:
        # mask over cache slots by absolute position validity
        ok = (cpos[:, None, :] >= 0) & (cpos[:, None, :] <= pos1d[:, :, None])
        if cfg.attn_window is not None:
            ok &= cpos[:, None, :] > pos1d[:, :, None] - cfg.attn_window
        out = sdpa(q, ck, cv, ok, scale)
    y = dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
    return y, {"k": ck, "v": cv, "pos": cpos}


def _fill_cache(cfg: ArchConfig, cache: Dict, k, v, pos1d) -> Dict:
    """Write prefill keys/values into an allocated cache (ring for windowed).

    When S > W only the last W tokens can survive, so slice before scattering —
    this keeps scatter indices unique (``.at[].set`` with duplicates is undefined).
    """
    B, S = pos1d.shape
    ck, cv, cpos = cache["k"], cache["v"], cache["pos"]
    W = ck.shape[1]
    if S > W:
        k, v, pos1d = k[:, -W:], v[:, -W:], pos1d[:, -W:]
    slot = (pos1d % W).astype(jnp.int32)
    bidx = jnp.arange(B)[:, None]
    ck = ck.at[bidx, slot].set(k.astype(ck.dtype))
    cv = cv.at[bidx, slot].set(v.astype(cv.dtype))
    cpos = cpos.at[bidx, slot].set(pos1d.astype(jnp.int32))
    return {"k": ck, "v": cv, "pos": cpos}


def _fill_cache_paged(cache: Dict, new: Dict[str, jnp.ndarray], pos1d,
                      block_table: jnp.ndarray) -> Dict:
    """Write per-position cache entries through the block table into paged
    pools: ``new`` maps a pool's name to its (B, S, ...) values (GQA ``k``
    and ``v``; MLA ``c_kv`` and ``k_rope``), and ``pos`` is written beside.

    ``block_table`` here is the *prefill* table (one row per unique prompt):
    position p lands in pool block ``table[b, p // bs]`` row ``p % bs``.
    Every row owns distinct blocks, so scatter indices stay unique.

    int8 pools quantize on fill: each written slot stores ``round(x /
    scale)`` per kv-head with ``scale = absmax / 127`` scattered into
    ``<name>_scale`` alongside.
    """
    cpos = cache["pos"]
    bs = cpos.shape[1]
    bidx = jnp.arange(pos1d.shape[0])[:, None]
    blk = block_table[bidx, pos1d // bs]
    row = (pos1d % bs).astype(jnp.int32)
    out = dict(cache)
    for name, val in new.items():
        pool = cache[name]
        if pool.dtype == jnp.int8:
            q, scale = _quantize_kv(val)
            out[name] = pool.at[blk, row].set(q)
            out[f"{name}_scale"] = cache[f"{name}_scale"].at[blk, row].set(
                scale)
        else:
            out[name] = pool.at[blk, row].set(val.astype(pool.dtype))
    out["pos"] = cpos.at[blk, row].set(pos1d.astype(jnp.int32))
    return out


def _quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 per-(token, kv-head) quantization over the head dim:
    x (B, S, n_kv, hd) -> (q int8, scale f32 (B, S, n_kv))."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


# =============================================================================
# MLA attention (DeepSeek-V2): latent KV cache
# =============================================================================

def mla_softmax_scale(cfg: ArchConfig) -> float:
    """``q_head_dim ** -0.5``, times ``mscale(factor, mscale_all_dim) ** 2``
    under YaRN scaling."""
    m = cfg.mla
    scale = 1.0 / np.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        scale *= yarn_mscale(rs.factor, rs.mscale_all_dim) ** 2
    return float(scale)


def _latent_attend(q_nope, q_rope, qpos, cc, cr, cpos, w_uk, w_uv,
                   scale: float, window: Optional[int]) -> jnp.ndarray:
    """Absorbed attention in latent space: each query's nope part (B, S, H,
    nd) folded through ``w_uk`` (r, H, nd), plus its rope part (B, S, H,
    rd), against the latent cache ``cc`` (B, K, r) and rope keys ``cr`` (B,
    K, rd), masked by the slots' positions ``cpos`` (B, K) against the
    queries' ``qpos`` (B, S); the latent context through ``w_uv`` (r, H,
    vd). Inputs in the cache's dtype, accumulation in float32. Returns
    (B, S, H, vd) in the cache's dtype."""
    B, S, H, _ = q_nope.shape
    K = cc.shape[1]
    dt = cc.dtype

    def attend(qn, qr, qp):
        ql = einsum_f32("bshn,rhn->bshr", qn, w_uk).astype(dt)
        with jax.named_scope("mla_attend"):
            s = einsum_f32("bshr,bkr->bhsk", ql, cc)
            s = s + einsum_f32("bshr,bkr->bhsk", qr.astype(dt), cr)
            ok = (cpos[:, None, :] >= 0) & (cpos[:, None, :] <= qp[:, :, None])
            if window is not None:
                ok &= cpos[:, None, :] > qp[:, :, None] - window
            s = jnp.where(ok[:, None], s * scale, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            ctx = einsum_f32("bhsk,bkr->bshr", p.astype(dt), cc)
        return einsum_f32("bshr,rhv->bshv", ctx.astype(dt), w_uv).astype(dt)

    return in_query_blocks(attend, H * K, q_nope, q_rope, qpos)


def paged_latent_kernel_runs(cfg: ArchConfig, S: int, block_table,
                             absorbed: bool) -> bool:
    """Whether an attention call runs the paged latent decode kernel: MLA,
    one absorbed decode query a sequence over paged pools, no window."""
    return (cfg.mla is not None and block_table is not None and S == 1
            and absorbed and cfg.attn_window is None)


# Key under which a scanned layer's latent pool dict carries the kernel's
# view of the whole pool stacks and its layer index (`bind_latent_views`).
RESIDENT = "resident"


def _latent_view(pools: Dict, block_table):
    """The paged latent decode kernel's view of stacked latent pools."""
    from repro.kernels.decode_attention import ops as da_ops
    return da_ops.paged_latent_pool_view(pools["c_kv"], pools["k_rope"],
                                         pools["pos"], block_table)


def latent_views(blocks: Dict, block_table) -> Dict:
    """The kernel's view of each latent pool stack of a scanned model's
    cache (``cache["blocks"]``), made once for the step's layers: the
    kernel reads the stacks in place (a layer's slice of a pool is a scan
    temporary that XLA stages in VMEM)."""
    return {k: _latent_view(v, block_table) for k, v in blocks.items()
            if isinstance(v, dict) and "c_kv" in v}


def bind_latent_views(sub: Dict, views: Dict, layer) -> Dict:
    """One scanned super-block's slice of a paged cache, each latent pool
    dict given its stack's `latent_views` entry and ``layer``."""
    return {k: {**v, RESIDENT: (views[k], layer)} if k in views else v
            for k, v in sub.items()}


def _paged_latent_decode(q_nope, q_rope, qpos, new, view, layer,
                         block_table, w_uk, w_uv,
                         scale: float) -> jnp.ndarray:
    """`_latent_attend` of one decode query a sequence (S == 1), without a
    window, by the Pallas kernel: layer ``layer`` of the paged latent
    pools' ``view`` (before this step's write), read in place through
    ``block_table`` (no gathered copy of the context), and the token
    ``new`` the step writes at ``qpos``. Returns (B, 1, H, vd) in the
    pools' dtype."""
    from repro.kernels.decode_attention import ops as da_ops
    dt = view[0].dtype
    ql = einsum_f32("bshn,rhn->bshr", q_nope, w_uk).astype(dt)[:, 0]
    with jax.named_scope("mla_attend"):
        ctx = da_ops.paged_latent_decode_attention(
            ql, q_rope[:, 0].astype(dt), new["c_kv"][:, 0].astype(dt),
            new["k_rope"][:, 0].astype(dt), view, layer, block_table,
            qpos[:, 0], scale=scale)
    return einsum_f32("bhr,rhv->bhv", ctx, w_uv).astype(dt)[:, None]


def mla_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                positions: jnp.ndarray,
                cache: Optional[Dict] = None,
                absorbed_decode: bool = True,
                use_kernel: bool = False,
                block_table: Optional[jnp.ndarray] = None,
                kv_len: Optional[int] = None,
                decode: bool = False) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """Multi-head Latent Attention (DeepSeek-V2's published block):
    ``q = x W_q``; ``[c_kv, k_rope] = x W_kv_a`` with RMSNorm on ``c_kv``;
    ``k_nope, v = c_kv W_uk, c_kv W_uv``; rope (YaRN where configured) on
    ``q_rope`` and the one shared ``k_rope``.

    The cache stores only the normed latent ``c_kv`` (rank kv_lora) plus the
    shared rope key. Train/prefill over a fresh cache decompresses k and v.
    Every cache-attending call (decode; with ``decode=True`` also tail
    prefill against pooled prefix blocks, chunked prefill and speculative
    verify) writes its latents first, then attends in latent space
    (W_uk folded into q, W_uv applied to the latent context), so the bytes
    read per step are O(S·(kv_lora + rope_hd)) instead of O(S·2·H·hd).
    With ``block_table`` the cache entries are block pools. An absorbed
    decode step (S == 1, no window) reads them in place through the table
    (`repro.kernels.decode_attention.paged_latent`); every other
    cache-attending call gathers each sequence's blocks in logical order up
    to ``kv_len``.
    """
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    nd, rd, vd, r = (m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim,
                     m.kv_lora_rank)
    scale = mla_softmax_scale(cfg)
    pos1d = positions[..., 0] if positions.ndim == 3 else positions

    q = dense(p["wq"], x).reshape(B, S, H, nd + rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = apply_rope(q_rope, pos1d, cfg.rope_theta,
                        scaling=cfg.rope_scaling)

    c_kv = rmsnorm(p["kv_norm"], dense(p["w_dkv"], x), cfg.norm_eps)  # (B,S,r)
    k_rope = dense(p["w_krope"], x).reshape(B, S, 1, rd)
    k_rope = apply_rope(k_rope, pos1d, cfg.rope_theta,
                        scaling=cfg.rope_scaling)
    new = {"c_kv": c_kv, "k_rope": k_rope[:, :, 0]}
    resident = None
    if cache is not None and RESIDENT in cache:
        cache = dict(cache)
        resident = cache.pop(RESIDENT)

    if cache is not None and (S == 1 or decode):
        if block_table is not None:
            new_cache = _fill_cache_paged(cache, new, pos1d, block_table)
            if paged_latent_kernel_runs(cfg, S, block_table,
                                        absorbed_decode):
                view, layer = resident or (_latent_view(
                    {k: v[None] for k, v in cache.items()}, block_table), 0)
                out = _paged_latent_decode(
                    q_nope, q_rope, pos1d, new, view, layer, block_table,
                    p["w_uk"]["w"].reshape(r, H, nd),
                    p["w_uv"]["w"].reshape(r, H, vd), scale).astype(x.dtype)
                y = dense(p["wo"], out.reshape(B, S, H * vd))
                return y, new_cache
            cc = new_cache["c_kv"][block_table].reshape(B, -1, r)
            cr = new_cache["k_rope"][block_table].reshape(B, -1, rd)
            cpos = new_cache["pos"][block_table].reshape(B, -1)
            if kv_len is not None:
                cc, cr, cpos = cc[:, :kv_len], cr[:, :kv_len], cpos[:, :kv_len]
        else:
            new_cache = _fill_ring(cache, new, pos1d)
            cc, cr, cpos = (new_cache["c_kv"], new_cache["k_rope"],
                            new_cache["pos"])

        if absorbed_decode:
            out = _latent_attend(
                q_nope, q_rope, pos1d, cc, cr, cpos,
                p["w_uk"]["w"].reshape(r, H, nd),
                p["w_uv"]["w"].reshape(r, H, vd), scale,
                cfg.attn_window).astype(x.dtype)
        else:
            ok = (cpos[:, None, :] >= 0) & \
                (cpos[:, None, :] <= pos1d[:, :, None])
            if cfg.attn_window is not None:
                ok &= cpos[:, None, :] > pos1d[:, :, None] - cfg.attn_window
            k_nope = dense(p["w_uk"], cc).reshape(B, -1, H, nd)
            vv = dense(p["w_uv"], cc).reshape(B, -1, H, vd)
            k_full = jnp.concatenate(
                [k_nope, jnp.broadcast_to(cr[:, :, None],
                                          (B, cc.shape[1], H, rd))], axis=-1)
            q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
            out = sdpa(q_full, k_full, vv, ok, scale)
        y = dense(p["wo"], out.reshape(B, S, H * vd))
        return y, new_cache

    # ---- train / prefill: decompress (compute-bound, MXU-friendly)
    k_nope = dense(p["w_uk"], c_kv).reshape(B, S, H, nd)
    vv = dense(p["w_uv"], c_kv).reshape(B, S, H, vd)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], axis=-1)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    if use_kernel:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q_full, k_full, vv, causal=True,
                                     window=cfg.attn_window, scale=scale)
    elif S * S > BLOCKED_THRESHOLD:
        out = sdpa_causal_blocked(q_full, k_full, vv, pos1d,
                                  cfg.attn_window, scale)
    else:
        mask = causal_mask(pos1d, pos1d, cfg.attn_window)
        out = sdpa(q_full, k_full, vv, mask, scale)
    new_cache = None
    if cache is not None:
        new_cache = (_fill_cache_paged(cache, new, pos1d, block_table)
                     if block_table is not None
                     else _fill_ring(cache, new, pos1d))
    y = dense(p["wo"], out.reshape(B, S, H * vd))
    return y, new_cache


def _fill_ring(cache: Dict, new: Dict[str, jnp.ndarray], pos1d) -> Dict:
    """Write per-position entries into a dense (ring, when windowed) cache:
    slot ``pos % W``. When S > W only the last W positions can survive, so
    slice before scattering (duplicate scatter indices are undefined)."""
    W = cache["pos"].shape[1]
    if pos1d.shape[1] > W:
        new = {k: v[:, -W:] for k, v in new.items()}
        pos1d = pos1d[:, -W:]
    slot = (pos1d % W).astype(jnp.int32)
    bidx = jnp.arange(pos1d.shape[0])[:, None]
    out = dict(cache)
    for name, val in new.items():
        out[name] = cache[name].at[bidx, slot].set(val.astype(
            cache[name].dtype))
    out["pos"] = cache["pos"].at[bidx, slot].set(pos1d.astype(jnp.int32))
    return out


# =============================================================================
# Cross-attention (musicgen conditioning) — memory is static, cache-free
# =============================================================================

def cross_forward(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                  memory: Optional[jnp.ndarray],
                  cached_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
                  ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """Cross-attention over the static conditioning memory.

    When ``cached_kv`` is provided (decode with cfg.cross_kv_cache), the
    memory projections are skipped entirely — the conditioning sequence never
    changes across decode steps, so re-projecting it every token is pure
    waste (§Perf beyond-paper; measured on musicgen decode_32k).
    """
    B, S, _ = x.shape
    hd = cfg.hd
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    if cached_kv is not None:
        k, v = cached_kv
    else:
        Tm = memory.shape[1]
        k = dense(p["wk"], memory).reshape(B, Tm, cfg.n_heads, hd)
        v = dense(p["wv"], memory).reshape(B, Tm, cfg.n_heads, hd)
    out = sdpa(q, k, v, None, 1.0 / np.sqrt(hd))
    return dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd)), (k, v)
