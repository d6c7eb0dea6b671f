"""Unified model: config -> params / forward / prefill / decode.

One class serves all ten assigned architectures. The decoder stack is a
``jax.lax.scan`` over stacked super-block parameters (HLO size independent of depth);
non-uniform leading layers (deepseek's first dense layer) are unrolled as "prefix"
layers.

Modes:
  * train:   ``forward(params, batch)`` — full causal sequence, no cache.
  * prefill: ``forward(params, batch, cache=fresh_cache)`` — fills the cache.
  * decode:  ``forward(params, batch, cache=cache)`` with S==1 — serve_step.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import blocks as blk
from repro.models import cache as cache_mod
from repro.models.config import ArchConfig
from repro.models.layers import (embed, embed_init, embed_spec, lm_head,
                                 lm_head_init, lm_head_spec, rmsnorm,
                                 rmsnorm_init, rmsnorm_spec)


class Model:
    def __init__(self, cfg: ArchConfig, dtype=jnp.bfloat16,
                 remat: bool = False, use_kernel: bool = False):
        self.cfg = cfg
        self.dtype = dtype
        self.remat = remat
        self.use_kernel = use_kernel

    # ------------------------------------------------------------------ params
    def _prefix_kinds(self):
        cfg = self.cfg
        period = len(cfg.pattern)
        return [(cfg.pattern[i % period],
                 "moe" if cfg.is_moe_layer(i) else "mlp")
                for i in range(cache_mod.n_prefix_layers(cfg))]

    def param_specs(self) -> Dict:
        cfg, dt = self.cfg, self.dtype
        n_prefix = cache_mod.n_prefix_layers(cfg)
        spec = {
            "embed": embed_spec(cfg.padded_vocab, cfg.d_model, dt,
                                cfg.n_codebooks),
            "prefix": [blk._sublayer_spec(cfg, mx, ff, dt)
                       for mx, ff in self._prefix_kinds()],
            "blocks": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    (cache_mod.n_scanned_super_blocks(cfg),) + s.shape, s.dtype),
                blk.super_block_spec(cfg, n_prefix, dt)),
            "final_norm": rmsnorm_spec(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            spec["lm_head"] = lm_head_spec(cfg.d_model, cfg.padded_vocab, dt,
                                           cfg.n_codebooks)
        return spec

    def init(self, rng) -> Dict:
        """Random parameters from ``rng``. The super-blocks are built stacked
        (vmapped over their keys), never as a list and then a copy, so under
        ``jax.jit(model.init)`` the peak is about the size of the output."""
        cfg, dt = self.cfg, self.dtype
        n_prefix = cache_mod.n_prefix_layers(cfg)
        n_super = cache_mod.n_scanned_super_blocks(cfg)
        k_embed, k_blocks, k_head, k_prefix = jax.random.split(rng, 4)
        block_keys = jax.random.split(k_blocks, n_super)
        stacked = jax.vmap(
            lambda k: blk.super_block_init(k, cfg, n_prefix, dt))(block_keys)
        prefix_keys = jax.random.split(k_prefix, max(n_prefix, 1))
        params = {
            "embed": embed_init(k_embed, cfg.padded_vocab, cfg.d_model, dt,
                                cfg.n_codebooks),
            "prefix": [blk._sublayer_init(prefix_keys[i], cfg, mx, ff, dt)
                       for i, (mx, ff) in enumerate(self._prefix_kinds())],
            "blocks": stacked,
            "final_norm": rmsnorm_init(cfg.d_model, dt),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = lm_head_init(k_head, cfg.d_model,
                                             cfg.padded_vocab, dt,
                                             cfg.n_codebooks)
        return params

    def param_count(self) -> int:
        total = 0
        for leaf in jax.tree.leaves(self.param_specs()):
            n = 1
            for s in leaf.shape:
                n *= s
            total += n
        return total

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: only top-k + shared experts)."""
        cfg = self.cfg
        total = self.param_count()
        if cfg.moe is None:
            return total
        m = cfg.moe
        ff = cfg.expert_ff()
        per_expert = 3 * cfg.d_model * ff
        n_moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
        inactive = n_moe_layers * per_expert * max(m.held - m.top_k, 0)
        return total - inactive

    # ------------------------------------------------------------------ cache
    def init_cache(self, batch: int, cache_len: int, spec_only: bool = False):
        return cache_mod.make_cache(self.cfg, batch, cache_len, self.dtype,
                                    spec_only=spec_only)

    def init_paged_cache(self, n_blocks: int, block_size: int,
                         spec_only: bool = False, kv_dtype=None):
        """Block-pool cache (repro.models.cache paged layout); address it by
        passing ``batch["block_table"]`` (and a static ``kv_len``) to
        `forward`. ``kv_dtype=jnp.int8`` stores the pools quantized (half the
        bytes per token slot; scales ride alongside)."""
        return cache_mod.make_cache(
            self.cfg, 0, 0, self.dtype, spec_only=spec_only,
            paged=cache_mod.PagedLayout(n_blocks, block_size),
            kv_dtype=kv_dtype)

    # ------------------------------------------------------------------ forward
    def forward(self, params: Dict, batch: Dict,
                cache: Optional[Dict] = None,
                kv_len: Optional[int] = None,
                decode: bool = False, last_only: bool = False
                ) -> Tuple[jnp.ndarray, Optional[Dict], jnp.ndarray]:
        """Returns (logits, new_cache, aux_loss); with ``last_only`` the
        logits of the last position alone (a prefill samples from no
        other, and the head over every position of a long prompt is the
        largest buffer of its step).

        ``batch["block_table"]`` switches attention caching to the paged
        layout (prefill: one row per unique prompt; decode: one row per
        sequence); ``kv_len`` is the static logical cache length the paged
        reference path slices the gathered pools to. ``decode=True`` forces
        the cache-attending decode branches even when S > 1 — the speculative
        verify step, where S = 1 + n drafted tokens score in one forward
        (`repro.spec`).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape[:2]
        block_table = batch.get("block_table")

        positions = batch.get("positions")
        if positions is None:
            base = batch.get("position_offset", 0)
            positions = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None], (B, S)) + base
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(positions[..., None], (B, S, 3))

        h = embed(params["embed"], tokens)

        if cfg.rope_variant == "sinusoidal":  # musicgen-style additive positions
            half = cfg.d_model // 2
            freq = jnp.exp(-jnp.log(10000.0) *
                           jnp.arange(half, dtype=jnp.float32) / half)
            ang = positions[..., None].astype(jnp.float32) * freq
            h = h + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                                    axis=-1).astype(h.dtype)

        vision = batch.get("vision_embeds")
        if vision is not None and S > 1 and not decode:
            nv = min(vision.shape[1], S)
            h = h.at[:, :nv].set(vision[:, :nv].astype(h.dtype))

        memory = batch.get("cond_memory") if cfg.cross_attention else None

        aux_total = jnp.zeros((), jnp.float32)
        new_prefix = [] if cache is not None else None

        # ---- prefix layers (unrolled)
        for i, (mixer, _ffn) in enumerate(self._prefix_kinds()):
            sub_cache = cache["prefix"][i] if cache is not None else None
            h, nc, aux = blk.sublayer_forward(
                params["prefix"][i], cfg, h, positions, mixer, sub_cache,
                memory, self.use_kernel, block_table=block_table,
                kv_len=kv_len, decode=decode)
            aux_total = aux_total + aux
            if new_prefix is not None:
                new_prefix.append(nc)

        # ---- scanned super-blocks
        sb_fwd = functools.partial(blk.super_block_forward, cfg=cfg,
                                   positions=positions, memory=memory,
                                   use_kernel=self.use_kernel,
                                   block_table=block_table, kv_len=kv_len,
                                   decode=decode)
        # int8 weight stacks stay whole, read in place by the kernel
        from repro.quant.quantize import bind_int8_stacks, split_int8_stacks
        blocks, stacks = split_int8_stacks(params["blocks"])
        layers = jnp.arange(cache_mod.n_scanned_super_blocks(cfg))
        if cache is None:
            def one(bp_, x_):
                x2_, _, a_ = sb_fwd(bp_, x=x_, cache=None)
                return x2_, a_

            if self.remat:
                one = jax.checkpoint(one)

            def body(carry, inp):
                x, aux = carry
                bp, i = inp
                x2, a = one(bind_int8_stacks(bp, stacks, i), x)
                return (x2, aux + a), None

            (h, aux_s), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                                         (blocks, layers))
            new_cache = None
        else:
            # the paged latent decode kernel reads the pool stacks in place
            views = {}
            if attn_mod.paged_latent_kernel_runs(cfg, S, block_table,
                                                 cfg.mla_absorbed):
                views = attn_mod.latent_views(cache["blocks"], block_table)

            def body(carry, inp):
                x, aux = carry
                bp, i, bc = inp
                if views:
                    bc = attn_mod.bind_latent_views(bc, views, i)
                x2, nc, a = sb_fwd(bind_int8_stacks(bp, stacks, i), x=x,
                                   cache=bc)
                return (x2, aux + a), nc

            (h, aux_s), new_blocks = jax.lax.scan(
                body, (h, jnp.zeros((), jnp.float32)),
                (blocks, layers, cache["blocks"]))
            new_cache = {"prefix": new_prefix, "blocks": new_blocks}

        aux_total = aux_total + aux_s
        if last_only:
            h = h[:, -1:]
        h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
        if cfg.tie_embeddings:
            table = params["embed"]["table"]
            logits = h @ table.T if table.ndim == 2 else jnp.einsum(
                "bsd,kvd->bskv", h, table)
        else:
            logits = lm_head(params["lm_head"], h)
        if cfg.padded_vocab != cfg.vocab_size:
            # mask pad columns: exact softmax/sampling over the true vocab
            pad_mask = jnp.arange(cfg.padded_vocab) >= cfg.vocab_size
            logits = jnp.where(pad_mask, jnp.asarray(-1e9, logits.dtype),
                               logits)
        return logits, new_cache, aux_total

    # ------------------------------------------------------------------ losses
    def loss(self, params: Dict, batch: Dict) -> jnp.ndarray:
        logits, _, aux = self.forward(params, batch)
        labels = batch["labels"]
        lf = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(lf, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return nll.mean() + aux
