"""Per-architecture smoke tests (deliverable f).

Each assigned architecture instantiates its REDUCED variant (<=2 super-block
periods, d_model<=256, <=4 experts) and runs one forward pass and one train step
on CPU, asserting output shapes and the absence of NaNs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import Model
from repro.training import AdamWConfig, init_opt_state, make_train_step


def _make_batch(cfg, B=2, S=16, rng=None):
    rng = rng or np.random.default_rng(0)
    shape = (B, S, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B, S)
    toks = rng.integers(0, cfg.vocab_size, size=shape).astype(np.int32)
    batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    if cfg.frontend == "vision":
        batch["vision_embeds"] = jnp.ones((B, 4, cfg.d_model), jnp.float32) * 0.01
    if cfg.cross_attention:
        batch["cond_memory"] = jnp.ones((B, 8, cfg.d_model), jnp.float32) * 0.01
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_forward_and_shapes(arch):
    cfg = get_config(arch).reduced()
    assert cfg.d_model <= 256
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    batch = _make_batch(cfg)
    logits, _, aux = model.forward(params, batch)
    B, S = batch["tokens"].shape[:2]
    if cfg.n_codebooks > 1:
        assert logits.shape == (B, S, cfg.n_codebooks, cfg.vocab_size)
    else:
        assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), f"{arch}: NaN logits"
    assert not bool(jnp.isnan(aux)), f"{arch}: NaN aux loss"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_train_step(arch):
    cfg = get_config(arch).reduced()
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    opt_state = init_opt_state(params)
    step = jax.jit(make_train_step(model, AdamWConfig(lr=1e-3, warmup_steps=1,
                                                      total_steps=10)))
    batch = _make_batch(cfg)
    params2, opt_state, metrics = step(params, opt_state, batch)
    assert np.isfinite(float(metrics["loss"])), f"{arch}: non-finite loss"
    assert np.isfinite(float(metrics["grad_norm"]))
    # parameters actually moved
    moved = any(
        bool(jnp.any(a != b))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(params2)))
    assert moved, f"{arch}: train step did not update parameters"


@pytest.mark.parametrize("arch", ["deepseek-v2-lite-16b", "chatglm3-6b",
                                  "qwen2-vl-7b", "musicgen-medium",
                                  "granite-moe-3b-a800m"])
def test_reduced_decode_step(arch):
    """serve_step: prefill then one decode token, shapes + finiteness."""
    cfg = get_config(arch).reduced()
    if cfg.moe:  # avoid capacity-drop nondeterminism in the smoke check
        cfg = cfg.with_overrides(
            moe=cfg.moe.__class__(**{**cfg.moe.__dict__,
                                     "capacity_factor": 8.0}))
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    B, S = 2, 16
    batch = _make_batch(cfg, B, S)
    cache = model.init_cache(B, S + 4)
    logits, cache, _ = model.forward(params, batch, cache)
    nt_shape = (B, 1, cfg.n_codebooks) if cfg.n_codebooks > 1 else (B, 1)
    nt = jnp.zeros(nt_shape, jnp.int32)
    pos = jnp.full((B, 1), S, jnp.int32)
    if cfg.mrope_sections:
        pos = jnp.broadcast_to(pos[..., None], (B, 1, 3))
    b2 = {"tokens": nt, "positions": pos}
    if cfg.cross_attention:
        b2["cond_memory"] = batch["cond_memory"]
    ld, cache2, _ = model.forward(params, b2, cache)
    assert ld.shape[1] == 1
    assert not bool(jnp.isnan(ld).any())


def test_full_configs_match_assignment_sheet():
    sheet = {
        # published config.json: dense intermediate_size 10944 (layer 0);
        # the experts' moe_intermediate_size 1408 is pinned below
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "qwen2-vl-7b": (28, 3584, 28, 4, 18944, 152064),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
        "yi-34b": (60, 7168, 56, 8, 20480, 64000),
        "mamba2-370m": (48, 1024, 1, 1, 0, 50280),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "deepseek-coder-33b": (62, 7168, 56, 8, 19200, 32256),
        "granite-moe-3b-a800m": (32, 1536, 24, 8, 512, 49155),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
    }
    for arch, (L, d, H, kv, ff, V) in sheet.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, H, kv, ff, V), arch
    ds = get_config("deepseek-v2-lite-16b")
    assert (ds.expert_ff(), ds.norm_eps) == (1408, 1e-6)


def test_param_counts_near_nameplate():
    targets = {"deepseek-v2-lite-16b": 16e9, "chatglm3-6b": 6e9,
               "qwen2-vl-7b": 7.6e9, "jamba-v0.1-52b": 52e9,
               "yi-34b": 34e9, "mamba2-370m": 0.37e9, "qwen2-72b": 72e9,
               "deepseek-coder-33b": 33e9, "granite-moe-3b-a800m": 3.4e9,
               "musicgen-medium": 1.8e9}
    for arch, target in targets.items():
        n = Model(get_config(arch)).param_count()
        assert 0.8 * target < n < 1.25 * target, (arch, n, target)
