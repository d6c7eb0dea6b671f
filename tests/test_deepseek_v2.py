"""DeepSeek-V2-Lite's served path against the plain float32 reference
(`chipbench/reference/deepseek_v2.py`) at a small size on the CPU: logits
through prefill and the latent block pool, the expert shares against the
uncut layer, dropless routing, and YaRN at the published values."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
for p in (ROOT, os.path.join(ROOT, "tests", "chipbench")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import registry  # noqa: E402
from chipbench.reference import deepseek_v2 as ref  # noqa: E402
from small_deepseek import small_arch, small_config  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.decode_attention import paged_latent  # noqa: E402
from repro.models import Model  # noqa: E402
from repro.models import attention, moe  # noqa: E402
from repro.models.layers import rope_freqs, rope_mscale, yarn_range  # noqa: E402
from repro.serving import ExecutionBackend  # noqa: E402

SEED = [2 ** 31 + 5, 1]
HIGHEST = jax.lax.Precision.HIGHEST


def _conf(**sizes):
    return small_config(registry.config("deepseek-v2-lite-ep4"), **sizes)


def _program(conf, dtype):
    shape = ref.Shape(conf)
    key = ref.root_key(SEED)
    tree = jax.jit(lambda k: ref.program_tree(ref.gen_model(k, shape)))(key)
    model = Model(small_arch(conf), dtype=dtype)
    want = jax.eval_shape(model.init, key)
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert all(a.shape == b.shape for a, b in zip(jax.tree.leaves(tree),
                                                  jax.tree.leaves(want)))
    return model, jax.tree.map(lambda a: a.astype(dtype), tree)


def _serve(model, params):
    """Two prompts that share 5 blocks: the first fills the pool, the
    second hits it and fans out to 4 sampled continuations (copy-on-write
    of the shared partial block); every served token's log-probability."""
    be = ExecutionBackend(model, params, kv_blocks=128, kv_block_size=4,
                          kv_pool=True)
    rng = np.random.default_rng(0)
    pre = rng.integers(0, model.cfg.vocab_size, 22).astype(np.int32)
    items, hits = [], []
    for k in (1, 4):
        prompt = np.concatenate([pre, rng.integers(
            0, model.cfg.vocab_size, 5).astype(np.int32)])
        h = be.start_batch([prompt], k, 12, 1.0, jax.random.key(k))
        while be.decode_step(h):
            pass
        hits.append(h.pool_hit_blocks)
        toks, lps = np.stack(h.out_toks, 1), np.stack(h.out_lps, 1)
        be.release(h)
        items += [(np.concatenate([prompt, t]), len(prompt), lp)
                  for t, lp in zip(toks, lps)]
    return items, hits


# f32: the program and the reference differ only in summation order and in
# the absorbed (program) against the un-absorbed (reference) attention, a
# few ulps of each log-probability. bf16: weights are bf16 in both; the
# program rounds every activation, the latent cache and the attention
# inputs to bf16 (8 bits of mantissa), which moves log-probabilities by a
# few hundredths over three layers (0.052 measured at this seed).
TOL = {"float32": 1e-4, "bfloat16": 0.15}


@pytest.mark.parametrize("dtype,chunk", [
    pytest.param("float32", None, id="float32"),
    pytest.param("bfloat16", None, id="bfloat16"),
    # decode reads the latent pool in chunks of 2 blocks (8 positions): its
    # steps cross block boundaries (28, 32, 36) and a chunk boundary (32)
    pytest.param("float32", 2, id="float32-chunk2")])
def test_served_logits_match_the_reference(dtype, chunk, monkeypatch):
    """Every served token's log-probability, decode through the paged
    latent decode kernel, against the reference."""
    if chunk is not None:
        monkeypatch.setattr(paged_latent, "CHUNK_BLOCKS", chunk)
    kernel, traced = paged_latent.paged_latent_decode_pallas, []
    monkeypatch.setattr(paged_latent, "paged_latent_decode_pallas",
                        lambda *a, **kw: traced.append(1) or kernel(*a, **kw))
    conf = _conf()
    model, params = _program(conf, jnp.dtype(dtype))
    items, hits = _serve(model, params)
    assert hits == [0, 5]
    assert traced                 # decode attended through the kernel
    got = ref.readings(conf, SEED, [(s, f) for s, f, _ in items], "bf16")
    err = max(float(np.abs(g["lp"] - lp).max())
              for g, (_, _, lp) in zip(got, items))
    assert err < TOL[dtype], err


def test_blocked_latent_attention_matches_one_block(monkeypatch):
    """Queries in blocks (a long prefill) give the one-block result."""
    conf = _conf()
    model, params = _program(conf, jnp.float32)
    whole, _ = _serve(model, params)
    # a 27-token prefill then runs its queries in 4 blocks of 8
    monkeypatch.setattr(attention, "CACHE_SCORES", 128)
    blocked, _ = _serve(model, params)
    for (_, _, a), (_, _, b) in zip(whole, blocked):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5)


def _moe_inputs(conf, T, skew=False):
    shape = ref.Shape(conf)
    key = ref.root_key(SEED)
    w = {**ref.gen_moe(key, 1, shape)}
    x = jax.random.normal(jax.random.key(9), (T, shape.D), jnp.float32)
    if skew:
        # every token's router score is largest for expert 2
        x = jnp.abs(x)
        w["router"] = w["router"].at[:, 2].set(1.0)
    return shape, key, w, x


def _program_moe(conf, w, x, held_offset, n_held, key, shape):
    """The program's MoE layer of the share [offset, offset + n_held)."""
    c = dict(conf, n_routed_experts=n_held)
    c["expert_parallel"] = dict(c["expert_parallel"],
                                held_expert_offset=held_offset)
    sh = ref.Shape(c)
    ex = ref.gen_held(key, 1, sh)
    arch = small_arch(c)
    p = {"router": {"w": w["router"]}, "gate": ex["gate"], "up": ex["up"],
         "down": ex["down"],
         "shared": {"gate": {"w": w["sh_gate"]}, "up": {"w": w["sh_up"]},
                    "down": {"w": w["sh_down"]}}}
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    y, _ = moe.moe_forward(p, arch, x[None], dropless=True)
    return y[0]


def _reference_moe(conf, w, x, key, held):
    """The reference's layer holding ``held`` experts from 0."""
    c = dict(conf, n_routed_experts=held)
    c["expert_parallel"] = dict(c["expert_parallel"], held_expert_offset=0)
    sh = ref.Shape(c)
    wf = {k: v.astype(jnp.float32) for k, v in w.items()}
    wf["experts"] = {k: v.astype(jnp.float32)
                     for k, v in ref.gen_held(key, 1, sh).items()}

    def mm(a, b):
        return jnp.matmul(a, b, precision=HIGHEST)
    return ref.moe(wf, x, sh, mm)


@pytest.mark.parametrize("T", [40, 600])
def test_four_shares_sum_to_the_uncut_layer(T):
    """Each chip's part (its 4 of 16 experts, plus the shared experts every
    chip computes alike), summed with the shared experts counted once, is
    the uncut reference layer over all 16."""
    conf = _conf()
    shape, key, w, x = _moe_inputs(conf, T)
    with jax.default_matmul_precision("highest"):
        parts = [_program_moe(conf, w, x, 4 * i, 4, key, shape)
                 for i in range(4)]
        wf = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        shared = jnp.matmul(jax.nn.silu(x @ wf["sh_gate"]) * (x @ wf["sh_up"]),
                            wf["sh_down"])
        whole = _reference_moe(conf, w, x, key, 16)
    total = sum(parts) - 3 * shared
    # float32 sums of the same terms in another order
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("T", [40, 600, 4500])
def test_routing_skewed_onto_one_expert_drops_nothing(T):
    """Every token picks expert 2: the dense (T <= 512), grouped and
    row-blocked grouped paths compute all of them, as the reference does;
    the capacity path that training uses drops most of them."""
    conf = _conf()
    shape, key, w, x = _moe_inputs(conf, T, skew=True)
    with jax.default_matmul_precision("highest"):
        y = _program_moe(conf, w, x, 0, 4, key, shape)
        want = _reference_moe(conf, w, x, key, 4)
    # float32, same terms, another summation order
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
    if T == 40:
        arch = small_arch(dict(conf))
        p = jax.tree.map(lambda a: a.astype(jnp.float32), {
            "router": {"w": w["router"]},
            **ref.gen_held(key, 1, shape),
            "shared": {"gate": {"w": w["sh_gate"]}, "up": {"w": w["sh_up"]},
                       "down": {"w": w["sh_down"]}}})
        dropped, _ = moe.moe_forward(p, arch, x[None], dropless=False)
        assert float(jnp.abs(dropped[0] - y).max()) > 1e-2


def test_yarn_at_the_published_values():
    """DeepSeek-V2-Lite's rope_scaling: correction range [10, 23] of the 32
    frequencies, blended as the published formula (and the reference's own
    code) gives them; cos/sin times 1.0 (mscale equals mscale_all_dim); the
    softmax scale 192 ** -0.5 times mscale(40, 0.707) ** 2 = 1.26080 ** 2."""
    cfg = get_config("deepseek-v2-lite-16b-ep4")
    rs = cfg.rope_scaling
    assert yarn_range(64, cfg.rope_theta, rs) == (10, 23)
    shape = ref.Shape(registry.config("deepseek-v2-lite-ep4"))
    base = 1.0 / 10000.0 ** (np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = base / 40.0 * ramp + base * (1 - ramp)
    np.testing.assert_allclose(np.asarray(rope_freqs(64, 10000.0, rs)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.yarn_inv_freq(shape), want, rtol=1e-12)
    assert rope_mscale(rs) == 1.0
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert abs(m - 1.26080) < 1e-5
    scale = attention.mla_softmax_scale(cfg)
    assert abs(scale - 192 ** -0.5 * m * m) < 1e-9
    assert abs(scale - ref.softmax_scale(shape)) < 1e-9
    # the frequencies below the range are interpolated by the factor,
    # those above it kept
    freqs = np.asarray(rope_freqs(64, 10000.0, rs))
    np.testing.assert_allclose(freqs[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], base[23:] / 40.0, rtol=1e-6)


def test_published_config_and_the_chips_share():
    full = get_config("deepseek-v2-lite-16b")
    share = get_config("deepseek-v2-lite-16b-ep4")
    assert (full.d_ff, full.expert_ff(), full.norm_eps) == (10944, 1408, 1e-6)
    m = full.moe
    assert (m.n_experts, m.top_k, m.n_shared, m.norm_topk_prob,
            m.routed_scaling_factor, m.held) == (64, 6, 2, False, 1.0, 64)
    assert (share.moe.n_experts, share.moe.held, share.moe.held_offset) == (
        64, 16, 0)
    assert dataclasses.replace(share, name=full.name,
                               moe=full.moe) == full
    specs = Model(share).param_specs()
    assert specs["blocks"]["l0"]["moe"]["router"]["w"].shape[-1] == 64
    assert specs["blocks"]["l0"]["moe"]["gate"].shape[1:] == (16, 2048, 1408)
