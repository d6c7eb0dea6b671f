"""Beyond-paper decode optimizations must be exact (EXPERIMENTS.md §Perf):
cross-attention K/V caching and the dense pass over the held experts."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.models import ArchConfig, MoEConfig, Model


def test_cross_kv_cache_decode_matches_recompute():
    base = ArchConfig(name="a", arch_type="audio", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=33,
                      mlp_variant="gelu", rope_variant="sinusoidal",
                      n_codebooks=4, cross_attention=True, frontend="audio")
    cached = base.with_overrides(cross_kv_cache=True)
    m0 = Model(base, dtype=jnp.float32)
    m1 = Model(cached, dtype=jnp.float32)
    params = m0.init(jax.random.key(0))
    B, S = 2, 12
    toks = jax.random.randint(jax.random.key(1), (B, S, 4), 0, 33)
    mem = jax.random.normal(jax.random.key(2), (B, 8, 64)) * 0.1
    c0, c1 = m0.init_cache(B, S + 4), m1.init_cache(B, S + 4)
    _, c0, _ = m0.forward(params, {"tokens": toks, "cond_memory": mem}, c0)
    _, c1, _ = m1.forward(params, {"tokens": toks, "cond_memory": mem}, c1)
    for step in range(3):
        nt = jax.random.randint(jax.random.key(5 + step), (B, 1, 4), 0, 33)
        pos = jnp.full((B, 1), S + step, jnp.int32)
        l0, c0, _ = m0.forward(params, {"tokens": nt, "positions": pos,
                                        "cond_memory": mem}, c0)
        # the cached variant decodes WITHOUT the conditioning input at all
        l1, c1, _ = m1.forward(params, {"tokens": nt, "positions": pos}, c1)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   rtol=1e-5, atol=1e-5)


def test_moe_dense_decode_matches_dispatch():
    """The serving layer's dense pass over the held experts (a handful of
    tokens) equals capacity dispatch wherever no pair is dropped."""
    from repro.models import moe
    mo = ArchConfig(name="g", arch_type="moe", n_layers=2, d_model=64,
                    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=97,
                    moe=MoEConfig(n_experts=4, top_k=2, capacity_factor=8.0))
    params = Model(mo, dtype=jnp.float32).init(jax.random.key(0))
    p = jax.tree.map(lambda a: a[0], params["blocks"]["l0"]["moe"])
    x = jax.random.normal(jax.random.key(1), (2, 16, 64), jnp.float32)
    y0, a0 = moe.moe_forward(p, mo, x)
    y1, a1 = moe.moe_forward(p, mo, x, dropless=True)
    assert 2 * 16 <= moe.DENSE_TOKENS
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(a0), float(a1), rtol=1e-5)
