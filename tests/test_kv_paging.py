"""Paged KV cache with prefix sharing (PR 5 tentpole).

* `BlockAllocator` invariants — no double-free, refcounts hit zero exactly
  once, blocks_in_use + blocks_free == total — deterministically and under
  hypothesis-driven random admit/fork/early-stop/release sequences;
* paged decode is *bit-identical* to the dense path (tokens + logprobs),
  including copy-on-write of a partially-filled prefix block and
  non-uniform per-prompt sample counts (the pinned acceptance parity);
* the paged Pallas kernel matches the gathered jnp oracle;
* `ExecutionBackend.release` raises on double release (regression: it used
  to silently drive the budget negative);
* extras are tiled once at prefill and reused across decode steps;
* scheduler admission prices requests in blocks at shared-prefix cost, and
  `early_stop` (CSVET) returns private blocks mid-flight;
* "serve" trace records carry KV block occupancy + prefill bytes saved.
"""
from types import SimpleNamespace

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.models import ArchConfig, Model  # noqa: E402
from repro.models.cache import (kv_bytes_per_token, make_cache,  # noqa: E402
                                PagedLayout, paged_supported)
from repro.serving import (BlockAllocator, ContinuousBatchingScheduler,  # noqa: E402
                           ExecutionBackend, SchedulerConfig, ServingEngine,
                           build_paged_layout)

CFG = ArchConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


@pytest.fixture(scope="module")
def model_params():
    model = Model(CFG, dtype=jnp.float32)
    return model, model.init(jax.random.key(0))


def _prompt(n, mult=1):
    return (np.arange(1, n + 1, dtype=np.int32) * mult) % CFG.vocab_size


# ========================================================== allocator (unit)

def test_allocator_alloc_fork_cow_free_lifecycle():
    a = BlockAllocator(4, 8)
    b0 = a.alloc()
    assert a.refcount(b0) == 1 and a.blocks_in_use == 1
    a.fork(b0)
    a.fork(b0)
    assert a.refcount(b0) == 3
    # shared -> cow copies and drops one reference
    c1, copied = a.cow(b0)
    assert copied and c1 != b0 and a.refcount(b0) == 2
    c2, copied = a.cow(b0)
    assert copied and c2 not in (b0, c1)
    # sole holder -> write in place
    c3, copied = a.cow(b0)
    assert not copied and c3 == b0
    assert a.blocks_in_use + a.blocks_free == a.n_blocks == 4
    # each holder frees once; block returns with its last reference
    assert a.free(c1) and a.free(c2)
    assert a.free(b0)
    assert a.blocks_free == 4


def test_allocator_double_free_and_exhaustion_raise():
    a = BlockAllocator(2, 4)
    b = a.alloc()
    a.free(b)
    with pytest.raises(RuntimeError, match="double free"):
        a.free(b)
    with pytest.raises(KeyError):
        a.fork(b)
    a.alloc()
    a.alloc()
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc()


def test_allocator_free_of_trie_resident_block_raises():
    """Regression (PrefixPool guard): dropping the *last* reference of a
    trie-resident block must raise, naming the block and its owning prefix
    — cached KV silently returning to the free list would corrupt the
    prefix index. Holder refs above the trie ref still release normally."""
    a = BlockAllocator(2, 4)
    b = a.alloc()                       # trie ref (pool insert forks+protects)
    a.protect(b, "depth 1, chunk tokens [5, 6, 7, 8]...")
    a.fork(b)                           # one live holder on top
    assert a.free(b) is False           # holder release: fine, ref 2 -> 1
    with pytest.raises(RuntimeError) as ei:
        a.free(b)                       # last ref is the trie's: hard error
    assert f"block {b}" in str(ei.value)
    assert "depth 1, chunk tokens [5, 6, 7, 8]" in str(ei.value)
    assert a.refcount(b) == 1           # nothing was released
    assert a.blocks_in_use == 1
    a.unprotect(b)                      # eviction path: unprotect, then free
    assert a.free(b)
    assert a.blocks_free == 2
    with pytest.raises(KeyError, match="unallocated"):
        a.protect(b, "stale")           # protection requires a live block


def _distinct_gids(layout):
    """The allocator ids a layout's sequences reference."""
    return {g for gids in layout.seq_gids for g in gids}


def _cache_blocks(cache):
    """Block counts of every pool of a paged cache (one per layer)."""
    pools = list(cache["prefix"]) + list(cache["blocks"].values())
    return {e["pos"].shape[-2] for e in pools}


def _run_lifecycle(n_blocks, bs, requests, early, seed):
    """Drive build_paged_layout + early/final release over `requests`
    (plen, max_new, k) triples; checks the allocator invariants throughout.
    Returns the allocator for final assertions."""
    a = BlockAllocator(n_blocks, bs)
    rng = np.random.default_rng(seed)
    returned = {}                      # physical block -> times it came back
    live = []
    for (plen, max_new, k) in requests:
        n_logical = max(-(-(plen + max_new - 1) // bs), 1)
        need = plen // bs + k * (n_logical - plen // bs)
        if need > a.blocks_free:
            continue                   # admission would reject; skip
        layout = build_paged_layout(a, plen, max_new, [k])
        assert a.blocks_in_use + a.blocks_free == a.n_blocks
        assert len(_distinct_gids(layout)) == need
        live.append((layout, set()))
    for layout, freed in live:
        n_seq = len(layout.seq_gids)
        for i in rng.permutation(n_seq)[: rng.integers(0, n_seq + 1)] \
                if early else []:
            for g in layout.seq_gids[i]:
                if a.free(g):
                    returned[g] = returned.get(g, 0) + 1
            freed.add(int(i))
        assert a.blocks_in_use + a.blocks_free == a.n_blocks
    for layout, freed in live:
        for i, gids in enumerate(layout.seq_gids):
            if i in freed:
                continue
            for g in gids:
                if a.free(g):
                    returned[g] = returned.get(g, 0) + 1
    assert a.blocks_free == a.n_blocks          # everything came back
    assert all(v == 1 for v in returned.values())   # ...exactly once
    return a


def test_allocator_lifecycle_deterministic():
    reqs = [(7, 6, 3), (8, 8, 4), (3, 2, 1), (12, 8, 2), (5, 9, 5)]
    _run_lifecycle(64, 4, reqs, early=False, seed=0)
    _run_lifecycle(64, 4, reqs, early=True, seed=1)
    _run_lifecycle(24, 4, reqs * 3, early=True, seed=2)   # exercises skips


@settings(max_examples=40, deadline=None)
@given(st.integers(8, 64), st.sampled_from([2, 4, 8]),
       st.lists(st.tuples(st.integers(1, 12), st.integers(1, 10),
                          st.integers(1, 5)), min_size=1, max_size=8),
       st.booleans(), st.integers(0, 10))
def test_allocator_invariants_property(n_blocks, bs, requests, early, seed):
    _run_lifecycle(n_blocks, bs, requests, early=early, seed=seed)


def test_request_blocks_matches_actual_allocation():
    model = Model(CFG, dtype=jnp.float32)
    be = ExecutionBackend(model, None, kv_blocks=64, kv_block_size=4)
    for plen, max_new, k in [(7, 6, 3), (8, 8, 1), (3, 2, 4), (4, 4, 2)]:
        a = BlockAllocator(64, 4)
        layout = build_paged_layout(a, plen, max_new, [k])
        assert a.blocks_in_use == be.request_blocks(plen, max_new, k)
        assert len(_distinct_gids(layout)) == a.blocks_in_use
        # shared-prefix price is never above the dense-equivalent price
        dense_eq = k * -(-(plen + max_new) // 4)
        assert be.request_blocks(plen, max_new, k) <= dense_eq


# ===================================================== paged/dense parity

def _generate(backend, prompts, n_samples, max_new, seed):
    h = backend.start_batch(prompts, n_samples, max_new, 0.8,
                            jax.random.key(seed))
    while backend.decode_step(h):
        pass
    return backend.finalize(h), h


def _serve_batches(backend, prompts, n_samples, max_new, batches):
    """Start ``batches`` batches of the same prompts (seeds 7, 8, ...), all
    live at once, then drain and finalize each."""
    hs = [backend.start_batch(prompts, n_samples, max_new, 0.8,
                              jax.random.key(7 + i)) for i in range(batches)]
    for h in hs:
        while backend.decode_step(h):
            pass
    return [(backend.finalize(h), h) for h in hs]


@pytest.mark.parametrize("n_samples,plen,max_new,batches", [
    # partial prefix block -> CoW fan-out; padded tail
    pytest.param(3, 7, 6, 1, id="3-7-6"),
    # no sharing, block-aligned
    pytest.param(1, 8, 8, 1, id="1-8-8"),
    # non-uniform per-prompt sample counts
    pytest.param([2, 3], 7, 5, 1, id="n_samples2-7-5"),
    # the same prompts in two live batches, no trie: one resident cache
    pytest.param(3, 7, 6, 2, id="3-7-6-two-batches"),
])
def test_paged_decode_bit_identical_to_dense(model_params, n_samples, plen,
                                             max_new, batches):
    """Acceptance: paged decode (prefix sharing + CoW + block-table
    attention) is bit-identical to the dense path — tokens AND logprobs."""
    model, params = model_params
    prompts = [_prompt(plen), _prompt(plen, mult=3)]
    dense = ExecutionBackend(model, params)
    paged = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=4)
    assert paged.prefix_pool is None
    want = _serve_batches(dense, prompts, n_samples, max_new, batches)
    got = _serve_batches(paged, prompts, n_samples, max_new, batches)
    # paged prefilled one row per prompt, not per sequence
    B = sum(n_samples) if isinstance(n_samples, list) else \
        n_samples * len(prompts)
    for (rw, _), (rg, h) in zip(want, got):
        for a, b in zip(rw, rg):
            assert len(a.samples) == len(b.samples)
            for s1, s2 in zip(a.samples, b.samples):
                np.testing.assert_array_equal(s1, s2)
            assert a.logprobs == b.logprobs
        assert h.prefill_bytes_saved == \
            (B - len(prompts)) * plen * paged.kv_token_bytes
        # tables hold the allocator's global ids into the resident cache;
        # no batch holds a cache of its own
        assert h.cache is None
        assert set(h.paged.decode_table.ravel()) == _distinct_gids(h.paged)
    # batches live at once index disjoint blocks of the one cache
    ids = [_distinct_gids(h.paged) for _, h in got]
    assert sum(map(len, ids)) == len(set().union(*ids))
    assert _cache_blocks(paged._pool_cache) == {paged.allocator.n_blocks}
    assert paged.allocator.blocks_free == paged.allocator.n_blocks


def test_paged_engine_generate_matches_dense(model_params):
    """The blocking `ServingEngine.generate` path works unchanged over a
    paged backend and reproduces the dense engine exactly."""
    model, params = model_params
    prompts = [_prompt(6), _prompt(6, 5), _prompt(9)]   # two buckets
    e_dense = ServingEngine(model, params, max_new_tokens=4)
    e_paged = ServingEngine(model, params, max_new_tokens=4,
                            backend=ExecutionBackend(model, params,
                                                     kv_blocks=64,
                                                     kv_block_size=4))
    want = e_dense.generate(prompts, n_samples=2, rng=jax.random.key(3))
    got = e_paged.generate(prompts, n_samples=2, rng=jax.random.key(3))
    for a, b in zip(want, got):
        for s1, s2 in zip(a.samples, b.samples):
            np.testing.assert_array_equal(s1, s2)
        assert a.logprobs == b.logprobs


def test_engine_chunks_to_kv_budget(model_params):
    """The blocking engine must split a call that exceeds the KV budget
    into budget-sized batches instead of crashing (regression: the serve
    launcher with --kv-blocks below the whole call's need died in
    start_batch), and a single impossible request fails with a clear
    error."""
    model, params = model_params
    # per request: plen=8, max_new=4, k=2 -> 2 + 2*1 = 4 blocks; budget 10
    # fits 2 requests per chunk -> 4 requests = 2 chunks
    be = ExecutionBackend(model, params, kv_blocks=10, kv_block_size=4)
    engine = ServingEngine(model, params, max_new_tokens=4, backend=be)
    prompts = [_prompt(8, m) for m in (1, 3, 5, 7)]
    results = engine.generate(prompts, n_samples=2, rng=jax.random.key(0))
    assert len(results) == 4
    assert all(len(r.samples) == 2 for r in results)
    assert be.allocator.blocks_free == 10
    with pytest.raises(ValueError, match="KV budget"):
        # 2 + 12*1 = 14 blocks > 10: no chunking can make one request fit
        engine.generate([_prompt(8)], n_samples=12, rng=jax.random.key(0))
    # dense slot budgets chunk the same way
    engine_d = ServingEngine(model, params, max_new_tokens=4,
                             backend=ExecutionBackend(model, params,
                                                      max_slots=4))
    results = engine_d.generate(prompts, n_samples=2, rng=jax.random.key(0))
    assert all(len(r.samples) == 2 for r in results)


def test_zero_sample_requests_rejected(model_params):
    """n_samples=0 would allocate prefix blocks no sequence references
    (an unreleasable leak) — rejected at every door."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=16, kv_block_size=4)
    with pytest.raises(ValueError, match=">= 1"):
        be.start_batch([_prompt(6)], 0, 4, 0.8, jax.random.key(0))
    with pytest.raises(ValueError, match=">= 1"):
        be.start_batch([_prompt(6)], [1, 0], 4, 0.8, jax.random.key(0))
    assert be.allocator.blocks_free == 16
    sched = ContinuousBatchingScheduler(
        be, _StubRouter(["economy"]),
        SchedulerConfig(max_batch_requests=4))
    with pytest.raises(ValueError, match=">= 1"):
        sched.submit(_prompt(6), tier="economy", n_samples=0)


def test_paged_kernel_matches_reference_model(model_params):
    """use_kernel=True routes paged decode through the Pallas block-table
    kernel; logits must match the gathered jnp reference path."""
    model, params = model_params
    kmodel = Model(CFG, dtype=jnp.float32, use_kernel=True)
    ref = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=4)
    ker = ExecutionBackend(kmodel, params, kv_blocks=32, kv_block_size=4)
    prompts = [_prompt(7)]
    want, _ = _generate(ref, prompts, 2, 4, seed=11)
    got, _ = _generate(ker, prompts, 2, 4, seed=11)
    # sampling goes through identical logits up to kernel tolerance; with
    # the tiny vocab and fixed rng the argmax-ish picks coincide
    for a, b in zip(want, got):
        np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=1e-4,
                                   atol=1e-4)


def test_paged_kernel_matches_ref_oracle():
    from repro.kernels.decode_attention.decode_attention import \
        paged_decode_attention_pallas
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    ks = jax.random.split(jax.random.key(0), 3)
    B, H, Hkv, D, P, bs, nb = 3, 4, 2, 16, 12, 4, 3
    q = jax.random.normal(ks[0], (B, 1, H, D), jnp.float32)
    kp = jax.random.normal(ks[1], (P, bs, Hkv, D), jnp.float32)
    vp = jax.random.normal(ks[2], (P, bs, Hkv, D), jnp.float32)
    table = jnp.asarray(np.random.default_rng(0).permutation(P)[: B * nb]
                        .reshape(B, nb), jnp.int32)
    q_pos = jnp.array([8, 5, 11], jnp.int32)
    pos = jnp.full((P, bs), -1, jnp.int32)
    for b in range(B):
        for j in range(nb):
            for r in range(bs):
                p_ = j * bs + r
                if p_ <= int(q_pos[b]):
                    pos = pos.at[table[b, j], r].set(p_)
    out = paged_decode_attention_pallas(q, kp, vp, pos, table, q_pos)
    ref = paged_decode_attention_ref(q, kp, vp, pos, table, q_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_pooled_prefill_in_query_blocks_matches_one_block(model_params,
                                                          monkeypatch):
    """A prefill against the pool whose scores would exceed
    `attention.CACHE_SCORES` runs its queries in blocks; each query's row
    is the same computation, so the served tokens agree and their
    log-probabilities agree to float32 rounding."""
    from repro.models import attention
    model, params = model_params
    prompts = [_prompt(13), _prompt(13, mult=3)]

    def serve():
        be = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=4,
                              kv_pool=True)
        return _generate(be, prompts, 2, 5, seed=3)[0]

    whole = serve()
    monkeypatch.setattr(attention, "CACHE_SCORES", 64)
    blocked = serve()
    for a, b in zip(whole, blocked):
        for s1, s2 in zip(a.samples, b.samples):
            np.testing.assert_array_equal(s1, s2)
        np.testing.assert_allclose(a.logprobs, b.logprobs, rtol=0,
                                   atol=1e-5)


def test_make_cache_rejects_unsupported_paged_archs():
    windowed = CFG.with_overrides(attn_window=8)
    assert not paged_supported(windowed)
    with pytest.raises(ValueError, match="paged"):
        make_cache(windowed, 1, 16, paged=PagedLayout(4, 4))
    with pytest.raises(ValueError, match="paged"):
        ExecutionBackend(Model(windowed, dtype=jnp.float32), None,
                         kv_blocks=8, kv_block_size=4)


# ============================================== release / early-stop / extras

def test_release_raises_on_double_release(model_params):
    """Regression: releasing a handle twice used to silently drive the
    budget negative; now it raises and the budget stays exact."""
    model, params = model_params
    for backend in (ExecutionBackend(model, params, max_slots=8),
                    ExecutionBackend(model, params, kv_blocks=32,
                                     kv_block_size=4)):
        results, h = _generate(backend, [_prompt(6)], 2, 3, seed=0)
        assert len(results) == 1
        with pytest.raises(RuntimeError, match="already-released"):
            backend.finalize(h)
        with pytest.raises(RuntimeError, match="already-released"):
            backend.release(h)
        assert backend.slots_in_use == 0
        if backend.allocator is not None:
            assert backend.allocator.blocks_free == backend.allocator.n_blocks
        with pytest.raises(RuntimeError, match="unknown"):
            backend.release(SimpleNamespace(paged=None, n_sequences=1,
                                            freed_seqs=set()))


def test_release_sequences_frees_blocks_mid_flight(model_params):
    """CSVET early stop: a sample's private blocks return to the budget
    immediately; shared prefix blocks only with their last holder; the
    final release does not double-free."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=4)
    h = be.start_batch([_prompt(7)], 3, 6, 0.8, jax.random.key(1))
    in_use = be.allocator.blocks_in_use
    assert in_use == be.request_blocks(7, 6, 3)     # 1 + 3*2 = 7
    be.decode_step(h)
    # sample 0's privates (CoW partial + decode block) come back; the
    # full prefix block is still held by samples 1 and 2
    freed = be.release_sequences(h, [0])
    assert freed == 2
    assert be.allocator.blocks_in_use == in_use - 2
    # the budget is the bytes: the batch holds no array of its own, and the
    # one resident cache stays kv_blocks blocks after the early release
    assert h.cache is None
    assert _cache_blocks(be._pool_cache) == {be.allocator.n_blocks}
    assert be.release_sequences(h, [0]) == 0        # idempotent per sample
    # releasing the rest returns everything, including the shared prefix
    assert be.release_sequences(h, [1, 2]) == 5
    assert be.allocator.blocks_free == be.allocator.n_blocks
    results = be.finalize(h)                        # no double-free
    assert len(results) == 1 and len(results[0].samples) == 3


def test_release_sequences_rejects_out_of_range_indices(model_params):
    """An out-of-range sequence index must raise, not silently release a
    neighbouring batch row's budget (dense) or crash mid-free (paged)."""
    model, params = model_params
    for backend in (ExecutionBackend(model, params, max_slots=8),
                    ExecutionBackend(model, params, kv_blocks=32,
                                     kv_block_size=4)):
        h = backend.start_batch([_prompt(6)], 2, 3, 0.8, jax.random.key(0))
        slots_before = backend.slots_in_use
        blocks_before = backend.blocks_in_use
        with pytest.raises(ValueError, match="out of range"):
            backend.release_sequences(h, [0, 5])
        assert backend.slots_in_use == slots_before       # nothing freed
        assert backend.blocks_in_use == blocks_before
        backend.finalize(h)


def test_scheduler_early_stop_rejects_out_of_range_samples(model_params):
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=4)
    sched = ContinuousBatchingScheduler(
        be, _StubRouter(["economy"]),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=4))
    adm = sched.submit(_prompt(6), tier="economy", n_samples=2)
    sched.step()
    with pytest.raises(ValueError, match="out of range"):
        sched.early_stop(adm.request_id, [2])   # request has samples 0..1
    sched.run_until_idle()


def test_failed_paged_prefill_returns_blocks(model_params, monkeypatch):
    """If anything after block allocation raises (OOM, bad extras), the
    layout's blocks must return to the budget — a failed start_batch must
    not permanently shrink the allocator."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=4)

    def _boom(*a, **k):
        raise RuntimeError("simulated prefill failure")

    monkeypatch.setattr(be, "_tail_prefill_jit", _boom)
    with pytest.raises(RuntimeError, match="simulated"):
        be.start_batch([_prompt(7)], 3, 6, 0.8, jax.random.key(0))
    assert be.allocator.blocks_free == 32


def test_extras_tiled_once_and_reused_across_decode_steps(model_params,
                                                          monkeypatch):
    """The per-request extras rows are tiled to the sequence count at
    prefill; decode steps must reuse the tiled arrays, not re-tile."""
    model, params = model_params
    be = ExecutionBackend(model, params)
    extras = {"bias": np.zeros((1, 3), np.float32)}
    h = be.start_batch([_prompt(6)], 3, 4, 0.8, jax.random.key(0), extras)
    tiled = {k: v for k, v in h.extras.items()}
    assert tiled["bias"].shape[0] == 3

    def _no_retile(*a, **k):
        raise AssertionError("decode_step must not re-tile extras")

    monkeypatch.setattr(jnp, "repeat", _no_retile)
    while be.decode_step(h):
        assert all(h.extras[k] is tiled[k] for k in tiled)
    be.finalize(h)


# ============================================= scheduler: blocks + telemetry

class _StubRouter:
    def __init__(self, tiers):
        self.tiers = {t: SimpleNamespace(name=t) for t in tiers}

    def resolve_tier(self, tier):
        return self.tiers[tier] if isinstance(tier, str) else tier

    def required_samples(self, tier):
        return None

    def route_batch(self, tiers, **kw):
        return SimpleNamespace(
            tier=self.resolve_tier(tiers[0]), tier_counts={},
            assignment=object(), point_index=0, meets_caps=True,
            batch_costs=None, energy_j=1.0, latency_s=1.0, notes=[])


def _paged_sched(model, params, kv_blocks, bs=4, max_batch=8):
    backend = ExecutionBackend(model, params, kv_blocks=kv_blocks,
                               kv_block_size=bs)
    return ContinuousBatchingScheduler(
        backend, _StubRouter(["economy"]),
        SchedulerConfig(max_batch_requests=max_batch, max_new_tokens=4)), \
        backend


def test_scheduler_admission_prices_blocks_at_shared_prefix(model_params):
    model, params = model_params
    # budget 12 blocks, bs=4: plen=8, max_new=8, k=4 costs 2 + 4*2 = 10
    # blocks at shared-prefix price — admitted; dense-equivalent would be
    # 4 * 4 = 16 and could never fit
    sched, backend = _paged_sched(model, params, kv_blocks=12)
    assert backend.request_blocks(8, 8, 4) == 10
    adm = sched.submit(_prompt(8), tier="economy", n_samples=4,
                       max_new_tokens=8)
    assert adm.admitted
    # a request over the total block budget is rejected at the door
    bad = sched.submit(_prompt(8), tier="economy", n_samples=6,
                       max_new_tokens=8)
    assert not bad.admitted and "exceeds the KV budget" in bad.reason
    sched.run_until_idle()
    assert adm.request_id in sched.completed
    assert backend.allocator.blocks_free == 12


def test_scheduler_batches_respect_block_budget(model_params):
    model, params = model_params
    sched, backend = _paged_sched(model, params, kv_blocks=16)
    # each request: plen=4, max_new=4, k=2 -> 1 + 2*1 = 3 blocks
    ids = [sched.submit(_prompt(4), tier="economy", n_samples=2,
                        max_new_tokens=4).request_id for _ in range(8)]
    high = 0
    while sched.queue.pending or sched.inflight:
        if not sched.step():
            break
        high = max(high, backend.allocator.blocks_in_use)
    assert high <= 16
    assert all(i in sched.completed for i in ids)
    assert backend.allocator.blocks_free == 16


def test_scheduler_early_stop_returns_blocks(model_params):
    model, params = model_params
    sched, backend = _paged_sched(model, params, kv_blocks=32)
    adm = sched.submit(_prompt(7), tier="economy", n_samples=3,
                       max_new_tokens=4)
    sched.step()                                    # prefill + first decode
    before = backend.allocator.blocks_free
    freed = sched.early_stop(adm.request_id, [1, 2])
    assert freed > 0
    assert backend.allocator.blocks_free == before + freed
    sched.run_until_idle()
    assert adm.request_id in sched.completed
    assert backend.allocator.blocks_free == 32
    # unknown / retired requests are a no-op
    assert sched.early_stop(adm.request_id) == 0


def test_serve_trace_records_carry_paging_fields(model_params):
    from repro.qeil2 import TraceStore

    model, params = model_params
    backend = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=4)
    trace = TraceStore()
    sched = ContinuousBatchingScheduler(
        backend, _StubRouter(["economy"]),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=3), trace=trace)
    sched.submit(_prompt(7), tier="economy", n_samples=3)
    sched.run_until_idle()
    [rec] = trace.records("serve")
    assert rec["kv_blocks_in_use"] == backend.request_blocks(7, 3, 3)
    assert rec["prefill_bytes_saved"] == \
        2 * 7 * kv_bytes_per_token(CFG, 4)
