"""Resident prefix-sharing KV pool (radix-trie block reuse across batches).

* Trie invariants — `lookup` returns the unique longest cached prefix,
  `insert`/`evict` preserve ``blocks_in_use + blocks_free == total``, and
  refcounts hit zero exactly once — deterministically and under
  hypothesis-driven random admit/hit/release/evict interleavings;
* eviction never fires on a block any admitted request holds a ref to
  (hard error naming the block and its owning prefix), and interior nodes
  never orphan children (leaf-first peeling);
* pooled decode is *bit-identical* to the paged path without the trie
  (tokens + logprobs), cold and cache-hot, greedy and sampled, including the CoW
  partial tail block and non-uniform per-prompt sample counts — the pinned
  acceptance parity;
* admission prices cache-hot requests at marginal (post-dedup) cost and
  `capacity_free` counts evictable idle blocks, consistently: an idle hit
  charges the evictable unit its pinning consumes, a hit pinned by a live
  batch is free;
* ``pool_evict="off"`` disables reclamation: admission fails loudly when
  the budget is genuinely exhausted;
* obs counters (hits/misses/evictions/resident/hit-ratio) and scheduler
  `BatchRecord` / ``stats()`` / "serve" trace fields account the reuse.
"""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from types import SimpleNamespace  # noqa: E402

from repro.models import ArchConfig, Model  # noqa: E402
from repro.models.cache import (kv_bytes_per_token,  # noqa: E402
                                prefix_pool_bytes)
from repro.serving import (BlockAllocator, ContinuousBatchingScheduler,  # noqa: E402
                           ExecutionBackend, PrefixPool, SchedulerConfig)
from repro.serving.prefix_pool import chunk_key  # noqa: E402

CFG = ArchConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                 n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)
BS = 4


@pytest.fixture(scope="module")
def model_params():
    model = Model(CFG, dtype=jnp.float32)
    return model, model.init(jax.random.key(0))


def _prompt(n, mult=1):
    return (np.arange(1, n + 1, dtype=np.int32) * mult) % CFG.vocab_size


def _chunked(bits):
    """Prompt of ``len(bits)`` full blocks; chunk i is ``2*i + bits[i]``
    repeated — equal bit-prefixes share token-prefixes and nothing else."""
    return np.concatenate([np.full(BS, 2 * i + b, np.int32)
                           for i, b in enumerate(bits)])


def _fill_chain(pool, prompt, n_blocks):
    """Simulate what a pooled batch does for one holder: pin the cached
    chain, allocate + "fill" the rest, index it. Returns the holder's
    per-block gids (the caller releases each exactly once)."""
    a = pool.allocator
    chain = pool.acquire(prompt, n_blocks, holders=1)
    need = n_blocks - len(chain)
    pool.ensure_free(need)
    if need > a.blocks_free:
        for g in chain:
            a.free(g)
        return None
    gids = list(chain) + [a.alloc() for _ in range(need)]
    pool.insert(prompt, gids)
    return gids


# ================================================== trie (no model needed)

def test_lookup_returns_unique_longest_cached_prefix():
    a = BlockAllocator(16, BS)
    pool = PrefixPool(a)
    p_ab = _chunked([0, 0, 0])
    gids = _fill_chain(pool, p_ab, 3)
    assert pool.blocks_resident == 3
    # full walk, capped walk, divergent walk
    assert pool.lookup(p_ab, 3) == gids
    assert pool.lookup(p_ab, 2) == gids[:2]
    assert pool.lookup(_chunked([0, 0, 1]), 3) == gids[:2]
    assert pool.lookup(_chunked([1, 0, 0]), 3) == []
    # a sibling chain shares exactly the common blocks (same physical ids)
    p_div = _chunked([0, 1, 0])
    gids2 = _fill_chain(pool, p_div, 3)
    assert gids2[0] == gids[0] and gids2[1] != gids[1]
    assert pool.lookup(p_div, 3) == gids2
    # dtype canonicalization: int64 prompt resolves the int32-keyed chain
    assert pool.lookup(p_ab.astype(np.int64), 3) == gids
    assert chunk_key(p_ab, 0, BS) == chunk_key(p_ab.astype(np.int64), 0, BS)
    for g in set(gids) | set(gids2):
        assert a.refcount(g) >= 2            # holder + trie ref


def test_insert_first_writer_wins_and_duplicate_blocks_stay_plain():
    a = BlockAllocator(8, BS)
    pool = PrefixPool(a)
    p = _chunked([0, 0])
    first = _fill_chain(pool, p, 2)
    # a same-prefix sibling that prefilled its own duplicate blocks (the
    # within-batch race): insert keeps the incumbents and indexes nothing
    dup = [a.alloc(), a.alloc()]
    assert pool.insert(p, dup) == 0
    assert pool.lookup(p, 2) == first
    # the duplicates stayed plain refcounted blocks: freeing them fully
    # returns them (a trie-resident block would raise here)
    assert a.free(dup[0]) and a.free(dup[1])
    for g in first:
        a.free(g)
    assert a.blocks_in_use == pool.blocks_resident == 2


def test_evict_refuses_live_refs_and_interior_nodes():
    a = BlockAllocator(8, BS)
    pool = PrefixPool(a)
    p = _chunked([0, 0])
    root_bid, leaf_bid = _fill_chain(pool, p, 2)
    with pytest.raises(RuntimeError, match="live holder"):
        pool.evict(leaf_bid)                 # our holder ref is live
    a.free(leaf_bid)                         # release the holder's refs
    a.free(root_bid)
    with pytest.raises(RuntimeError, match="orphan"):
        pool.evict(root_bid)                 # interior: leaf-first only
    pool.evict(leaf_bid)
    pool.evict(root_bid)
    assert pool.blocks_resident == 0 and a.blocks_free == 8
    assert pool.evictions == 2
    with pytest.raises(KeyError, match="not resident"):
        pool.evict(leaf_bid)


def test_ensure_free_evicts_idle_leaves_in_lru_order():
    a = BlockAllocator(4, BS)
    pool = PrefixPool(a)
    cold = _fill_chain(pool, _chunked([0, 0]), 2)
    warm = _fill_chain(pool, _chunked([1, 1]), 2)
    for g in cold + warm:
        a.free(g)                            # all idle, all evictable
    pool.lookup(_chunked([1, 1]), 2)         # touch -> warm is most recent
    assert pool.evictable_blocks == 4
    assert pool.ensure_free(1) == 1          # peels the cold *leaf* first
    assert pool.lookup(_chunked([0, 0]), 2, touch=False) == cold[:1]
    assert pool.ensure_free(2) == 1          # then the cold root
    assert pool.lookup(_chunked([0, 0]), 2, touch=False) == []
    assert pool.lookup(_chunked([1, 1]), 2, touch=False) == warm
    assert pool.ensure_free(4) == 2          # warm chain last
    assert a.blocks_free == 4 and pool.blocks_resident == 0


def test_evict_off_policy_never_reclaims():
    a = BlockAllocator(4, BS)
    pool = PrefixPool(a, evict="off")
    gids = _fill_chain(pool, _chunked([0, 0]), 2)
    for g in gids:
        a.free(g)
    assert pool.evictable_blocks == 0        # idle but not reclaimable
    assert pool.ensure_free(4) == 0
    assert a.blocks_free == 2                # residency is permanent
    with pytest.raises(ValueError, match="eviction policy"):
        PrefixPool(a, evict="fifo")


def test_allocator_refuses_freeing_resident_blocks_under_the_pool():
    a = BlockAllocator(4, BS)
    pool = PrefixPool(a)
    bid = _fill_chain(pool, _chunked([0]), 1)[0]
    a.free(bid)                              # holder ref: fine
    with pytest.raises(RuntimeError, match="trie-resident"):
        a.free(bid)                          # trie ref: never via free()
    assert pool.owner_of(bid) == a.protected_owner(bid)
    assert "depth 1" in pool.owner_of(bid)


# ---------------------------------------------- random interleaving driver

def _drive(n_blocks, ops):
    """Random admit/release/evict interleaving; checks the pool invariants
    after every op. Holder gid lists release each block exactly once."""
    a = BlockAllocator(n_blocks, BS)
    pool = PrefixPool(a)
    holders = []                             # (prompt, n_blocks, gids)
    created = 0
    for kind, bits, arg in ops:
        if kind == "admit":
            prompt = _chunked(bits)
            before = pool.lookup(prompt, len(bits), touch=False)
            gids = _fill_chain(pool, prompt, len(bits))
            if gids is not None:
                assert gids[:len(before)] == before   # hits reuse, in order
                created += len(gids) - len(before)
                holders.append((prompt, len(bits), gids))
        elif kind == "release" and holders:
            prompt, nb, gids = holders.pop(arg % len(holders))
            for g in gids:
                a.free(g)
        else:
            pool.ensure_free(arg % (n_blocks + 1))
        # ---- invariants after every op
        assert a.blocks_in_use + a.blocks_free == n_blocks
        assert a.blocks_in_use == pool.blocks_resident
        for prompt, nb, gids in holders:
            # held chains are pinned: the walk resolves them exactly
            assert pool.lookup(prompt, nb, touch=False) == gids
            assert all(a.refcount(g) >= 2 for g in gids)
    for _, _, gids in holders:
        for g in gids:
            a.free(g)
    pool.ensure_free(n_blocks)
    assert a.blocks_free == n_blocks         # every block back exactly once
    assert pool.blocks_resident == 0
    assert pool.evictions == created         # each indexed block: out once


def test_trie_invariants_deterministic():
    _drive(8, [("admit", [0, 0], 0), ("admit", [0, 1], 0),
               ("release", [], 0), ("evict", [], 8),
               ("admit", [0, 0, 0], 0), ("release", [], 0),
               ("release", [], 0), ("evict", [], 8),
               ("admit", [1, 1], 0)])
    # budget-exhaustion skip path: 4 blocks cannot host two disjoint
    # 3-chains while one is held
    _drive(4, [("admit", [0, 0, 0], 0), ("admit", [1, 1, 1], 0),
               ("release", [], 0), ("admit", [1, 1, 1], 0)])


@settings(max_examples=30, deadline=None)
@given(st.integers(3, 12),
       st.lists(st.tuples(st.sampled_from(["admit", "release", "evict"]),
                          st.lists(st.integers(0, 1), min_size=1,
                                   max_size=3),
                          st.integers(0, 12)),
                min_size=1, max_size=24))
def test_trie_invariants_property(n_blocks, ops):
    _drive(n_blocks, ops)


# =========================================== pooled execution: bit parity

def _gen(backend, batches, n_samples, max_new, temperature, seed=0):
    out = []
    for prompts in batches:
        h = backend.start_batch(prompts, n_samples, max_new, temperature,
                                jax.random.key(seed))
        while backend.decode_step(h):
            pass
        out.append((backend.finalize(h), h))
    return out


def _assert_results_identical(got, want):
    for (rg, _), (rw, _) in zip(got, want):
        for g, w in zip(rg, rw):
            assert g.logprobs == w.logprobs
            for sg, sw in zip(g.samples, w.samples):
                np.testing.assert_array_equal(sg, sw)


@pytest.mark.parametrize("temperature", [0.0, 0.8])
@pytest.mark.parametrize("n_samples", [1, [2, 1]])
def test_pooled_matches_paged_bitwise_cold_and_hot(model_params, temperature,
                                                   n_samples):
    """The pinned acceptance parity: pooled tokens/logprobs are bit-equal
    to the paged path without the trie — on a cold trie (full prefill + insert)
    and cache-hot (trie hits + tail-only prefill) — greedy and sampled,
    with the CoW partial tail block (plen=7 on bs=4) and non-uniform
    per-prompt sample counts."""
    model, params = model_params
    shared = _prompt(4)
    batches = [[np.concatenate([shared, _prompt(3, 5)]),
                np.concatenate([shared, _prompt(3, 7)])]] * 2
    plain = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=BS)
    pooled = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=BS,
                              kv_pool=True)
    want = _gen(plain, batches, n_samples, 4, temperature, seed=3)
    got = _gen(pooled, batches, n_samples, 4, temperature, seed=3)
    _assert_results_identical(got, want)
    # the replay actually ran cache-hot: plen=7, bs=4 -> 1 reusable block
    # per prompt ((plen-1)//bs caps the walk; the tail token stays)
    assert got[0][1].pool_hit_blocks == 0
    assert got[1][1].pool_hit_blocks == 2
    assert pooled.allocator.blocks_in_use == pooled.prefix_pool.blocks_resident
    # with the trie or without, a paged backend holds one resident cache of
    # kv_blocks blocks and no batch holds one of its own
    for be, runs in ((plain, want), (pooled, got)):
        cache = be._pool_cache
        pools = list(cache["prefix"]) + list(cache["blocks"].values())
        assert {e["pos"].shape[-2] for e in pools} == {64}
        assert all(h.cache is None for _, h in runs)


def test_pool_hits_and_prefill_bytes_saved_accounting(model_params):
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=BS,
                          kv_pool=True)
    p = _prompt(16)
    (r1, h1), (r2, h2) = _gen(be, [[p], [p]], 1, 3, 0.0)
    ktb = be.kv_token_bytes
    assert h1.pool_hit_blocks == 0 and h1.prefill_bytes_saved == 0.0
    # warm replay reuses (16-1)//4 = 3 of the 4 full prefix blocks; only
    # the 4-token tail was prefilled
    assert h2.pool_hit_blocks == 3
    assert h2.prefill_bytes_saved == (16 - 4) * ktb
    assert prefix_pool_bytes(CFG, be.prefix_pool.blocks_resident, BS, 4) == \
        be.prefix_pool.blocks_resident * BS * kv_bytes_per_token(CFG, 4)
    _assert_results_identical([(r2, h2)], [(r1, h1)])


def test_eviction_reclaims_idle_chains_under_pressure(model_params):
    """A tight budget forces LRU eviction of an idle resident chain to fit
    a new request's tail — and the evicted prefix then misses."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=4, kv_block_size=BS,
                          kv_pool=True)
    pa, pb = _prompt(8), _prompt(8, 3)
    assert be.request_blocks(8, 4, 1) == 3   # 2 prefix + 1 decode block
    (_, ha), = _gen(be, [[pa]], 1, 4, 0.0)
    assert be.prefix_pool.blocks_resident == 2 and be.allocator.blocks_free == 2
    assert be.capacity_free == 4             # free + evictable idle chain
    (_, hb), = _gen(be, [[pb]], 1, 4, 0.0)
    assert hb.pool_evictions >= 1            # peeled pa's idle leaf
    assert len(be.prefix_pool.lookup(pa, 2, touch=False)) < 2
    assert be.allocator.blocks_in_use == be.prefix_pool.blocks_resident


def test_eviction_never_fires_under_live_refs_budget_fails_loudly(
        model_params):
    """While a batch holds refs on its chains, those blocks are not
    evictable; an over-budget start raises (after unwinding) instead of
    evicting under the live batch, which then completes unperturbed."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=5, kv_block_size=BS,
                          kv_pool=True)
    want = _gen(ExecutionBackend(model, params, kv_blocks=5,
                                 kv_block_size=BS, kv_pool=True),
                [[_prompt(8)]], 1, 4, 0.0)
    h = be.start_batch([_prompt(8)], 1, 4, 0.0, jax.random.key(0))
    assert be.prefix_pool.evictable_blocks == 0      # all chains held
    free_before = be.allocator.blocks_free
    with pytest.raises(RuntimeError, match="KV block budget exceeded"):
        be.start_batch([_prompt(8, 3)], 1, 4, 0.0, jax.random.key(0))
    assert be.allocator.blocks_free == free_before   # unwound cleanly
    while be.decode_step(h):
        pass
    got = [(be.finalize(h), h)]
    _assert_results_identical(got, want)
    assert be.capacity_free == 5             # retired: 3 free + 2 evictable


def test_evict_off_backend_raises_when_full(model_params):
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=5, kv_block_size=BS,
                          kv_pool=True, pool_evict="off")
    _gen(be, [[_prompt(8)]], 1, 4, 0.0)
    assert be.capacity_free == 3             # 2 resident forever
    # marginal price under "off": hits are free (they cost no evictable
    # headroom), so the warm replay fits where a cold one would not
    assert be.marginal_request_cost(_prompt(8), 4, 1) == 2
    _gen(be, [[_prompt(8)]], 1, 4, 0.0)      # tail-only: fits in 3 free
    with pytest.raises(RuntimeError, match="KV block budget exceeded"):
        be.start_batch([_prompt(8, 3)], 2, 4, 0.0, jax.random.key(0))


def test_kv_pool_requires_paged_cache(model_params):
    model, params = model_params
    with pytest.raises(ValueError, match="kv_pool requires the paged"):
        ExecutionBackend(model, params, kv_pool=True)


# ============================================ admission, scheduler, obs

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


def test_marginal_cost_free_only_for_pinned_hits(model_params):
    """Pricing must stay consistent with `capacity_free`: an idle hit
    charges the evictable unit its pinning consumes; a hit held by a live
    batch is genuinely marginal (free)."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=BS,
                          kv_pool=True)
    p = _prompt(8)
    full = be.request_cost(8, 4, 1)
    assert be.marginal_request_cost(p, 4, 1) == full     # cold: no hits
    h = be.start_batch([p], 1, 4, 0.0, jax.random.key(0))
    # in flight: the 1 reusable block is pinned -> free; price = tail only
    assert be.marginal_request_cost(p, 4, 1) == full - 1
    while be.decode_step(h):
        pass
    be.finalize(h)
    # retired: hits idle again -> charged against evictable headroom,
    # which capacity_free now includes
    assert be.marginal_request_cost(p, 4, 1) == full
    assert be.capacity_free == 32


def test_idle_hits_are_charged_once_a_batch(model_params):
    """Requests of one batch that hit the same idle cached prefix pay for
    it once: the first pins it for the others. A budget that holds the
    prefix once plus every request's tail admits them all in one batch;
    priced one by one, each request paid for the prefix again."""
    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=BS,
                          kv_pool=True)
    p = _prompt(16)
    h = be.start_batch([p], 1, 3, 0.0, jax.random.key(0))
    while be.decode_step(h):
        pass
    be.finalize(h)
    full = be.request_cost(16, 3, 1)
    assert be.marginal_request_cost(p, 3, 1) == full      # idle: charged
    charged: set = set()
    first = be.marginal_request_cost(p, 3, 1, charged=charged)
    assert first == full and len(charged) == 3
    assert be.marginal_request_cost(p, 3, 1, charged=charged) == full - 3

    sched = ContinuousBatchingScheduler(
        be, _StubRouter(["economy"]),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=3))
    for _ in range(4):
        assert sched.submit(p, tier="economy", n_samples=1).admitted
    # room for the prefix once and four tails, not for it four times
    sched.kv_reserve = be.capacity_free - (full + 3 * (full - 3))
    assert 4 * full > be.capacity_free - sched.kv_reserve
    entry = sched._form_batch()
    assert entry is not None and len(entry.requests) == 4


def test_scheduler_prices_marginally_and_records_pool_fields(model_params):
    from repro.qeil2 import TraceStore

    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=32, kv_block_size=BS,
                          kv_pool=True)
    trace = TraceStore()
    sched = ContinuousBatchingScheduler(
        be, _StubRouter(["economy"]),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=3),
        trace=trace)
    p = _prompt(16)
    for _ in range(2):
        assert sched.submit(p, tier="economy", n_samples=1).admitted
        sched.run_until_idle()
    assert len(sched.records) == 2
    assert sched.records[0].pool_hit_blocks == 0
    assert sched.records[1].pool_hit_blocks == 3
    st = sched.stats()
    assert st["pool_hit_blocks"] == 3 and st["pool_evictions"] == 0
    assert st["prefill_bytes_saved"] == 12 * be.kv_token_bytes
    recs = trace.records("serve")
    assert [r["pool_hit_blocks"] for r in recs] == [0, 3]
    assert all("pool_evictions" in r for r in recs)
    assert be.allocator.blocks_in_use == be.prefix_pool.blocks_resident == 4


def test_obs_counters_track_hits_misses_resident_ratio(model_params):
    from repro.obs import make_observability

    model, params = model_params
    obs = make_observability()
    be = ExecutionBackend(model, params, kv_blocks=64, kv_block_size=BS,
                          kv_pool=True, obs=obs)
    p = _prompt(16)                          # 4 full blocks, 3 reusable
    _gen(be, [[p], [p]], 1, 3, 0.0)
    reg = obs.metrics
    assert reg.counter("serving_prefix_pool_hits_total").value() == 3
    assert reg.counter("serving_prefix_pool_misses_total").value() == 5
    assert reg.counter("serving_prefix_pool_evictions_total").value() == 0
    assert reg.gauge("serving_prefix_pool_blocks_resident").value() == 4
    h = reg.histogram("serving_prefix_pool_hit_ratio")
    assert h.sum_value() == pytest.approx(0.75)   # 0/4 then 3/4
    # the counters reproduce the analytic hit rate of the stream
    hits = reg.counter("serving_prefix_pool_hits_total").value()
    lookups = hits + reg.counter("serving_prefix_pool_misses_total").value()
    assert hits / lookups == pytest.approx(3 / 8)


def test_spec_decode_composes_with_pool(model_params):
    """Speculative decode rides the pooled cache: the draft/verify loop
    threads the resident array, and warm batches still resolve hits."""
    from repro.spec import make_draft_policy

    model, params = model_params
    be = ExecutionBackend(model, params, kv_blocks=96, kv_block_size=BS,
                          kv_pool=True,
                          spec_policy=make_draft_policy("ngram"), spec_n=2)
    p = _prompt(16)
    (r1, h1), (r2, h2) = _gen(be, [[p], [p]], 1, 5, 0.0)
    assert h2.pool_hit_blocks == 3
    _assert_results_identical([(r2, h2)], [(r1, h1)])
    assert be.allocator.blocks_in_use == be.prefix_pool.blocks_resident
