"""Prefix-pool trie vs the resident cache without it (PR 9 bench).

A seeded request stream shaped like real serving traffic: every prompt is
one of three Zipf-weighted **system prompts** (24 tokens = 6 full blocks)
followed by a short unique user suffix (4 tokens). Two backends see the
identical stream at the *identical KV block budget*:

* ``batch`` — the resident cache without the trie (``kv_pool=False``):
  prefix sharing and CoW inside one batch only; every batch re-prefills
  the system prompt from scratch.
* ``pool``  — the same cache with the `PrefixPool` trie: a radix trie over
  token-id block chunks survives batch retirement, so a warm request
  resolves its cached system-prompt chain in one trie walk and prefills
  only the 4-token tail.

Reported per policy: prefill bytes moved (total and **steady-state** —
excluding the cold first batch), and ``throughput_rps``: completed
requests over a *modeled* makespan (fixed batch overhead + a prefill term
per token actually moved + a decode term per sequence-token; the decode
term is identical for both policies because batch formation is, so the
ratio is purely the prefill traffic the pool avoids). It is a service
model over the records, not a measured speed.

The pooled stream's hit/miss counters are cross-checked against an
**analytic replay**: a pure-python trie is driven with the recorded batch
memberships (``BatchRecord.request_entries``) and must reproduce the
backend's `serving_prefix_pool_{hits,misses}_total` exactly — the
eviction-free budget makes the expectation exact. A separate tight-budget
run forces LRU evictions and must still complete every request.

Acceptance (seeded, CI-gated): pooled decode is token/logprob bit-identical
to the paged path without the trie (cold AND cache-hot, sampled, CoW tail);
steady-state prefill bytes are >= 3x lower than per-batch sharing at equal
block budget; modeled throughput matches-or-beats the trie-less path; the obs
counters match the analytic replay; the tight run evicts (> 0) and
completes the full stream.

Run: PYTHONPATH=src python benchmarks/prefix_pool.py [--out FILE]
"""
from __future__ import annotations

import json
import sys
import time
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

SEED = 0
N_REQUESTS = 18
K_SAMPLES = 2                        # repeats exercise CoW on top of the pool
SYS_LEN = 24                         # 6 full blocks of shared system prompt
USER_LEN = 4                         # unique per-request tail
PROMPT_LEN = SYS_LEN + USER_LEN
MAX_NEW = 4
BLOCK_SIZE = 4
N_SYSTEM = 3
ZIPF_W = [1.0 / (i + 1) for i in range(N_SYSTEM)]
BUDGET_BLOCKS = 96                   # generous: main runs never evict
TIGHT_BLOCKS = 24                    # resident demand ~36 blocks -> LRU churn
# roofline-style service model: fixed pipeline overhead + bandwidth-bound
# prefill (per token moved) + compute-bound decode (per sequence-token)
BATCH_BASE_S = 0.5
PREFILL_S_PER_TOKEN = 0.02
DECODE_S_PER_SEQ_TOKEN = 0.01

ARCH = dict(name="pool-bench", arch_type="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


class _FixedRouter:
    """Deterministic routing double: this bench measures cross-batch KV
    reuse, not SLA routing (serving_schedule.py gates that)."""

    def __init__(self):
        self.tier = SimpleNamespace(name="standard")

    def resolve_tier(self, tier):
        return self.tier

    def required_samples(self, tier):
        return None

    def route_batch(self, tiers, **kw):
        return SimpleNamespace(
            tier=self.tier, tier_counts={}, assignment=object(),
            point_index=0, meets_caps=True, batch_costs=None,
            energy_j=float(len(tiers)), latency_s=BATCH_BASE_S, notes=[])


def _arrivals() -> List[Dict]:
    rng = np.random.default_rng(SEED)
    probs = np.asarray(ZIPF_W) / sum(ZIPF_W)
    systems = [rng.integers(0, ARCH["vocab_size"],
                            size=(SYS_LEN,)).astype(np.int32)
               for _ in range(N_SYSTEM)]
    t, out = 0.0, []
    for _ in range(N_REQUESTS):
        t += rng.exponential(0.4)
        sysid = int(rng.choice(N_SYSTEM, p=probs))
        suffix = rng.integers(0, ARCH["vocab_size"],
                              size=(USER_LEN,)).astype(np.int32)
        out.append({"t": t, "sysid": sysid,
                    "prompt": np.concatenate([systems[sysid], suffix])})
    return out


def _modeled_makespan(records) -> float:
    """Post-hoc service model over the recorded batches. Prefill tokens
    actually moved come from the records' savings accounting, so per-batch
    repeat sharing and pool hits both get credit."""
    from repro.models import ArchConfig
    from repro.models.cache import kv_bytes_per_token

    ktb = kv_bytes_per_token(ArchConfig(**ARCH), 4)
    total = 0.0
    for r in records:
        moved_tokens = r.n_sequences * PROMPT_LEN \
            - r.prefill_bytes_saved / ktb
        total += (BATCH_BASE_S + PREFILL_S_PER_TOKEN * moved_tokens
                  + DECODE_S_PER_SEQ_TOKEN * r.n_sequences * MAX_NEW)
    return total


def _run_stream(pooled: bool, arrivals, kv_blocks: int = BUDGET_BLOCKS,
                verbose: bool = True) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.models.cache import kv_bytes_per_token
    from repro.obs import make_observability
    from repro.serving import (ContinuousBatchingScheduler, ExecutionBackend,
                               SchedulerConfig)

    cfg = ArchConfig(**ARCH)
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    ktb = kv_bytes_per_token(cfg, 4)            # f32 model
    obs = make_observability()
    backend = ExecutionBackend(model, params, kv_blocks=kv_blocks,
                               kv_block_size=BLOCK_SIZE, kv_pool=pooled,
                               obs=obs)
    sched = ContinuousBatchingScheduler(
        backend, _FixedRouter(),
        SchedulerConfig(max_batch_requests=4, max_inflight_batches=2,
                        max_new_tokens=MAX_NEW, seed=SEED))

    prompt_by_id: Dict[int, np.ndarray] = {}
    i = 0
    t0 = time.perf_counter()
    while i < len(arrivals) or sched.queue.pending or sched.inflight:
        horizon = max(sched.clock, sched.pipeline_free_t)
        while i < len(arrivals) and arrivals[i]["t"] <= horizon:
            a = arrivals[i]
            adm = sched.submit(a["prompt"], tier="standard",
                               n_samples=K_SAMPLES, arrival_s=a["t"])
            assert adm.admitted, adm.reason
            prompt_by_id[adm.request_id] = a["prompt"]
            i += 1
        if not sched.queue.pending and not sched.inflight:
            sched.advance_to(arrivals[i]["t"])
            continue
        sched.step()
    wall_s = time.perf_counter() - t0

    recs = list(sched.records)

    def moved(rs) -> int:
        return int(sum(r.n_sequences * PROMPT_LEN * ktb
                       - r.prefill_bytes_saved for r in rs))

    reg = obs.metrics
    resident = backend.prefix_pool.blocks_resident if pooled else 0
    out = {
        "policy": "pool" if pooled else "batch",
        "kv_blocks": kv_blocks,
        "completed": len(sched.completed),
        "batches": len(recs),
        "prefill_bytes_moved": moved(recs),
        "steady_prefill_bytes_moved": moved(recs[1:]),
        "prefill_bytes_saved": int(sum(r.prefill_bytes_saved for r in recs)),
        "pool_hit_blocks": int(sum(r.pool_hit_blocks for r in recs)),
        "pool_evictions": int(sum(r.pool_evictions for r in recs)),
        "pool_blocks_resident": int(resident),
        "pool_resident_bytes": int(resident * BLOCK_SIZE * ktb),
        "obs_hits": int(reg.counter(
            "serving_prefix_pool_hits_total").value()) if pooled else 0,
        "obs_misses": int(reg.counter(
            "serving_prefix_pool_misses_total").value()) if pooled else 0,
        "modeled_makespan_s": _modeled_makespan(recs),
        "wall_s": wall_s,
        "_records": recs,              # stripped before serialization
        "_prompt_by_id": prompt_by_id,
    }
    out["throughput_rps"] = out["completed"] / out["modeled_makespan_s"]
    if verbose:
        tag = out["policy"] + ("" if kv_blocks == BUDGET_BLOCKS else "+tight")
        print(f"  {tag:11s} {out['batches']:2d} batches, "
              f"prefill {out['prefill_bytes_moved'] / 1e3:.1f} kB "
              f"(steady {out['steady_prefill_bytes_moved'] / 1e3:.1f} kB), "
              f"hits {out['pool_hit_blocks']}, "
              f"evictions {out['pool_evictions']}, "
              f"{out['throughput_rps']:.3f} req per modeled s")
    return out


def _analytic_replay(records, prompt_by_id) -> Dict[str, int]:
    """Drive a pure-python trie with the recorded batch memberships and
    predict the pool's hit/miss counters. Mirrors the backend accounting:
    per request ``plen // bs`` lookupable chunks, hits capped at
    ``(plen - 1) // bs`` (at least one tail token must remain for the
    first-token logits), inserts applied after the whole batch (acquires
    see the pre-batch trie; first writer wins)."""
    from repro.serving.prefix_pool import chunk_key

    full_prefix = PROMPT_LEN // BLOCK_SIZE
    max_hit = (PROMPT_LEN - 1) // BLOCK_SIZE
    root: Dict = {}
    hits = misses = 0
    for rec in records:
        chains = []
        for entry in rec.request_entries:
            prompt = prompt_by_id[entry["id"]]
            node, depth = root, 0
            while depth < max_hit:
                key = chunk_key(prompt, depth * BLOCK_SIZE, BLOCK_SIZE)
                if key not in node:
                    break
                node = node[key]
                depth += 1
            hits += depth
            misses += full_prefix - depth
            chains.append(prompt)
        for prompt in chains:
            node = root
            for d in range(full_prefix):
                key = chunk_key(prompt, d * BLOCK_SIZE, BLOCK_SIZE)
                node = node.setdefault(key, {})
    return {"hits": hits, "misses": misses}


def _parity() -> bool:
    """Pinned acceptance parity: pooled generation must be token- and
    logprob-identical to the paged path without the trie, cold AND cache-hot,
    sampled, with a CoW partial tail block (plen % bs != 0)."""
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.serving import ExecutionBackend

    cfg = ArchConfig(**ARCH)
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    shared = rng.integers(0, ARCH["vocab_size"], size=(8,)).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(
        0, ARCH["vocab_size"], size=(3,)).astype(np.int32)])
        for _ in range(2)]                      # plen 11 -> CoW partial tail

    def gen(backend):
        h = backend.start_batch(prompts, K_SAMPLES, MAX_NEW, 0.8,
                                jax.random.key(42))
        while backend.decode_step(h):
            pass
        return backend.finalize(h)

    def same(a, b) -> bool:
        for ra, rb in zip(a, b):
            for s1, s2 in zip(ra.samples, rb.samples):
                if not np.array_equal(s1, s2):
                    return False
            if ra.logprobs != rb.logprobs:
                return False
        return True

    want = gen(ExecutionBackend(model, params, kv_blocks=64,
                                kv_block_size=BLOCK_SIZE))
    pooled = ExecutionBackend(model, params, kv_blocks=64,
                              kv_block_size=BLOCK_SIZE, kv_pool=True)
    cold = gen(pooled)                          # trie empty: all misses
    hot = gen(pooled)                           # warm: shared chain reused
    return same(cold, want) and same(hot, want)


def run(verbose: bool = True) -> Dict:
    arrivals = _arrivals()
    if verbose:
        print(f"stream: {N_REQUESTS} requests x {K_SAMPLES} samples, "
              f"{N_SYSTEM} Zipf system prompts of {SYS_LEN} + {USER_LEN} "
              f"user tokens, budget {BUDGET_BLOCKS} blocks of {BLOCK_SIZE} "
              f"(tight run: {TIGHT_BLOCKS})")
    batch = _run_stream(False, arrivals, verbose=verbose)
    pool = _run_stream(True, arrivals, verbose=verbose)
    tight = _run_stream(True, arrivals, kv_blocks=TIGHT_BLOCKS,
                        verbose=verbose)
    expected = _analytic_replay(pool["_records"], pool["_prompt_by_id"])
    hits_match = (pool["obs_hits"] == expected["hits"]
                  == pool["pool_hit_blocks"]
                  and pool["obs_misses"] == expected["misses"])
    parity_ok = _parity()
    for r in (batch, pool, tight):              # drop replay-only fields
        r.pop("_records"), r.pop("_prompt_by_id")

    steady_ratio = batch["steady_prefill_bytes_moved"] / \
        max(pool["steady_prefill_bytes_moved"], 1)
    prefill_ratio = batch["prefill_bytes_moved"] / \
        max(pool["prefill_bytes_moved"], 1)
    lookups = pool["obs_hits"] + pool["obs_misses"]
    result = {
        "seed": SEED,
        "k_samples": K_SAMPLES,
        "batch": batch,
        "pool": pool,
        "tight": tight,
        "parity_ok": parity_ok,
        "hits_match_analytic": hits_match,
        "expected_hits": expected["hits"],
        "hit_rate": pool["obs_hits"] / max(lookups, 1),
        "steady_prefill_ratio": steady_ratio,
        "prefill_bytes_ratio": prefill_ratio,
        "throughput_ratio": pool["throughput_rps"] / batch["throughput_rps"],
        "acceptance_all": bool(
            parity_ok and
            hits_match and
            steady_ratio >= 3.0 and
            pool["throughput_rps"] >= batch["throughput_rps"] and
            pool["completed"] == batch["completed"] == N_REQUESTS and
            pool["pool_evictions"] == 0 and        # budget sized to not evict
            tight["completed"] == N_REQUESTS and
            tight["pool_evictions"] > 0 and        # LRU actually reclaimed
            tight["pool_hit_blocks"] > 0),
    }
    if verbose:
        print(f"  parity_ok={parity_ok}, hits_match_analytic={hits_match} "
              f"({pool['obs_hits']} hits, rate {result['hit_rate']:.2f}), "
              f"steady prefill x{steady_ratio:.1f} less, "
              f"modeled throughput x{result['throughput_ratio']:.2f}, "
              f"acceptance_all={result['acceptance_all']}")
        print(json.dumps(result, indent=2))
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_path = None
    if "--out" in sys.argv:
        idx = sys.argv.index("--out") + 1
        if idx >= len(sys.argv):
            sys.exit("usage: prefix_pool.py [--out FILE]")
        out_path = sys.argv[idx]
    res = run()
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {out_path}", file=sys.stderr)
