"""Paged KV cache vs dense per-batch cache (PR 5 tentpole bench).

A seeded mixed-tier request stream where every request carries the paper's
repeated-sampling budget (k = 4 samples per prompt — the EAC/ARDE cascade
shape). Two backends see the identical stream at the *identical KV memory
budget* (same bytes; dense counts sequence slots, paged counts fixed-size
blocks):

* ``dense``  — the pre-PR backend: every repeat is prefilled independently
  and the batch holds ``B x (plen + max_new)`` rows until retirement.
* ``paged``  — `BlockAllocator` + block tables into one resident cache
  of the whole block budget: one prefill per unique prompt, repeats share
  prefix blocks (copy-on-write at the first divergent token), admission
  priced at shared-prefix cost.

Reported per policy: prefill bytes moved (KV bytes written during prefill —
the row-linear traffic the roofline model says dominates edge prefill), the
KV high-water mark in bytes (dense: live batches' slot rows; paged: blocks
in use — the resident cache itself is the budget's bytes from the first
batch on), and ``throughput_rps``: completed requests over the scheduler's
*simulated* pipeline makespan. That is a count of admission concurrency
under one service model for both policies (paged fits more requests per
batch into the same bytes), not a measured speed. A third run adds CSVET
early-stops (once one sample of a prompt verifies, pass@k cannot change —
the remaining repeats' private blocks are released mid-flight and the next
batch can use them at once).

Acceptance (seeded, CI-gated): paged moves >= 2x fewer prefill bytes at
k = 4, holds a strictly lower KV high-water mark, completes at least as
many requests per simulated second as dense at equal memory, and is
token/logprob bit-identical to dense on a pinned sub-stream; the CSVET run
completes everything, frees blocks mid-flight and never holds more blocks
than the budget.

Run: PYTHONPATH=src python benchmarks/kv_paging.py [--out FILE]
"""
from __future__ import annotations

import json
import sys
import time
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

SEED = 0
N_REQUESTS = 16
PROMPT_LEN = 12
MAX_NEW = 8
K_SAMPLES = 4                        # repeated-sampling budget per prompt
BLOCK_SIZE = 4
TIER_MIX = (("interactive", 0.3), ("standard", 0.4), ("economy", 0.3))
# equal-memory budget: 8 dense sequence slots' worth of KV rows
BUDGET_SLOTS = 8
BUDGET_ROWS = BUDGET_SLOTS * (PROMPT_LEN + MAX_NEW)
BUDGET_BLOCKS = BUDGET_ROWS // BLOCK_SIZE
# simulated per-batch service model (identical for both policies):
# fixed pipeline overhead + per-sequence decode cost
BATCH_BASE_S = 1.0
PER_SEQ_S = 0.25

ARCH = dict(name="kv-bench", arch_type="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


class _FixedRouter:
    """Deterministic routing double: this bench measures memory/bytes/
    admission concurrency, not SLA routing (serving_schedule.py gates
    that), so every batch gets the same simulated operating point."""

    def __init__(self, tiers):
        self.tiers = {t: SimpleNamespace(name=t) for t in tiers}

    def resolve_tier(self, tier):
        return self.tiers[tier] if isinstance(tier, str) else tier

    def required_samples(self, tier):
        return None

    def route_batch(self, tiers, **kw):
        n_seqs = kw.get("samples", 1) * len(tiers)
        return SimpleNamespace(
            tier=self.resolve_tier(tiers[0]), tier_counts={},
            assignment=object(), point_index=0, meets_caps=True,
            batch_costs=None, energy_j=float(n_seqs),
            latency_s=BATCH_BASE_S + PER_SEQ_S * n_seqs, notes=[])


def _arrivals() -> List[Dict]:
    rng = np.random.default_rng(SEED)
    names = [n for n, _ in TIER_MIX]
    probs = [p for _, p in TIER_MIX]
    t, out = 0.0, []
    for _ in range(N_REQUESTS):
        t += rng.exponential(0.5)
        out.append({"t": t, "tier": names[rng.choice(len(names), p=probs)],
                    "prompt": rng.integers(0, ARCH["vocab_size"],
                                           size=(PROMPT_LEN,)
                                           ).astype(np.int32)})
    return out


def _run_stream(paged: bool, arrivals, early_stop: bool = False,
                verbose: bool = True) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.models.cache import kv_bytes_per_token
    from repro.serving import (ContinuousBatchingScheduler, ExecutionBackend,
                               SchedulerConfig)

    cfg = ArchConfig(**ARCH)
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    ktb = kv_bytes_per_token(cfg, 4)            # f32 model
    if paged:
        backend = ExecutionBackend(model, params, kv_blocks=BUDGET_BLOCKS,
                                   kv_block_size=BLOCK_SIZE)
    else:
        backend = ExecutionBackend(model, params, max_slots=BUDGET_SLOTS)
    sched = ContinuousBatchingScheduler(
        backend, _FixedRouter([n for n, _ in TIER_MIX]),
        SchedulerConfig(max_batch_requests=8, max_inflight_batches=2,
                        max_new_tokens=MAX_NEW, seed=SEED))

    def kv_bytes_now() -> int:
        if paged:
            return backend.allocator.blocks_in_use * BLOCK_SIZE * ktb
        return backend.slots_in_use * (PROMPT_LEN + MAX_NEW) * ktb

    stop_rng = np.random.default_rng(SEED + 1)
    stopped: set = set()
    high_water = 0
    blocks_freed_early = 0
    i = 0
    t0 = time.perf_counter()
    while i < len(arrivals) or sched.queue.pending or sched.inflight:
        horizon = max(sched.clock, sched.pipeline_free_t)
        while i < len(arrivals) and arrivals[i]["t"] <= horizon:
            a = arrivals[i]
            adm = sched.submit(a["prompt"], tier=a["tier"],
                               n_samples=K_SAMPLES, arrival_s=a["t"])
            assert adm.admitted, adm.reason
            i += 1
        if not sched.queue.pending and not sched.inflight:
            sched.advance_to(arrivals[i]["t"])
            continue
        sched.step()
        high_water = max(high_water, kv_bytes_now())
        if early_stop:
            # CSVET signal (simulated, seeded): once one sample of a prompt
            # verifies, the remaining repeats cannot change pass@k — their
            # private blocks go back to the free list mid-flight
            for entry in list(sched.inflight):
                if entry.handle.step < 2:
                    continue
                for r in entry.requests:
                    if r.id not in stopped and stop_rng.random() < 0.5:
                        stopped.add(r.id)
                        blocks_freed_early += sched.early_stop(
                            r.id, list(range(1, r.n_samples)))
    wall_s = time.perf_counter() - t0

    recs = list(sched.records)
    seqs = sum(r.n_sequences for r in recs)
    prefill_moved = seqs * PROMPT_LEN * ktb \
        - sum(r.prefill_bytes_saved for r in recs)
    out = {
        "policy": "paged" if paged else "dense",
        "early_stop": early_stop,
        "completed": len(sched.completed),
        "batches": len(recs),
        "mean_batch_requests": float(np.mean([r.n_requests for r in recs])),
        "prefill_bytes_moved": int(prefill_moved),
        "prefill_bytes_saved": int(sum(r.prefill_bytes_saved for r in recs)),
        "kv_high_water_bytes": int(high_water),
        "kv_budget_bytes": int(BUDGET_ROWS * ktb),
        "makespan_s": sched.pipeline_free_t,
        "throughput_rps": len(sched.completed) / sched.pipeline_free_t,
        "blocks_freed_early": int(blocks_freed_early),
        "wall_s": wall_s,
    }
    if verbose:
        tag = out["policy"] + ("+csvet" if early_stop else "")
        print(f"  {tag:12s} {out['batches']:2d} batches "
              f"(mean {out['mean_batch_requests']:.1f} req), "
              f"prefill {out['prefill_bytes_moved'] / 1e3:.0f} kB, "
              f"high-water {out['kv_high_water_bytes'] / 1e3:.0f} kB, "
              f"{out['throughput_rps']:.2f} req per simulated s")
    return out


def _parity() -> bool:
    """Pinned sub-stream: paged generation (prefix sharing + CoW) must be
    token- and logprob-identical to dense."""
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.serving import ExecutionBackend

    cfg = ArchConfig(**ARCH)
    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, ARCH["vocab_size"],
                            size=(PROMPT_LEN - 1,)).astype(np.int32)
               for _ in range(2)]                  # plen % block != 0 -> CoW

    def gen(backend):
        h = backend.start_batch(prompts, K_SAMPLES, MAX_NEW, 0.8,
                                jax.random.key(42))
        while backend.decode_step(h):
            pass
        return backend.finalize(h)

    want = gen(ExecutionBackend(model, params))
    got = gen(ExecutionBackend(model, params, kv_blocks=64,
                               kv_block_size=BLOCK_SIZE))
    for a, b in zip(want, got):
        for s1, s2 in zip(a.samples, b.samples):
            if not np.array_equal(s1, s2):
                return False
        if a.logprobs != b.logprobs:
            return False
    return True


def run(verbose: bool = True) -> Dict:
    arrivals = _arrivals()
    if verbose:
        print(f"stream: {N_REQUESTS} requests x {K_SAMPLES} samples, "
              f"prompt {PROMPT_LEN} + {MAX_NEW} new, KV budget "
              f"{BUDGET_SLOTS} slots == {BUDGET_BLOCKS} blocks "
              f"of {BLOCK_SIZE}")
    dense = _run_stream(False, arrivals, verbose=verbose)
    paged = _run_stream(True, arrivals, verbose=verbose)
    csvet = _run_stream(True, arrivals, early_stop=True, verbose=verbose)
    parity_ok = _parity()

    prefill_ratio = dense["prefill_bytes_moved"] / \
        max(paged["prefill_bytes_moved"], 1)
    result = {
        "seed": SEED,
        "k_samples": K_SAMPLES,
        "kv_budget_bytes": dense["kv_budget_bytes"],
        "dense": dense,
        "paged": paged,
        "paged_csvet": csvet,
        "parity_ok": parity_ok,
        "prefill_bytes_ratio": prefill_ratio,
        "high_water_ratio": dense["kv_high_water_bytes"] /
        max(paged["kv_high_water_bytes"], 1),
        "throughput_ratio": paged["throughput_rps"] /
        dense["throughput_rps"],
        "acceptance_all": bool(
            parity_ok and
            prefill_ratio >= 2.0 and
            paged["kv_high_water_bytes"] < dense["kv_high_water_bytes"] and
            paged["throughput_rps"] >= dense["throughput_rps"] and
            paged["completed"] == dense["completed"] == N_REQUESTS and
            csvet["completed"] == N_REQUESTS and
            csvet["blocks_freed_early"] > 0 and
            csvet["kv_high_water_bytes"] <= csvet["kv_budget_bytes"]),
    }
    if verbose:
        print(f"  parity_ok={parity_ok}, prefill bytes x{prefill_ratio:.1f} "
              f"less, high-water x{result['high_water_ratio']:.2f} lower, "
              f"simulated throughput x{result['throughput_ratio']:.2f}, "
              f"csvet freed {csvet['blocks_freed_early']} blocks early, "
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
            sys.exit("usage: kv_paging.py [--out FILE]")
        out_path = sys.argv[idx]
    res = run()
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {out_path}", file=sys.stderr)
