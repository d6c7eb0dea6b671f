"""Preemptive serving under faults vs run-to-completion (PR 10 bench).

A seeded mixed-tier Poisson stream on the paper's 4-device edge platform,
with a mid-run chaos plan injected through the REAL `SafetyMonitor` event
bus: one device failure (+ recovery), one thermal spike, and a tight-KV
window (`kv_squeeze`). Two policies see the identical stream AND the
identical fault plan:

* ``preempt`` — decode-boundary preemption on: an interactive arrival cuts
  the lowest-priority pipeline-tail batch (victim state snapshots, its
  filled KV blocks park in the resident prefix pool, resume is a trie hit
  that prefills only the post-preemption tail), fault evictions retry with
  exponential backoff.
* ``run_to_completion`` — tier preemption off: interactive arrivals wait
  behind whatever the pipeline is serving. Device-failure eviction still
  fires (a dead placement must never run to completion — that is
  correctness, not policy), so the fault-recovery comparison is apples to
  apples.

Gates (the PR 10 robustness acceptance):
  1. zero lost — every admitted request completes under chaos, both
     policies (fault evictions are retried, never dropped);
  2. interactive p95 with preemption >= 1.5x better than run-to-completion;
  3. resume prefill bytes < 25% of what a pool-less full re-prefill of the
     preempted histories would have moved (parked chains make resume a
     trie hit);
  4. zero leaked KV blocks after drain: allocator residency equals the
     prefix-trie residency exactly, and no live batch handles remain.

Everything except wall-clock is seeded and reproducible.

Run: PYTHONPATH=src python benchmarks/preemption.py [--out FILE]
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

SEED = 0
N_REQUESTS = 36
PROMPT_LEN = 12
MAX_NEW_LONG = 16         # economy / standard decode horizon
MAX_NEW_INTERACTIVE = 4
SAMPLES = 2
TIER_MIX = (("interactive", 0.3), ("standard", 0.2), ("economy", 0.5))
OFFERED_LOAD = 1.5
KV_BLOCKS = 192
KV_BLOCK_SIZE = 4

ARCH = dict(name="preempt-bench", arch_type="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


def _build_router():
    from repro.core import Constraints, Workload
    from repro.core.devices import EDGE_PLATFORM
    from repro.models import ArchConfig
    from repro.qeil2 import (PGSAMConfig, PGSAMOrchestrator, ParetoRouter,
                             SLATier)

    cfg = ArchConfig(**ARCH)
    w = Workload(batch=1, prompt_tokens=PROMPT_LEN,
                 decode_tokens=MAX_NEW_LONG, samples=SAMPLES)
    orch = PGSAMOrchestrator(
        EDGE_PLATFORM, Constraints(latency_budget_factor=None),
        config=PGSAMConfig(seed=SEED, iters_max=1500, incremental=True),
        energy_model="v2")
    router = ParetoRouter(orch, cfg, w)
    # no hard caps: the contrast under test is pure service ORDER (tier
    # scalarization + preemption), not cap-driven batch shrinking
    router.add_tier(SLATier("interactive", energy_weight=0.0,
                            latency_weight=1.0))
    router.add_tier(SLATier("standard", energy_weight=0.5,
                            latency_weight=0.5))
    router.add_tier(SLATier("economy", energy_weight=1.0,
                            latency_weight=0.0))
    return cfg, router


def _arrivals(router) -> List[Dict]:
    """Seeded Poisson stream: interactive requests are short-horizon, the
    rest long-horizon (distinct buckets, so an interactive arrival always
    finds a *long* batch in front of it — the preemption win)."""
    rng = np.random.default_rng(SEED)
    svc = router.recost(router.route("economy").assignment,
                        router.batch_workload(1)).makespan_s
    rate = OFFERED_LOAD / svc
    names = [n for n, _ in TIER_MIX]
    probs = [p for _, p in TIER_MIX]
    t, out = 0.0, []
    for _ in range(N_REQUESTS):
        t += rng.exponential(1.0 / rate)
        tier = names[rng.choice(len(names), p=probs)]
        out.append({
            "t": t, "tier": tier,
            "max_new": (MAX_NEW_INTERACTIVE if tier == "interactive"
                        else MAX_NEW_LONG),
            "prompt": rng.integers(0, ARCH["vocab_size"],
                                   size=(PROMPT_LEN,)).astype(np.int32)})
    return out


def _chaos_plan(router, arrivals) -> "object":
    """Mid-run plan pinned to the arrival stream's own timeline: a failure
    of the device the economy tier actually routes onto (so in-flight
    batches are hit), a thermal spike, and a tight-KV window."""
    from repro.serving.chaos import FaultAction, FaultPlan

    dev = router.route("economy").assignment.device_names()[0]
    t_fail = arrivals[N_REQUESTS // 3]["t"]
    t_spike = arrivals[N_REQUESTS // 2]["t"]
    t_squeeze = arrivals[N_REQUESTS // 4]["t"]
    horizon = arrivals[-1]["t"]
    return FaultPlan(seed=SEED, actions=[
        FaultAction(t_squeeze, "kv_squeeze", value=float(KV_BLOCKS // 3),
                    detail="tight KV window"),
        FaultAction(t_fail, "device_fail", device=dev, detail="injected"),
        FaultAction(t_fail + 0.25 * horizon, "device_recover", device=dev),
        FaultAction(t_spike, "thermal_spike", device=dev, value=96.0),
        FaultAction(t_spike + 0.1 * horizon, "kv_squeeze", value=0.0),
    ])


def _make_backend(cfg):
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serving import ExecutionBackend

    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    return ExecutionBackend(model, params, kv_blocks=KV_BLOCKS,
                            kv_block_size=KV_BLOCK_SIZE, kv_pool=True)


def _drive(sched, arrivals, chaos) -> None:
    """Replay the stream; the chaos driver pumps on the same sim clock the
    scheduler advances, so injected faults land on live batches."""
    i = 0
    while i < len(arrivals) or sched.queue.pending or sched.inflight:
        # the sim clock only advances at batch boundaries; a fault whose
        # t_s falls inside an in-flight batch's service window must land
        # while that batch is still in flight (it gets preempted mid-run,
        # not conveniently after retiring)
        now = max([sched.clock] + [e.done_t - 1e-12
                                   for e in sched.inflight])
        chaos.apply_due(now)
        horizon = max(sched.clock, sched.pipeline_free_t)
        while i < len(arrivals) and arrivals[i]["t"] <= horizon:
            a = arrivals[i]
            adm = sched.submit(a["prompt"], tier=a["tier"],
                               n_samples=SAMPLES, max_new_tokens=a["max_new"],
                               arrival_s=a["t"])
            assert adm.admitted, adm.reason
            i += 1
        if not sched.queue.pending and not sched.inflight:
            sched.advance_to(arrivals[i]["t"])
            continue
        # if everything queued is backoff-parked but the stream has more
        # arrivals first, advance to the arrival — otherwise step() would
        # jump the clock past it to the retry instant and every later
        # request would inherit a phantom backoff wait
        nb = sched.queue.earliest_not_before()
        if (not sched.inflight and i < len(arrivals)
                and sched.queue.peek_ready(sched.clock) is None
                and nb is not None and arrivals[i]["t"] < nb):
            sched.advance_to(arrivals[i]["t"])
            continue
        if not sched.step() and (sched.queue.pending or sched.inflight):
            # starved mid-chaos (e.g. KV squeeze): advance to the next
            # chaos action or arrival so the squeeze can release
            nxt = [a["t"] for a in arrivals[i:]]
            nxt += [c.t_s for c in chaos._pending]
            if not nxt:
                raise RuntimeError("scheduler starved with no future event")
            sched.advance_to(min(x for x in nxt if x > sched.clock))
    chaos.apply_due(float("inf"))          # flush trailing actions


def _run_policy(cfg, router_factory, arrivals, preempt: bool,
                verbose: bool) -> Dict:
    from repro.core.devices import EDGE_PLATFORM
    from repro.core.safety import SafetyMonitor
    from repro.serving import ContinuousBatchingScheduler, SchedulerConfig
    from repro.serving.chaos import attach

    router = router_factory()
    backend = _make_backend(cfg)
    # retry backoff on the stream's own timescale (~2 batch services):
    # an absolute constant here would dwarf the sub-millisecond sim horizon
    # and turn one fault into a global stall for both policies
    svc = router.recost(router.route("economy").assignment,
                        router.batch_workload(1)).makespan_s
    sched = ContinuousBatchingScheduler(
        backend, router,
        # one in-flight batch: the pipeline is a single serialized server,
        # so head-of-line blocking is real and the only way an interactive
        # arrival gets ahead of a long economy batch is to preempt it
        SchedulerConfig(max_batch_requests=4, max_inflight_batches=1,
                        max_new_tokens=MAX_NEW_LONG, seed=SEED,
                        preempt=preempt, retry_backoff_s=2.0 * svc,
                        max_retries=8))
    safety = SafetyMonitor(EDGE_PLATFORM)
    chaos = attach(_chaos_plan(router, arrivals), safety, sched)
    _drive(sched, arrivals, chaos)

    s = sched.stats()
    alloc = backend.allocator
    leaked = (alloc.blocks_in_use - backend.prefix_pool.blocks_resident
              + len(backend._live))
    out = {
        "completed": s["completed"],
        "cancelled": s["cancelled"],
        "batches": s["batches"],
        "p95_latency_s": s["latency_p95_s"],
        "preemptions": s["preemptions"],
        "preemptions_total": s["preemptions_total"],
        "retries_total": s["retries_total"],
        "resume_full_tokens": s["resume_full_tokens"],
        "resume_tail_tokens": s["resume_tail_tokens"],
        "chaos_applied": len(chaos.applied),
        "leaked_blocks": int(leaked),
    }
    if verbose:
        name = "preempt" if preempt else "run_to_completion"
        print(f"  {name}: {out['completed']}/{N_REQUESTS} done, "
              f"{out['preemptions_total']} preemptions {out['preemptions']}, "
              f"{out['retries_total']} retries, "
              f"p95[interactive]={out['p95_latency_s'].get('interactive', 0):.3f}s, "
              f"leaked={out['leaked_blocks']}")
    return out


def run(verbose: bool = True) -> Dict:
    cfg, router0 = _build_router()
    arrivals = _arrivals(router0)
    plan = _chaos_plan(router0, arrivals)
    if verbose:
        mix: Dict[str, int] = {}
        for a in arrivals:
            mix[a["tier"]] = mix.get(a["tier"], 0) + 1
        print(f"stream: {N_REQUESTS} requests, tier mix {mix}, "
              f"offered load {OFFERED_LOAD}x; chaos: "
              f"{[(a.kind, f'{a.t_s * 1e3:.2f}ms') for a in plan.actions]}")

    # each policy gets its own router (its own healthy-set state machine)
    # over an identically-seeded frontier
    def router_factory():
        return _build_router()[1]

    pre = _run_policy(cfg, router_factory, arrivals, True, verbose)
    rtc = _run_policy(cfg, router_factory, arrivals, False, verbose)

    p95_pre = pre["p95_latency_s"].get("interactive", float("inf"))
    p95_rtc = rtc["p95_latency_s"].get("interactive", 0.0)
    p95_ratio = p95_rtc / max(p95_pre, 1e-12)
    tail_ratio = (pre["resume_tail_tokens"]
                  / max(pre["resume_full_tokens"], 1))
    gates = {
        "zero_lost": bool(pre["completed"] == N_REQUESTS
                          and rtc["completed"] == N_REQUESTS
                          and pre["cancelled"] == 0
                          and rtc["cancelled"] == 0),
        "interactive_p95_gain_ok": bool(p95_ratio >= 1.5),
        "resume_bytes_ok": bool(pre["resume_full_tokens"] > 0
                                and tail_ratio < 0.25),
        "zero_leaked": bool(pre["leaked_blocks"] == 0
                            and rtc["leaked_blocks"] == 0),
        "chaos_fully_applied": bool(
            pre["chaos_applied"] == len(plan.actions)
            and rtc["chaos_applied"] == len(plan.actions)),
        "faults_recovered": bool(pre["retries_total"] > 0
                                 and rtc["retries_total"] > 0),
    }
    result = {
        "seed": SEED,
        "n_requests": N_REQUESTS,
        "offered_load": OFFERED_LOAD,
        "chaos_actions": [(a.kind, a.device, a.value)
                          for a in plan.actions],
        "preempt": pre,
        "run_to_completion": rtc,
        "interactive_p95_ratio": p95_ratio,
        "resume_tail_ratio": tail_ratio,
        "gates": gates,
        "acceptance_all": all(gates.values()),
    }
    if verbose:
        print(f"  interactive p95: {p95_rtc:.3f}s -> {p95_pre:.3f}s "
              f"(x{p95_ratio:.2f}, gate >= 1.5)")
        print(f"  resume prefill: {pre['resume_tail_tokens']} of "
              f"{pre['resume_full_tokens']} tokens moved "
              f"({tail_ratio:.1%}, gate < 25%)")
        print(f"  gates: {gates}")
        print(f"  acceptance_all={result['acceptance_all']}")
        print(json.dumps(result, indent=2))
    return result


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_path: Optional[str] = None
    if "--out" in sys.argv:
        idx = sys.argv.index("--out") + 1
        if idx >= len(sys.argv):
            sys.exit("usage: preemption.py [--out FILE]")
        out_path = sys.argv[idx]
    res = run()
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {out_path}", file=sys.stderr)
    if not res["acceptance_all"]:
        sys.exit(1)
