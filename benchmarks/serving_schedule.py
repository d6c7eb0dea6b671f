"""Mixed-tier continuous batching vs per-call routing (PR 4 tentpole bench).

A seeded Poisson request stream on the paper's 4-device edge platform: three
SLA tiers (interactive / standard / economy) arrive interleaved at an
offered load sized to overload *per-call* serving. Two policies see the
identical stream:

* ``scheduler`` — the scheduler-centric stack: tier-aware admission,
  mixed-tier batches routed to one shared operating point (`route_batch`
  re-costs every frontier point under the batch workload, so decode
  weight-streaming amortization is priced into feasibility), prefill/decode
  interleaving over the real execution backend (tiny model, this
  container's CPU). Latencies are simulated (operating-point makespans on a
  serialized pipeline) — the same clock the SLA caps are defined on.
* ``per_call`` — the pre-refactor world: every request is its own
  `generate` call at its tier's `route()` operating point, serialized in
  arrival order (what `RoutedServingEngine` did before it became a shim).

Reported per policy: throughput (requests/s over the simulated makespan),
per-tier p95 latency (queue delay + service), and IPW (served sequences per
joule). Acceptance: the scheduler beats per-call routing on throughput at
equal-or-better per-tier p95 latency, with equal-or-better IPW — batching
amortizes the decode weight re-streaming that dominates edge inference
energy, which is exactly the paper's repeated-sampling amortization argument
lifted from one call to the whole request stream.

Everything except wall-clock is seeded and reproducible.

The bench doubles as the observability overhead gate: after the official
(obs-off) run, the identical stream replays twice more over the same warm
backend — once instrumented (`backend.set_obs`), once not — and the result's
``obs`` section reports (a) bit-parity of sampled tokens/logprobs between the
off and on runs (instrumentation must not perturb the RNG stream), (b) span
lifecycle completeness (every request reconstructs admit -> queue ->
schedule -> prefill -> decode -> release), and (c) the relative wall-clock
overhead of running instrumented, gated at <5% in CI.

Run: PYTHONPATH=src python benchmarks/serving_schedule.py \
         [--out FILE] [--spans-out FILE] [--metrics-out FILE]
"""
from __future__ import annotations

import json
import sys
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

SEED = 0
N_REQUESTS = 48
PROMPT_LEN = 12
MAX_NEW = 8
SAMPLES = 2
TIER_MIX = (("interactive", 0.3), ("standard", 0.4), ("economy", 0.3))
# offered load relative to per-call capacity at the standard tier's
# operating point: > 1 means per-call serving cannot keep up
OFFERED_LOAD = 1.6

ARCH = dict(name="sched-bench", arch_type="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


def _build_router():
    from repro.core import Constraints, Workload
    from repro.core.devices import EDGE_PLATFORM
    from repro.models import ArchConfig
    from repro.qeil2 import (PGSAMConfig, PGSAMOrchestrator, ParetoRouter,
                             SLATier)

    cfg = ArchConfig(**ARCH)
    w = Workload(batch=1, prompt_tokens=PROMPT_LEN, decode_tokens=MAX_NEW,
                 samples=SAMPLES)
    orch = PGSAMOrchestrator(
        EDGE_PLATFORM, Constraints(latency_budget_factor=None),
        config=PGSAMConfig(seed=SEED, iters_max=1500, incremental=True),
        energy_model="v2")
    router = ParetoRouter(orch, cfg, w)
    # caps are data-driven off the frontier so they are feasible by
    # construction at moderate batch sizes: interactive admits batches of
    # ~4 at the fastest point, standard of ~8 — a tight-SLA member caps how
    # much batching its batch absorbs (the scheduler's shrink loop)
    c4 = min(router.recost(a, router.batch_workload(4)).makespan_s
             for a in router.frontier)
    c8 = min(router.recost(a, router.batch_workload(8)).makespan_s
             for a in router.frontier)
    router.add_tier(SLATier("interactive", latency_p99_s=1.01 * c4,
                            energy_weight=0.0, latency_weight=1.0))
    router.add_tier(SLATier("standard", latency_p99_s=1.05 * c8,
                            energy_weight=0.5, latency_weight=0.5))
    router.add_tier(SLATier("economy", energy_weight=1.0,
                            latency_weight=0.0))
    return cfg, w, router


def _arrivals(router) -> List[Dict]:
    """Seeded Poisson stream; rate sized against per-call standard-tier
    service time so per-call serving runs at OFFERED_LOAD utilization."""
    rng = np.random.default_rng(SEED)
    svc = router.recost(router.route("standard").assignment,
                        router.batch_workload(1)).makespan_s
    rate = OFFERED_LOAD / svc
    names = [n for n, _ in TIER_MIX]
    probs = [p for _, p in TIER_MIX]
    t = 0.0
    out = []
    for _ in range(N_REQUESTS):
        t += rng.exponential(1.0 / rate)
        out.append({"t": t, "tier": names[rng.choice(len(names), p=probs)],
                    "prompt": rng.integers(
                        0, ARCH["vocab_size"],
                        size=(PROMPT_LEN,)).astype(np.int32)})
    return out


def _percentiles(lat: Dict[str, List[float]]) -> Dict[str, float]:
    return {t: float(np.percentile(v, 95)) for t, v in sorted(lat.items())}


def _make_backend(cfg):
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serving import ExecutionBackend

    model = Model(cfg, dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    return ExecutionBackend(model, params)


def _drive(sched, arrivals) -> float:
    """Replay the stream through the scheduler; returns wall seconds spent."""
    t0 = time.perf_counter()
    i = 0
    while i < len(arrivals) or sched.queue.pending or sched.inflight:
        horizon = max(sched.clock, sched.pipeline_free_t)
        while i < len(arrivals) and arrivals[i]["t"] <= horizon:
            a = arrivals[i]
            sched.submit(a["prompt"], tier=a["tier"], n_samples=SAMPLES,
                         arrival_s=a["t"])
            i += 1
        if not sched.queue.pending and not sched.inflight:
            sched.advance_to(arrivals[i]["t"])
            continue
        sched.step()
    return time.perf_counter() - t0


def _sampled(sched) -> Dict[int, Tuple]:
    """Bit-parity fingerprint: per request, sampled tokens + logprobs."""
    return {rid: ([s.tolist() for s in c.result.samples],
                  [float(lp) for lp in c.result.logprobs])
            for rid, c in sched.completed.items()}


def _run_scheduler(cfg, router, arrivals, verbose: bool, backend=None,
                   obs=None) -> Tuple[Dict, "object"]:
    from repro.qeil2 import TraceStore
    from repro.serving import ContinuousBatchingScheduler, SchedulerConfig

    if backend is None:
        backend = _make_backend(cfg)
    trace = TraceStore()
    sched = ContinuousBatchingScheduler(
        backend, router,
        SchedulerConfig(max_batch_requests=8, max_inflight_batches=2,
                        max_new_tokens=MAX_NEW, seed=SEED), trace=trace,
        obs=obs)
    wall_s = _drive(sched, arrivals)

    s = sched.stats()
    out = {
        "completed": s["completed"],
        "batches": s["batches"],
        "mean_batch_requests": s["mean_batch_requests"],
        "caps_met_fraction": s["caps_met_fraction"],
        "throughput_rps": s["completed"] / s["makespan_s"],
        "p95_latency_s": s["latency_p95_s"],
        "energy_j": s["energy_j"],
        "ipw_seq_per_j": s["sequences"] / max(s["energy_j"], 1e-12),
        "serve_trace_records": len(trace.records("serve")),
        "wall_s": wall_s,
    }
    if verbose:
        print(f"  scheduler: {out['batches']} batches "
              f"(mean {out['mean_batch_requests']:.1f} req/batch), "
              f"{out['throughput_rps']:.1f} req/s, "
              f"ipw={out['ipw_seq_per_j']:.3f} seq/J, "
              f"caps met {out['caps_met_fraction']:.0%}")
    return out, sched


def _run_per_call(router, arrivals, verbose: bool) -> Dict:
    """Analytic per-call baseline: each request served alone at its tier's
    routed point, serialized in arrival order (identical cost model)."""
    free = 0.0
    energy = 0.0
    lat: Dict[str, List[float]] = {}
    for a in arrivals:
        d = router.route(a["tier"])
        costs = router.recost(d.assignment, router.batch_workload(1))
        start = max(a["t"], free)
        free = start + costs.makespan_s
        energy += costs.energy_j
        lat.setdefault(a["tier"], []).append(free - a["t"])
    n = len(arrivals)
    out = {
        "completed": n,
        "throughput_rps": n / free,
        "p95_latency_s": _percentiles(lat),
        "energy_j": energy,
        "ipw_seq_per_j": n * SAMPLES / max(energy, 1e-12),
    }
    if verbose:
        print(f"  per_call:  serialized, {out['throughput_rps']:.1f} req/s, "
              f"ipw={out['ipw_seq_per_j']:.3f} seq/J")
    return out


def _run_obs_gate(cfg, router, arrivals, backend, reference: Dict[int, Tuple],
                  verbose: bool) -> Tuple[Dict, "object"]:
    """Replay the stream twice over the warm shared backend — obs off then
    obs on (`backend.set_obs` flips instrumentation without cold jit) — and
    gate parity, lifecycle completeness, and relative overhead."""
    from repro.obs import lifecycles_complete, make_observability

    wall_off = []
    wall_on = []
    obs_sched = None
    for rep in range(2):                      # interleave off/on, take mins
        off_out, _ = _run_scheduler(cfg, router, arrivals, False,
                                    backend=backend)
        wall_off.append(off_out["wall_s"])
        obs = make_observability()
        backend.set_obs(obs)
        try:
            on_out, obs_sched = _run_scheduler(cfg, router, arrivals, False,
                                               backend=backend, obs=obs)
        finally:
            from repro.obs import NULL_OBS
            backend.set_obs(NULL_OBS)
        wall_on.append(on_out["wall_s"])

    tracer = obs_sched.obs.tracer
    parity_ok = _sampled(obs_sched) == reference
    life_ok = lifecycles_complete(tracer.spans,
                                  expect_requests=len(reference))
    t_off, t_on = min(wall_off), min(wall_on)
    overhead = t_on / t_off - 1.0
    gate = {
        "parity_ok": bool(parity_ok),
        "span_lifecycle_ok": bool(life_ok),
        "n_spans": len(tracer),
        "wall_off_s": t_off,
        "wall_on_s": t_on,
        "overhead_frac": overhead,
        "overhead_ok": bool(overhead < 0.05),
    }
    if verbose:
        print(f"  obs gate:  parity={parity_ok} lifecycle={life_ok} "
              f"spans={len(tracer)} overhead={overhead:+.1%} "
              f"(off {t_off:.2f}s / on {t_on:.2f}s)")
    return gate, obs_sched


def run(verbose: bool = True) -> Dict:
    cfg, _w, router = _build_router()
    arrivals = _arrivals(router)
    if verbose:
        mix = {}
        for a in arrivals:
            mix[a["tier"]] = mix.get(a["tier"], 0) + 1
        print(f"stream: {N_REQUESTS} requests, tier mix {mix}, "
              f"offered load {OFFERED_LOAD}x per-call capacity")
    backend = _make_backend(cfg)
    sched, sched_obj = _run_scheduler(cfg, router, arrivals, verbose,
                                      backend=backend)
    base = _run_per_call(router, arrivals, verbose)
    obs_gate, obs_sched = _run_obs_gate(cfg, router, arrivals, backend,
                                        _sampled(sched_obj), verbose)
    run._obs_sched = obs_sched        # artifact hook for __main__

    tiers = sorted(base["p95_latency_s"])
    p95_ok = {t: sched["p95_latency_s"][t] <= base["p95_latency_s"][t] *
              (1 + 1e-9) for t in tiers}
    result = {
        "seed": SEED,
        "n_requests": N_REQUESTS,
        "offered_load": OFFERED_LOAD,
        "scheduler": sched,
        "per_call": base,
        "throughput_ratio": sched["throughput_rps"] / base["throughput_rps"],
        "ipw_ratio": sched["ipw_seq_per_j"] / base["ipw_seq_per_j"],
        "p95_no_worse": p95_ok,
        "obs": obs_gate,
        # overhead_ok is wall-clock (noisy on shared runners) so it gates a
        # separate CI assert, not the seeded acceptance bit
        "acceptance_all": bool(
            sched["throughput_rps"] > base["throughput_rps"] and
            all(p95_ok.values()) and
            sched["ipw_seq_per_j"] >= base["ipw_seq_per_j"] and
            sched["completed"] == N_REQUESTS and
            obs_gate["parity_ok"] and obs_gate["span_lifecycle_ok"]),
    }
    if verbose:
        for t in tiers:
            print(f"  p95[{t:12s}] scheduler {sched['p95_latency_s'][t]:.4f}s"
                  f" vs per-call {base['p95_latency_s'][t]:.4f}s "
                  f"ok={p95_ok[t]}")
        print(f"  throughput x{result['throughput_ratio']:.2f}, "
              f"ipw x{result['ipw_ratio']:.2f}, "
              f"acceptance_all={result['acceptance_all']}")
        print(json.dumps(result, indent=2))
    return result


def _flag(name: str) -> Optional[str]:
    if name not in sys.argv:
        return None
    idx = sys.argv.index(name) + 1
    if idx >= len(sys.argv):
        sys.exit("usage: serving_schedule.py [--out FILE] "
                 "[--spans-out FILE] [--metrics-out FILE]")
    return sys.argv[idx]


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    out_path = _flag("--out")
    spans_path = _flag("--spans-out")
    metrics_path = _flag("--metrics-out")
    res = run()
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {out_path}", file=sys.stderr)
    obs_sched = getattr(run, "_obs_sched", None)
    if spans_path and obs_sched is not None:
        obs_sched.obs.tracer.save(spans_path)
        print(f"wrote {spans_path}", file=sys.stderr)
    if metrics_path and obs_sched is not None:
        obs_sched.obs.metrics.write(metrics_path)
        print(f"wrote {metrics_path}", file=sys.stderr)
