"""Speculative multi-token decode vs plain decode (PR 8 tentpole bench).

The same seeded mixed-tier greedy request stream is served three ways
through the paged backend, the v2-costed PGSAM router, and the
`repro.spec.SpecPlanner` (which sweeps draft depths through the router's
spec-priced workload per formed batch):

* ``off``   — plain one-token-per-step decode (the PR 5/6 baseline).
* ``ngram`` — prompt-lookup drafting: free proposals, but a random-init
  model accepts almost none of them (~1/vocab per token).
* ``draft`` — draft model == target model: the deterministic accept-rate
  fixture. Greedy verify accepts every proposal, so each verify step
  commits n + 1 tokens for roughly one token's weight-stream cost.

Reported per variant: completed requests, committed tokens per decode
forward (the architecture-level speedup — decode is memory-bound, so
forwards are the unit wall-clock is proportional to), routed v2 energy at
the planner's priced depth, IPW, and the accept rates measured from the
scheduler's "spec" trace records. After each spec variant the bench closes
the calibration loop: `CalibrationFitter` fits the measured accept rates
into a profile, the planner refreshes, and the bench re-routes one batch —
the draft fixture must keep its full depth, the ngram variant must flip
drafting off (depth 0) purely by losing the price comparison.

Acceptance (seeded, CI-gated): every variant completes the stream; greedy
speculative decode is token-identical to plain decode (logprobs allclose)
for both policies on the engine path; the draft fixture commits >= 1.5x
tokens per decode forward with energy per request no worse than ``off``;
the fitter recovers the planted accept rates (draft ~1.0, ngram low); and
the refreshed planner picks depth 0 for ngram, full depth for draft.

Run: PYTHONPATH=src python benchmarks/spec_decode.py [--out FILE]
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List

import numpy as np

SEED = 0
N_REQUESTS = 10
PROMPT_LEN = 16
MAX_NEW = 12
K_SAMPLES = 1
SPEC_N = 4
BLOCK_SIZE = 4
KV_BLOCKS = 160
TIER_MIX = (("interactive", 0.3), ("standard", 0.4), ("economy", 0.3))
SPEEDUP_FLOOR = 1.5            # committed tokens per decode forward, draft
LOGPROB_ATOL = 3e-5            # one verify forward vs n single-token
                               # forwards: same math, different matmul
                               # reduction order (f32 ~1e-6 per element)

ARCH = dict(name="spec-bench", arch_type="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


def _build_router():
    from repro.core import Constraints, Workload
    from repro.core.devices import EDGE_PLATFORM
    from repro.models import ArchConfig
    from repro.qeil2 import (PGSAMConfig, PGSAMOrchestrator, ParetoRouter,
                             SLATier)

    cfg = ArchConfig(**ARCH)
    w = Workload(batch=1, prompt_tokens=PROMPT_LEN, decode_tokens=MAX_NEW,
                 samples=K_SAMPLES)
    orch = PGSAMOrchestrator(
        EDGE_PLATFORM, Constraints(latency_budget_factor=None),
        config=PGSAMConfig(seed=SEED, iters_max=1500, incremental=True),
        energy_model="v2")
    router = ParetoRouter(orch, cfg, w)
    # tiers mirror serving_schedule.py: latency caps data-driven off the
    # frontier so they are feasible by construction; economy is pure-energy,
    # which is where speculative pricing shows the starkest depth choice
    c8 = min(router.recost(a, router.batch_workload(8)).makespan_s
             for a in router.frontier)
    router.add_tier(SLATier("interactive", latency_p99_s=1.05 * c8,
                            energy_weight=0.0, latency_weight=1.0))
    router.add_tier(SLATier("standard", latency_p99_s=1.25 * c8,
                            energy_weight=0.5, latency_weight=0.5))
    router.add_tier(SLATier("economy", energy_weight=1.0,
                            latency_weight=0.0))
    return cfg, router


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


def _make_policy(kind: str, model, params):
    from repro.spec import make_draft_policy
    return make_draft_policy(kind, draft_model=model, draft_params=params)


def _make_backend(cfg, model, params, policy):
    from repro.serving import ExecutionBackend
    kw = {"spec_policy": policy, "spec_n": SPEC_N} if policy else {}
    return ExecutionBackend(model, params, kv_blocks=KV_BLOCKS,
                            kv_block_size=BLOCK_SIZE, **kw)


def _generate(backend, prompts, seed: int):
    """Engine-path greedy generation: prefill + decode to completion."""
    import jax
    h = backend.start_batch(prompts, 1, MAX_NEW, 0.0, jax.random.key(seed),
                            {})
    steps = 0
    while backend.decode_step(h):
        steps += 1
    return backend.finalize(h), steps + 1


def _parity(cfg, model, params, prompts) -> Dict:
    """Greedy spec output must be token-identical to plain decode (the
    accept rule degenerates to argmax agreement + argmax correction, which
    reproduces the sequential greedy chain exactly); logprobs only match to
    reduction-order tolerance."""
    ref, _ = _generate(_make_backend(cfg, model, params, None), prompts,
                       SEED + 1)
    out = {}
    for kind in ("ngram", "draft"):
        got, _ = _generate(
            _make_backend(cfg, model, params,
                          _make_policy(kind, model, params)),
            prompts, SEED + 1)
        tokens_equal = all(
            np.array_equal(a.samples[0], b.samples[0])
            for a, b in zip(ref, got))
        lp_close = all(
            np.allclose(a.logprobs, b.logprobs, atol=LOGPROB_ATOL)
            for a, b in zip(ref, got))
        out[kind] = {"tokens_equal": bool(tokens_equal),
                     "logprobs_allclose": bool(lp_close)}
    return out


def _run_variant(kind: str, cfg, router, model, params, arrivals,
                 verbose: bool = True) -> Dict:
    from repro.qeil2.telemetry import CalibrationFitter, TraceStore
    from repro.serving import ContinuousBatchingScheduler, SchedulerConfig
    from repro.spec import SpecPlanner

    policy = _make_policy(kind, model, params) if kind != "off" else None
    backend = _make_backend(cfg, model, params, policy)
    planner = (SpecPlanner(kind, depths=(0, SPEC_N // 2, SPEC_N),
                           model_name=cfg.name) if policy else None)
    trace = TraceStore()
    sched = ContinuousBatchingScheduler(
        backend, router,
        SchedulerConfig(max_batch_requests=8, max_inflight_batches=2,
                        max_new_tokens=MAX_NEW, temperature=0.0, seed=SEED),
        trace=trace, spec_planner=planner)

    # count decode forwards: in the memory-bound decode regime each forward
    # re-streams the weights once, so forwards are the bench's time unit
    decode_calls = 0
    inner = backend.decode_step

    def counted(h):
        nonlocal decode_calls
        decode_calls += 1
        return inner(h)

    backend.decode_step = counted

    i = 0
    while i < len(arrivals) or sched.queue.pending or sched.inflight:
        horizon = max(sched.clock, sched.pipeline_free_t)
        while i < len(arrivals) and arrivals[i]["t"] <= horizon:
            a = arrivals[i]
            adm = sched.submit(a["prompt"], tier=a["tier"],
                               n_samples=K_SAMPLES, temperature=0.0,
                               arrival_s=a["t"])
            assert adm.admitted, adm.reason
            i += 1
        if not sched.queue.pending and not sched.inflight:
            sched.advance_to(arrivals[i]["t"])
            continue
        sched.step()

    recs = list(sched.records)
    completed = len(sched.completed)
    n_seqs = completed * K_SAMPLES
    total_tokens = n_seqs * MAX_NEW
    # the first token of every sequence is sampled at prefill; the rest are
    # committed by decode forwards
    tps = (total_tokens - n_seqs) / max(decode_calls, 1)
    energy = sum(r.energy_j for r in recs)
    proposed = sum(r.spec_proposed for r in recs)
    accepted = sum(r.spec_accepted for r in recs)
    depths = sorted({r.spec_n for r in recs})

    out = {
        "policy": kind,
        "completed": completed,
        "batches": len(recs),
        "decode_forwards": int(decode_calls),
        "tokens_per_forward": float(tps),
        "energy_j": float(energy),
        "energy_per_request_j": float(energy / max(completed, 1)),
        "ipw": completed / energy,
        "proposed": int(proposed),
        "accepted": int(accepted),
        "accept_rate": (accepted / proposed) if proposed else None,
        "routed_depths": depths,
        "leaks": int(backend.allocator.blocks_in_use),
    }
    if policy is not None:
        # close the loop: fit the measured accept rates, refresh the
        # planner, and re-route one economy batch at the fitted rate
        profile, _ = CalibrationFitter(trace, n_bootstrap=0).fit()
        planner.refresh(profile)
        fitted = planner.accept_rate_for("economy")
        d = planner.route_batch(router, ["economy"] * 4, samples=K_SAMPLES,
                                prompt_tokens=PROMPT_LEN,
                                decode_tokens=MAX_NEW)
        out["fitted_accept_rate"] = float(fitted)
        out["refit_depth"] = int(d.spec.n)
    if verbose:
        rate = (f"{out['accept_rate']:.2f}" if out["accept_rate"] is not None
                else "-")
        refit = (f", refit a={out['fitted_accept_rate']:.2f} -> "
                 f"n={out['refit_depth']}" if policy is not None else "")
        print(f"  {kind:5s}: {completed} done in {len(recs)} batches, "
              f"{decode_calls} decode forwards "
              f"({tps:.2f} tok/fwd), E={energy:.3f} J, "
              f"accept={rate}{refit}")
    return out


def run(verbose: bool = True) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model

    cfg, router = _build_router()
    model = Model(ArchConfig(**ARCH), dtype=jnp.float32)
    params = model.init(jax.random.key(SEED))
    arrivals = _arrivals()
    if verbose:
        print(f"stream: {N_REQUESTS} greedy requests, prompt {PROMPT_LEN} + "
              f"{MAX_NEW} new, draft depth {SPEC_N}, paged KV "
              f"{KV_BLOCKS}x{BLOCK_SIZE}")

    parity = _parity(cfg, model, params,
                     [a["prompt"] for a in arrivals[:4]])
    if verbose:
        for kind, p in parity.items():
            print(f"  parity {kind:5s}: tokens_equal={p['tokens_equal']} "
                  f"logprobs_allclose={p['logprobs_allclose']}")

    by_kind = {}
    for kind in ("off", "ngram", "draft"):
        by_kind[kind] = _run_variant(kind, cfg, router, model, params,
                                     arrivals, verbose=verbose)

    off, ng, dr = by_kind["off"], by_kind["ngram"], by_kind["draft"]
    speedup = dr["tokens_per_forward"] / off["tokens_per_forward"]
    result = {
        "seed": SEED,
        "spec_n": SPEC_N,
        "parity": parity,
        "variants": by_kind,
        "tokens_per_forward_ratio": float(speedup),
        "energy_ratio_draft": dr["energy_per_request_j"] /
        off["energy_per_request_j"],
        "acceptance_all": bool(
            all(v["completed"] == N_REQUESTS for v in by_kind.values()) and
            all(v["leaks"] == 0 for v in by_kind.values()) and
            all(p["tokens_equal"] and p["logprobs_allclose"]
                for p in parity.values()) and
            speedup >= SPEEDUP_FLOOR and
            dr["energy_per_request_j"] <= off["energy_per_request_j"] *
            (1 + 1e-9) and
            dr["ipw"] >= off["ipw"] and
            dr["accept_rate"] is not None and dr["accept_rate"] > 0.99 and
            ng["accept_rate"] is not None and ng["accept_rate"] < 0.3 and
            dr["fitted_accept_rate"] > 0.99 and
            ng["fitted_accept_rate"] < 0.3 and
            dr["refit_depth"] == SPEC_N and
            ng["refit_depth"] == 0),
    }
    if verbose:
        print(f"  draft commits x{speedup:.2f} tokens/forward vs off, "
              f"energy/req x{result['energy_ratio_draft']:.3f}, "
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
            sys.exit("usage: spec_decode.py [--out FILE]")
        out_path = sys.argv[idx]
    res = run()
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {out_path}", file=sys.stderr)
