"""Serving launcher: batched requests through the engine with QEIL
orchestration + safety monitoring in the loop.

  PYTHONPATH=src python -m repro.launch.serve --arch granite-moe-3b-a800m \
      --smoke --requests 8 --samples 4

``--router`` replaces the one-shot greedy plan with the scheduler-centric
runtime: a PGSAM anneal builds the non-dominated archive once, requests
enter tier-aware admission, and the continuous-batching scheduler forms
(optionally mixed-tier, with ``--mixed``) batches routed to shared
operating points off the archive (`repro.serving.scheduler` +
`repro.qeil2.runtime`). Without ``--router`` the v1 blocking engine path
runs unchanged as the baseline.
"""
from __future__ import annotations

import argparse
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core import (Constraints, GreedyOrchestrator, SafetyMonitor,
                        Workload, EDGE_PLATFORM)
from repro.launch.compile_cache import enable_compile_cache
from repro.models import Model
from repro.serving import ServingEngine
from repro.serving.backend import GenerationResult


def main(argv: Optional[Sequence[str]] = None) -> List[GenerationResult]:
    """Serve ``--requests`` seeded random prompts; return the results of
    the requests that completed (a rejected or cancelled one is absent)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ASSIGNED_ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--samples", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--router", action="store_true",
                    help="scheduler-centric serving: tier-aware admission "
                         "+ continuous batching over the PGSAM archive")
    ap.add_argument("--tier", default="standard",
                    choices=["interactive", "standard", "economy"],
                    help="SLA tier to serve requests under (--router)")
    ap.add_argument("--mixed", action="store_true",
                    help="round-robin requests over all three tiers so "
                         "batches mix tiers (--router)")
    ap.add_argument("--max-batch", type=int, default=8,
                    help="scheduler batch size bound (--router)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged KV cache: block budget (prefix sharing "
                         "across repeated samples; supported archs only)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="paged KV cache: token slots per block")
    ap.add_argument("--kv-pool", action="store_true",
                    help="prefix pool (needs --kv-blocks): a radix trie "
                         "over the resident cache reuses cached full "
                         "prefix blocks across the stream, and admission "
                         "prices requests at their marginal (post-dedup) "
                         "tail blocks")
    ap.add_argument("--pool-evict", default="lru", choices=["lru", "off"],
                    help="prefix-pool eviction of idle (zero-ref) trie "
                         "blocks: LRU on demand, or off (resident set only "
                         "grows; admission fails loudly when full)")
    ap.add_argument("--quant", default="bf16",
                    choices=["bf16", "int8", "int4"],
                    help="weight-only serving format (repro.quant): linear "
                         "layers run the fused dequant-matmul kernel")
    ap.add_argument("--group-size", type=int, default=32,
                    help="int4 quantization group size along d_in")
    ap.add_argument("--kv-int8", action="store_true",
                    help="store the paged KV cache int8 (needs --kv-blocks; "
                         "halves cache bytes per token slot)")
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "draft"],
                    help="speculative decode draft policy (repro.spec): "
                         "prompt-lookup n-grams or a small draft model; "
                         "with --router the SpecPlanner prices draft depth "
                         "per batch")
    ap.add_argument("--spec-n", type=int, default=4,
                    help="max draft tokens verified per decode step")
    ap.add_argument("--draft-model", default=None, choices=ASSIGNED_ARCHS,
                    help="arch whose reduced config serves as the draft "
                         "model (--spec draft; defaults to --arch reduced; "
                         "must share the target vocab)")
    ap.add_argument("--preempt", action="store_true",
                    help="decode-boundary preemption (--router): an "
                         "interactive arrival may cut a lower-tier "
                         "in-flight batch; with --kv-pool the victim's "
                         "blocks park in the trie and resume prefills "
                         "only the tail")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill (needs --kv-blocks): split "
                         "prefills into <= N-token slices interleaved "
                         "with decode steps (bit-identical output)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-tier deadline factor (--router): cancel a "
                         "queued request once it waits longer than "
                         "FACTOR x its tier's p99 latency cap")
    ap.add_argument("--chaos", default=None, metavar="PLAN.json",
                    help="fault-injection plan (repro.serving.chaos JSON) "
                         "replayed on the simulated clock through the "
                         "SafetyMonitor into the live scheduler")
    ap.add_argument("--metrics-out", default=None,
                    help="write a metrics snapshot (JSON + .prom sibling) "
                         "here; with --router, refreshed periodically while "
                         "the scheduler drains")
    ap.add_argument("--metrics-interval", type=float, default=5.0,
                    help="simulated seconds between periodic metrics writes")
    ap.add_argument("--spans-out", default=None,
                    help="write request lifecycle spans (JSONL) here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro.obs import NULL_OBS, PeriodicReporter, make_observability
    obs = (make_observability() if args.metrics_out or args.spans_out
           else NULL_OBS)

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = Model(cfg, dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    key = jax.random.key(0)
    if args.quant == "bf16":
        params = jax.jit(model.init)(key)
    else:
        # init and quantize in one program: only the quantized tree is an
        # output, so the full-precision weights never sit beside it
        from repro.quant import param_bytes, quantize_model
        before = param_bytes(jax.eval_shape(model.init, key))
        params = jax.jit(lambda k: quantize_model(
            model.init(k), args.quant, args.group_size))(key)
        print(f"[quant] weights {args.quant}: {before / 1e6:.1f} MB -> "
              f"{param_bytes(params) / 1e6:.1f} MB")

    spec_policy = None
    if args.spec != "off":
        from repro.spec import (DEFAULT_ACCEPT_RATE, expected_tokens_per_step,
                                make_draft_policy, spec_supported)
        if not spec_supported(cfg):
            raise SystemExit(f"--spec: arch {cfg.name!r} unsupported "
                             "(needs uniform full attention, one codebook)")
        draft_model = draft_params = None
        if args.spec == "draft":
            dcfg = get_config(args.draft_model or args.arch).reduced()
            if dcfg.vocab_size != cfg.vocab_size:
                raise SystemExit(
                    f"--draft-model {dcfg.name!r} vocab {dcfg.vocab_size} != "
                    f"target vocab {cfg.vocab_size}")
            draft_model = Model(dcfg, dtype=model.dtype)
            draft_params = draft_model.init(jax.random.key(1))
        spec_policy = make_draft_policy(args.spec, draft_model=draft_model,
                                        draft_params=draft_params)
        print(f"[spec] policy {spec_policy.name} depth {args.spec_n}: "
              f"~{expected_tokens_per_step(args.spec_n, DEFAULT_ACCEPT_RATE):.2f} "
              f"tok/step at accept rate {DEFAULT_ACCEPT_RATE}")

    # --- QEIL plan for this workload (simulated edge platform profile)
    from repro.quant import quant_workload
    w = Workload(batch=args.requests, prompt_tokens=args.prompt_len,
                 decode_tokens=args.max_new, samples=args.samples)
    w = quant_workload(w, args.quant,
                       kv_format="int8" if args.kv_int8 else "bf16")
    router = None
    if args.router:
        from repro.qeil2 import (PGSAMConfig, PGSAMOrchestrator, ParetoRouter,
                                 default_tiers)
        orch = PGSAMOrchestrator(
            EDGE_PLATFORM, Constraints(latency_budget_factor=None),
            config=PGSAMConfig(seed=0, incremental=True))
        frontier = orch.pareto_frontier(cfg, w)
        placed = [a for a in frontier if a.mapping]
        if not placed:
            # nothing fits the platform: degrade to the same infeasible-plan
            # report the non-router path gives instead of crashing
            print(f"[router] no placeable operating point: "
                  f"{'; '.join(frontier[0].violations)}")
            router, plan = None, frontier[0]
        else:
            base = min(a.latency_s for a in placed) / 0.9
            router = ParetoRouter(orch, cfg, w, tiers=default_tiers(base))
            print(f"[router] archive {len(placed)} operating points")
            for name, d in sorted(router.route_all().items()):
                print(f"[router] tier {name:12s} -> point {d.point_index:2d} "
                      f"E={d.energy_j:.2f} J T={d.latency_s * 1e3:.1f} ms "
                      f"P={d.avg_power_w:.1f} W caps_met={d.meets_caps}")
            plan = router.route(args.tier).assignment
    else:
        orch = GreedyOrchestrator(EDGE_PLATFORM,
                                  Constraints(latency_budget_factor=1.0))
        plan = orch.assign(cfg, w)
    print(f"[orchestrator] devices={plan.device_names()} "
          f"energy={plan.energy_j:.2f} J latency={plan.latency_s * 1e3:.1f} ms "
          f"feasible={plan.feasible}")

    safety = SafetyMonitor(EDGE_PLATFORM, max_seq_len=args.prompt_len * 4,
                           vocab_size=cfg.vocab_size)

    rng = np.random.default_rng(0)
    prompts = []
    for _ in range(args.requests):
        p = rng.integers(0, cfg.vocab_size,
                         size=(args.prompt_len,)).astype(np.int32)
        if cfg.n_codebooks > 1:
            p = np.stack([p] * cfg.n_codebooks, -1)
        check = safety.validator.validate(p if p.ndim == 1 else p[:, 0],
                                          now_s=time.time() % 1e6)
        if not check.ok:
            print("[safety] rejected request:", check.reason)
            continue
        prompts.append(p)

    extras = {}
    if cfg.frontend == "vision":
        extras["vision_embeds"] = jnp.zeros(
            (len(prompts), 4, cfg.d_model), model.dtype)
    if cfg.cross_attention:
        extras["cond_memory"] = jnp.zeros(
            (len(prompts), cfg.n_cond_tokens, cfg.d_model), model.dtype)

    backend = None
    if args.kv_int8 and args.kv_blocks is None:
        raise SystemExit("--kv-int8 requires --kv-blocks (paged cache)")
    if args.kv_pool and args.kv_blocks is None:
        raise SystemExit("--kv-pool requires --kv-blocks (paged cache)")
    if args.prefill_chunk is not None and args.kv_blocks is None:
        raise SystemExit("--prefill-chunk requires --kv-blocks (paged "
                         "cache)")
    if (args.chaos or args.preempt or args.deadline) and not args.router:
        raise SystemExit("--chaos/--preempt/--deadline need --router "
                         "(the continuous-batching scheduler)")
    spec_kwargs = ({"spec_policy": spec_policy, "spec_n": args.spec_n}
                   if spec_policy is not None else {})
    if args.kv_blocks is not None:
        from repro.models.cache import paged_supported
        from repro.serving import ExecutionBackend
        if paged_supported(cfg):
            kv_format = "int8" if args.kv_int8 else "bf16"
            backend = ExecutionBackend(model, params, kv_blocks=args.kv_blocks,
                                       kv_block_size=args.kv_block_size,
                                       kv_format=kv_format, obs=obs,
                                       kv_pool=args.kv_pool,
                                       pool_evict=args.pool_evict,
                                       prefill_chunk=args.prefill_chunk,
                                       **spec_kwargs)
            print(f"[kv] paged cache: {args.kv_blocks} blocks x "
                  f"{args.kv_block_size} slots ({kv_format}, "
                  f"{backend.kv_token_bytes} B/token)")
            if args.kv_pool:
                print(f"[kv] resident prefix pool: cross-batch block "
                      f"reuse, evict={args.pool_evict}")
            if args.prefill_chunk:
                print(f"[kv] chunked prefill: <= {args.prefill_chunk} "
                      "tokens per slice, interleaved with decode")
        else:
            if args.prefill_chunk:
                raise SystemExit("--prefill-chunk requires a "
                                 "paging-supported arch")
            print(f"[kv] arch {cfg.name!r} unsupported for paging; "
                  "dense cache")
    if backend is None and spec_policy is not None:
        # drafting rides the dense cache too: an explicit backend carries
        # the policy where the engine would otherwise build a plain one
        from repro.serving import ExecutionBackend
        backend = ExecutionBackend(model, params, obs=obs, **spec_kwargs)
    engine = ServingEngine(model, params, max_new_tokens=args.max_new,
                           backend=backend, obs=obs)
    t0 = time.perf_counter()
    if router is not None:
        from repro.serving import ContinuousBatchingScheduler, SchedulerConfig
        spec_planner = None
        if spec_policy is not None:
            from repro.spec import SpecPlanner
            depths = tuple(sorted({0, args.spec_n // 2, args.spec_n}))
            spec_planner = SpecPlanner(args.spec, depths=depths,
                                       model_name=cfg.name)
        sched = ContinuousBatchingScheduler(
            engine.backend, router,
            SchedulerConfig(max_batch_requests=args.max_batch,
                            max_new_tokens=args.max_new,
                            preempt=args.preempt,
                            deadline_factor=args.deadline), obs=obs,
            spec_planner=spec_planner)
        chaos = None
        if args.chaos:
            from repro.serving.chaos import FaultPlan, attach
            plan_doc = FaultPlan.load(args.chaos)
            chaos = attach(plan_doc, safety, sched)
            print(f"[chaos] plan seed={plan_doc.seed}: "
                  f"{len(plan_doc.actions)} actions")
        tiers = (["interactive", "standard", "economy"] if args.mixed
                 else [args.tier])
        ids = []
        for i, p in enumerate(prompts):
            row = {k: np.asarray(v)[i] for k, v in extras.items()} or None
            adm = sched.submit(p, tier=tiers[i % len(tiers)],
                               n_samples=args.samples, extras=row)
            if adm.admitted:
                ids.append(adm.request_id)
            else:
                print(f"[admission] rejected request {i}: {adm.reason}")
        if chaos is not None or (args.metrics_out and obs.metrics.enabled):
            # drain explicitly so the chaos plan fires on the simulated
            # clock / the reporter snapshots between steps
            reporter = (PeriodicReporter(obs.metrics, args.metrics_out,
                                         interval_s=args.metrics_interval)
                        if args.metrics_out and obs.metrics.enabled
                        else None)
            while sched.queue.pending or sched.inflight:
                if chaos is not None:
                    for act in chaos.apply_due(sched.clock):
                        print(f"[chaos] t={act.t_s:.2f}s {act.kind} "
                              f"{act.device or ''}".rstrip())
                if not sched.step():
                    break
                if reporter is not None:
                    reporter.maybe_write(sched.clock)
            done = sched.completed
        else:
            done = sched.run_until_idle()
        st = sched.stats()
        if st["preemptions_total"] or st["cancelled"]:
            print(f"[robustness] preemptions={st['preemptions']} "
                  f"deadline_misses={st['deadline_misses']} "
                  f"retries={st['retries_total']} shed={st['shed_total']} "
                  f"resume_tail/full={st['resume_tail_tokens']}/"
                  f"{st['resume_full_tokens']} tokens")
        for rec in sched.records:
            spec = ""
            if rec.spec_n:
                rate = (f" a={rec.spec_accept_rate:.2f}"
                        if rec.spec_accept_rate is not None else "")
                spec = f" spec={rec.spec_policy}:{rec.spec_n}{rate}"
            pool = ""
            if args.kv_pool:
                pool = (f" pool_hits={rec.pool_hit_blocks}"
                        f" evict={rec.pool_evictions}")
            print(f"[scheduler] batch {rec.batch_id}: "
                  f"{rec.n_requests} req ({rec.tier_mix}) -> point "
                  f"{rec.point_index} E={rec.energy_j * 1e3:.2f} mJ "
                  f"T={rec.latency_s * 1e3:.2f} ms "
                  f"queue={rec.queue_delay_s * 1e3:.2f} ms "
                  f"caps_met={rec.meets_caps}{spec}{pool}")
        if args.kv_pool and backend is not None and \
                backend.prefix_pool is not None:
            st = sched.stats()
            resident = backend.prefix_pool.blocks_resident
            cached = resident * args.kv_block_size * backend.kv_token_bytes
            print(f"[kv] prefix pool: {st['pool_hit_blocks']} hit blocks, "
                  f"{st['pool_evictions']} evictions, "
                  f"{st['prefill_bytes_saved'] / 1e3:.1f} kB prefill "
                  f"saved; {resident} blocks resident "
                  f"({cached / 1e3:.1f} kB cached)")
        # lifecycle policies may cancel (deadline/shed); report completions
        results = [done[i].result for i in ids if i in done]
    else:
        results = engine.generate(prompts, n_samples=args.samples,
                                  extras=extras)
    dt = time.perf_counter() - t0
    n_tok = sum(r.decode_tokens for r in results)
    print(f"[serve] {len(results)} requests x {args.samples} samples, "
          f"{n_tok} tokens in {dt:.2f}s ({n_tok / dt:.0f} tok/s)")
    for i, r in enumerate(results[:3]):
        print(f"  req {i}: best logprob {max(r.logprobs):.3f}")

    if args.metrics_out and obs.metrics.enabled:
        obs.metrics.write(args.metrics_out)
        print(f"[obs] metrics snapshot -> {args.metrics_out} (+ .prom)")
    if args.spans_out and obs.tracer.enabled:
        obs.tracer.save(args.spans_out)
        print(f"[obs] {len(obs.tracer)} spans -> {args.spans_out}")
    return results


if __name__ == "__main__":
    main()
