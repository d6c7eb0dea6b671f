"""Quantized serving vs bf16 at an equal KV byte budget (PR 6 tentpole bench).

The same seeded mixed-tier request stream (the kv_paging shape: every request
carries k = 4 repeated samples) is served three ways, all through the paged
backend and the v2-costed router:

* ``bf16``  — full-precision weights, bf16 paged KV (the PR 5 baseline).
* ``int8``  — per-channel int8 weights (fused dequant-matmul via
  `repro.models.layers.dense` dispatch) + int8 paged KV: half the cache
  bytes per token slot, so the same byte budget buys ~2x the block budget.
* ``int4``  — group-wise int4 weights + int8 KV: the paper's headline
  efficiency point (4-bit weights are where its best IPW lands).

Every variant's router is a fixed-device v2 coster: each formed batch is
decomposed (`repro.core.decomposition` with the variant's re-priced
`Workload` — packed weight bytes, 1-byte KV elements) and costed with
``plan_costs(model="v2", quant=fmt)``, so batch energy reflects both the
byte reduction (DASI/roofline time) and the paper's f(Q) power factor.
Quality is a deterministic fixed-batch NLL delta against the bf16 model on
identical token batches — no sampling in the quality probe.

Reported per variant: completed requests, KV block budget + high-water at
the equal byte budget, total v2 batch energy, IPW (completed inferences per
joule), and NLL delta. Acceptance (seeded, CI-gated): every variant
completes the stream; the int8-KV block budget is >= 1.8x bf16's at equal
bytes (pos metadata keeps it shy of exactly 2x); int4 beats bf16 IPW while
holding the NLL quality floor; v2 energy is strictly monotone decreasing
bf16 > int8 > int4; and the serve trace records carry the quant formats.

Run: PYTHONPATH=src python benchmarks/quant_serving.py [--out FILE]
"""
from __future__ import annotations

import json
import sys
from types import SimpleNamespace
from typing import Dict, List

import numpy as np

SEED = 0
N_REQUESTS = 12
PROMPT_LEN = 12
MAX_NEW = 8
K_SAMPLES = 4
BLOCK_SIZE = 4
GROUP_SIZE = 16
TIER_MIX = (("interactive", 0.3), ("standard", 0.4), ("economy", 0.3))
# equal KV byte budget across variants, denominated in bf16 blocks
BUDGET_BLOCKS_BF16 = 24
# quality floor: quantized fixed-batch NLL may not drift more than this
# from bf16 (random-init tiny model; NLL approx log(vocab) = 4.2 nats)
QUALITY_FLOOR_NLL = {"int8": 0.05, "int4": 0.35}

ARCH = dict(name="quant-bench", arch_type="dense", n_layers=2, d_model=64,
            n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)

VARIANTS = (("bf16", "bf16"), ("int8", "int8"), ("int4", "int8"))


class _V2Router:
    """Fixed-device router double that costs every formed batch with the v2
    energy model at the variant's quantized byte prices. Routing policy is
    out of scope here (serving_schedule.py gates that); what this bench
    needs is decision.energy_j/latency_s moving with the quant format, and
    real ``batch_costs`` so `plan_signals` feeds the trace."""

    def __init__(self, cfg, fmt: str, kv_format: str):
        from repro.core.devices import TPU_V5E
        self.cfg = cfg
        self.fmt = fmt
        self.kv_format = kv_format
        self.device = TPU_V5E
        self.tiers = {t: SimpleNamespace(name=t) for t, _ in TIER_MIX}

    def resolve_tier(self, tier):
        return self.tiers[tier] if isinstance(tier, str) else tier

    def required_samples(self, tier):
        return None

    def route_batch(self, tiers, *, samples=1, prompt_tokens=PROMPT_LEN,
                    decode_tokens=MAX_NEW, **kw):
        from repro.core.decomposition import Workload, decompose
        from repro.core.energy import plan_costs
        from repro.quant import quant_workload

        wl = quant_workload(
            Workload(batch=len(tiers), prompt_tokens=prompt_tokens,
                     decode_tokens=decode_tokens, samples=samples),
            self.fmt, kv_format=self.kv_format)
        stages = decompose(self.cfg, wl)
        assignment = {st.name: self.device for st in stages}
        costs = plan_costs(stages, assignment, quant=self.fmt, workload=wl,
                           model="v2")
        return SimpleNamespace(
            tier=self.resolve_tier(tiers[0]), tier_counts={},
            assignment=assignment, point_index=0, meets_caps=True,
            batch_costs=costs, energy_j=costs.energy_j,
            latency_s=costs.makespan_s, notes=[])


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


def _kv_token_bytes(cfg, kv_format: str) -> int:
    from repro.models.cache import kv_bytes_per_token
    return kv_bytes_per_token(cfg, 1 if kv_format == "int8" else 2)


def _nll(model, params, batch) -> float:
    return float(model.loss(params, batch))


def _quality_batch(cfg, n_codebooks_vocab: int):
    rng = np.random.default_rng(SEED + 7)
    toks = rng.integers(0, n_codebooks_vocab, size=(4, 24)).astype(np.int32)
    import jax.numpy as jnp
    tokens = jnp.asarray(toks)
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32)[None], (4, 24))
    return {"tokens": tokens[:, :-1], "labels": tokens[:, 1:],
            "positions": pos[:, :-1]}


def _run_variant(fmt: str, kv_format: str, arrivals, nll_ref: float,
                 verbose: bool = True) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.qeil2.telemetry import TraceStore
    from repro.quant import param_bytes, quantize_model
    from repro.serving import (ContinuousBatchingScheduler, ExecutionBackend,
                               SchedulerConfig)

    cfg = ArchConfig(**ARCH)
    model = Model(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.key(SEED))
    qparams = quantize_model(params, fmt, GROUP_SIZE) \
        if fmt != "bf16" else params

    # equal byte budget: bf16's block budget in bytes, re-denominated in
    # this variant's (possibly int8) KV blocks
    budget_bytes = BUDGET_BLOCKS_BF16 * BLOCK_SIZE * _kv_token_bytes(cfg,
                                                                     "bf16")
    kv_blocks = budget_bytes // (BLOCK_SIZE * _kv_token_bytes(cfg, kv_format))
    backend = ExecutionBackend(model, qparams, kv_blocks=int(kv_blocks),
                               kv_block_size=BLOCK_SIZE, kv_format=kv_format)
    trace = TraceStore()
    sched = ContinuousBatchingScheduler(
        backend, _V2Router(cfg, fmt, kv_format),
        SchedulerConfig(max_batch_requests=8, max_inflight_batches=2,
                        max_new_tokens=MAX_NEW, seed=SEED),
        trace=trace)

    block_high_water = 0
    i = 0
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
        block_high_water = max(block_high_water,
                               backend.allocator.blocks_in_use)

    recs = list(sched.records)
    energy = sum(r.energy_j for r in recs)
    completed = len(sched.completed)
    nll = _nll(model, qparams, _quality_batch(cfg, ARCH["vocab_size"]))
    serve_recs = trace.records("serve")
    out = {
        "fmt": fmt,
        "kv_format": kv_format,
        "completed": completed,
        "batches": len(recs),
        "weight_bytes": int(param_bytes(qparams)),
        "kv_blocks": int(kv_blocks),
        "kv_block_high_water": int(block_high_water),
        "kv_token_bytes": int(backend.kv_token_bytes),
        "energy_j": float(energy),
        "ipw": completed / energy,
        "nll": nll,
        "nll_delta": abs(nll - nll_ref),
        "makespan_s": sched.pipeline_free_t,
        "trace_quants": sorted(list(pair) for pair in
                               {(r["quant"], r["kv_format"])
                                for r in serve_recs}),
        "trace_has_bytes": all("weight_bytes" in r and "kv_bytes_in_use" in r
                               for r in serve_recs),
    }
    if verbose:
        print(f"  {fmt:5s}/{kv_format}-kv: {completed} done in "
              f"{out['batches']} batches, weights "
              f"{out['weight_bytes'] / 1e3:.0f} kB, blocks "
              f"{out['kv_blocks']} (hw {out['kv_block_high_water']}), "
              f"E={energy:.3f} J, IPW={out['ipw']:.2f}, "
              f"dNLL={out['nll_delta']:.4f}")
    return out


def run(verbose: bool = True) -> Dict:
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model

    arrivals = _arrivals()
    cfg = ArchConfig(**ARCH)
    model = Model(cfg, dtype=jnp.bfloat16)
    params = model.init(jax.random.key(SEED))
    nll_ref = _nll(model, params, _quality_batch(cfg, ARCH["vocab_size"]))
    if verbose:
        print(f"stream: {N_REQUESTS} requests x {K_SAMPLES} samples, "
              f"prompt {PROMPT_LEN} + {MAX_NEW} new, budget "
              f"{BUDGET_BLOCKS_BF16} bf16 blocks of {BLOCK_SIZE} "
              f"(ref NLL {nll_ref:.4f})")

    by_fmt = {}
    for fmt, kvf in VARIANTS:
        by_fmt[fmt] = _run_variant(fmt, kvf, arrivals, nll_ref,
                                   verbose=verbose)

    bf16, i8, i4 = by_fmt["bf16"], by_fmt["int8"], by_fmt["int4"]
    blocks_ratio = i8["kv_blocks"] / bf16["kv_blocks"]
    result = {
        "seed": SEED,
        "k_samples": K_SAMPLES,
        "group_size": GROUP_SIZE,
        "variants": by_fmt,
        "kv_blocks_ratio": blocks_ratio,
        "ipw_ratio_int4": i4["ipw"] / bf16["ipw"],
        "acceptance_all": bool(
            all(v["completed"] == N_REQUESTS for v in by_fmt.values()) and
            blocks_ratio >= 1.8 and
            i4["ipw"] > bf16["ipw"] and
            i8["nll_delta"] <= QUALITY_FLOOR_NLL["int8"] and
            i4["nll_delta"] <= QUALITY_FLOOR_NLL["int4"] and
            bf16["energy_j"] > i8["energy_j"] > i4["energy_j"] and
            i8["weight_bytes"] < bf16["weight_bytes"] and
            i4["weight_bytes"] < i8["weight_bytes"] and
            i8["trace_quants"] == [["int8", "int8"]] and
            i4["trace_quants"] == [["int4", "int8"]] and
            all(v["trace_has_bytes"] for v in by_fmt.values())),
    }
    if verbose:
        print(f"  int8-KV block budget x{blocks_ratio:.2f}, "
              f"int4 IPW x{result['ipw_ratio_int4']:.2f} vs bf16, "
              f"energy {bf16['energy_j']:.3f} > {i8['energy_j']:.3f} > "
              f"{i4['energy_j']:.3f} J, "
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
            sys.exit("usage: quant_serving.py [--out FILE]")
        out_path = sys.argv[idx]
    res = run()
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(res, fh, indent=2)
        print(f"wrote {out_path}", file=sys.stderr)
