"""Per-kernel profiling: compiled cost analysis + timed reps for the four
Pallas kernels, emitted as TraceStore-ingestible ``kind="kernel"`` records.

Where ``benchmarks/kernel_bench.py`` stamps records with hand-derived
analytic FLOP/byte counts, this entry point asks the compiler: each kernel
wrapper is lowered and compiled, and ``compiled.cost_analysis()`` supplies
the flops / bytes-accessed terms (falling back to the analytic counts when
the backend doesn't report them — the ``cost_source`` field says which side
produced the numbers). Timed reps run under ``repro.obs.annotate`` so they
are attributable in a host profile, and the dequant records carry their
``quant`` stamp so the calibration fitter keys them as
``dequant_matmul:int8`` / ``dequant_matmul:int4`` (telemetry.fit._eta_key).

Usage:
  PYTHONPATH=src python -m repro.launch.profile --out traces/kernels.jsonl
  PYTHONPATH=src python -m repro.launch.profile --kernels flash_attention
"""
from __future__ import annotations

import argparse
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.devices import TPU_V5E
from repro.obs.profiling import annotate, tpu_roofline_us


def _time_reps(fn, *args, n: int = 3, label: str = "kernel") -> List[float]:
    """Per-rep us/call, warm call excluded; each rep annotated for the host
    profiler so kernel time is attributable in a captured trace."""
    jax.block_until_ready(fn(*args))  # warm (compiles)
    out = []
    for _ in range(n):
        with annotate(f"profile/{label}"):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            out.append((time.perf_counter() - t0) * 1e6)
    return out


def _compiled_costs(fn, *args) -> Optional[Dict[str, float]]:
    """flops / bytes accessed from the compiled executable, or None when the
    backend reports neither (CPU builds often omit byte counters)."""
    try:
        ca = jax.jit(fn).lower(*args).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        costs = {k: float(v) for k, v in ca.items()
                 if isinstance(v, (int, float))}
    except Exception:
        return None
    out = {}
    if costs.get("flops", 0.0) > 0.0:
        out["flops"] = costs["flops"]
    by = costs.get("bytes accessed", 0.0)
    if by > 0.0:
        out["bytes"] = by
    return out or None


def _records(kernel: str, reps: List[float], flops: float, bytes_moved: float,
             cost_source: str, quant: str = "fp32") -> List[dict]:
    # roofline_us is modelled from the TPU_V5E table whatever ran the reps;
    # device/platform say what actually ran them
    roofline = tpu_roofline_us(flops, bytes_moved)
    dev = jax.devices()[0]
    backend = jax.default_backend()
    return [{"kind": "kernel", "kernel": kernel, "rep": i,
             "flops": flops, "bytes": bytes_moved,
             "measured_us": us, "roofline_us": roofline,
             "roofline_source": f"modelled:{TPU_V5E.name}",
             "device": dev.device_kind, "platform": dev.platform,
             "backend": backend if backend == "tpu" else f"{backend}-interpret",
             "cost_source": cost_source, "quant": quant}
            for i, us in enumerate(reps)]


def _profile_one(name: str, fn: Callable, args: tuple, analytic_flops: float,
                 analytic_bytes: float, reps: int,
                 quant: str = "fp32") -> Tuple[List[dict], Dict]:
    """Time one kernel and stamp records with compiled costs when available."""
    costs = _compiled_costs(fn, *args)
    flops = analytic_flops
    bytes_moved = analytic_bytes
    source = "analytic"
    if costs is not None:
        # compiled counts only replace terms the backend actually reports;
        # a flops-only report keeps the analytic byte side (and vice versa)
        flops = costs.get("flops", flops)
        bytes_moved = costs.get("bytes", bytes_moved)
        source = ("compiled" if len(costs) == 2
                  else f"compiled-{next(iter(costs))}+analytic")
    timed = _time_reps(fn, *args, n=reps, label=name)
    recs = _records(name, timed, flops, bytes_moved, source, quant=quant)
    summary = {"kernel": name, "quant": quant, "cost_source": source,
               "flops": flops, "bytes": bytes_moved,
               "mean_us": float(np.mean(timed)),
               "roofline_us": recs[0]["roofline_us"]}
    return recs, summary


def run(verbose: bool = True, reps: int = 3,
        kernels: Optional[List[str]] = None) -> Dict:
    """Profile the Pallas kernel call sites at small fixed shapes (the same
    shapes benchmarks/kernel_bench.py times) and return TraceStore-ingestible
    records plus per-kernel summaries."""
    from repro.kernels.decode_attention.ops import decode_attention_cache
    from repro.kernels.dequant_matmul.ops import dequant_matmul
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.ssd_scan.ops import ssd_chunk
    from repro.quant import quantize_int4, quantize_int8

    ks = jax.random.split(jax.random.key(0), 3)
    jobs: List[Tuple[str, Callable, tuple, float, float, str]] = []

    # flash attention (causal prefill tile)
    B, S, H, D = 1, 256, 4, 64
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    jobs.append(("flash_attention", flash_attention, (q, k, v),
                 4.0 * B * S * S / 2 * H * D, 4 * B * S * H * D * 4, "fp32"))

    # decode attention (cache streaming)
    W = 1024
    kc = jax.random.normal(ks[1], (2, W, 2, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (2, W, 2, 64), jnp.float32)
    qd = jax.random.normal(ks[0], (2, 1, 4, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(W)[None], (2, W)).astype(jnp.int32)
    qpos = jnp.full((2,), W - 1, jnp.int32)
    jobs.append(("decode_attention", decode_attention_cache,
                 (qd, kc, vc, pos, qpos),
                 4.0 * 2 * W * 4 * 64, 2 * W * 2 * 64 * 2 * 4, "fp32"))

    # ssd chunked scan
    nc, Q, P, N = 4, 64, 32, 64
    x = jax.random.normal(ks[0], (2, nc, Q, 2, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, nc, Q, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)))
    dA = dt * A[None, None, None]
    dAcs = jnp.cumsum(dA, axis=2)
    Bm = jax.random.normal(ks[1], (2, nc, Q, 2, N), jnp.float32)
    Cm = jax.random.normal(ks[2], (2, nc, Q, 2, N), jnp.float32)
    jobs.append(("ssd_scan", ssd_chunk, (x, dt, dA, dAcs, Bm, Cm),
                 2 * nc * (2 * Q * Q * 2 * (P + N)),
                 2 * nc * Q * 2 * (P + 2 * N) * 4, "fp32"))

    # fused dequant-matmul, both serving formats (quant-stamped records)
    M, Kd, Nd = 8, 256, 256
    xq = jax.random.normal(ks[0], (M, Kd), jnp.float32)
    wq = jax.random.normal(ks[1], (Kd, Nd), jnp.float32)
    fl_q = 2.0 * M * Kd * Nd
    for fmt, (qw, sc), wbytes in (
            ("int8", quantize_int8(wq), Kd * Nd),
            ("int4", quantize_int4(wq, 32), Kd * Nd // 2)):
        by_q = wbytes + sc.size * 4 + (M * Kd + M * Nd) * 4
        jobs.append((f"dequant_matmul", dequant_matmul, (xq, qw, sc),
                     fl_q, by_q, fmt))

    results: Dict = {"records": [], "kernels": []}
    for name, fn, args, fl, by, quant in jobs:
        if kernels and name not in kernels:
            continue
        recs, summary = _profile_one(name, fn, args, fl, by, reps,
                                     quant=quant)
        results["records"] += recs
        results["kernels"].append(summary)
        if verbose:
            print(f"[profile] {name}"
                  f"{'[' + quant + ']' if quant != 'fp32' else '':8s} "
                  f"{summary['mean_us']:9.0f} us/call  "
                  f"roofline {summary['roofline_us']:8.2f} us  "
                  f"costs: {summary['cost_source']}")
    results["n_records"] = len(results["records"])
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="append kernel records to this JSONL trace")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--kernels", nargs="*", default=None,
                    help="subset of kernel names to profile")
    ap.add_argument("--profile-dir", default=None,
                    help="also capture a jax.profiler trace into this dir")
    args = ap.parse_args()

    if args.profile_dir:
        with jax.profiler.trace(args.profile_dir):
            results = run(verbose=True, reps=args.reps, kernels=args.kernels)
        print(f"[profile] jax.profiler trace -> {args.profile_dir}")
    else:
        results = run(verbose=True, reps=args.reps, kernels=args.kernels)

    if args.out:
        from repro.qeil2.telemetry import TraceStore
        store = TraceStore(path=args.out)
        n = store.ingest_many(results["records"])
        print(f"appended {n} kernel records -> {args.out}")


if __name__ == "__main__":
    main()
