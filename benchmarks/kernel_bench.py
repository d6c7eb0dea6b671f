"""Kernel microbenchmarks: interpret-mode us/call on CPU (correctness-path
cost) + modeled TPU v5e roofline time for the production shapes each kernel
serves.

Besides the human-readable table, ``run()`` emits machine-readable per-kernel
trace records (one per timing rep: flops, bytes, measured us, roofline us)
suitable for `repro.qeil2.telemetry.TraceStore.ingest` — the measurement side
of the calibration loop. ``python benchmarks/kernel_bench.py --out FILE``
appends them to a JSONL trace directly.

Note the honesty caveat carried in each record's ``backend`` field: the
measured numbers here are CPU interpret-mode timings while the roofline is
the modeled TPU time, so the implied duty factor eta = roofline/measured is
meaningful only when both sides describe the same silicon (the fitter clamps
eta to (0, 1]; real calibration feeds records measured on the target device).
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.devices import TPU_V5E
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
from repro.kernels.ssd_scan.ops import ssd_chunk
try:
    from benchmarks.common import fmt_table
except ModuleNotFoundError:      # run as a script: benchmarks/ is sys.path[0]
    from common import fmt_table


def _time_reps(fn, *args, n=3, **kw) -> List[float]:
    """Per-rep us/call (warm call excluded) — reps feed the bootstrap CI of
    the fitted per-kernel duty factor."""
    fn(*args, **kw)  # warm
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kw))
        out.append((time.perf_counter() - t0) * 1e6)
    return out


def _tpu_roofline_us(flops: float, bytes_moved: float) -> float:
    t = max(flops / (TPU_V5E.peak_flops * TPU_V5E.util),
            bytes_moved / (TPU_V5E.mem_bw * TPU_V5E.util))
    return t * 1e6


def _records(kernel: str, reps: List[float], flops: float,
             bytes_moved: float, quant: str = "fp32") -> List[dict]:
    # quant stamps the numeric format the kernel ran at; the calibration
    # fitter keys quantized formats as "<kernel>:<quant>" (full-precision
    # records keep the bare kernel name — see telemetry.fit._eta_key)
    roofline = _tpu_roofline_us(flops, bytes_moved)
    return [{"kind": "kernel", "kernel": kernel, "rep": i,
             "flops": flops, "bytes": bytes_moved,
             "measured_us": us, "roofline_us": roofline,
             "device": TPU_V5E.name, "backend": "cpu-interpret",
             "quant": quant}
            for i, us in enumerate(reps)]


def run(verbose: bool = True) -> Dict:
    rows = []
    results: Dict = {"records": []}

    # flash attention: one prefill tile set (small CPU shape; model the 32k)
    B, S, H, D = 1, 256, 4, 64
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    reps = _time_reps(flash_attention_pallas, q, k, v,
                      block_q=128, block_k=128)
    us = float(np.mean(reps))
    # measured shape's analytic costs (causal: half the score matrix)
    fl_m = 4.0 * B * S * S / 2 * H * D
    by_m = 4 * B * S * H * D * 4                    # q,k,v,out fp32
    results["records"] += _records("flash_attention", reps, fl_m, by_m)
    # production shape: qwen2-72b prefill_32k per chip slice
    Sp, Hp = 32768, 4  # heads per chip after sharding
    fl = 4.0 * Sp * Sp / 2 * Hp * 128
    by = (3 * Sp * Hp * 128) * 2
    rows.append(["flash_attention", f"{us:.0f}",
                 f"{_tpu_roofline_us(fl, by):.0f} (32k tile/chip)"])
    results["flash_attention_us"] = us

    # decode attention: cache streaming
    W = 1024
    kc = jax.random.normal(ks[1], (2, W, 2, 64), jnp.float32)
    vc = jax.random.normal(ks[2], (2, W, 2, 64), jnp.float32)
    qd = jax.random.normal(ks[0], (2, 1, 4, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(W)[None], (2, W)).astype(jnp.int32)
    qpos = jnp.full((2,), W - 1, jnp.int32)
    reps = _time_reps(decode_attention_pallas, qd, kc, vc, pos, qpos,
                      block_k=256)
    us = float(np.mean(reps))
    fl_m = 4.0 * 2 * W * 4 * 64                     # QK^T + PV over the cache
    by_m = 2 * W * 2 * 64 * 2 * 4                   # k+v cache fp32 streams
    results["records"] += _records("decode_attention", reps, fl_m, by_m)
    fl_d = 4.0 * 32768 * 8 * 128 * 8   # decode_32k per chip: 8 batch x kv8
    by_d = 32768 * 2 * 8 * 128 * 2 * 8
    rows.append(["decode_attention", f"{us:.0f}",
                 f"{_tpu_roofline_us(fl_d, by_d):.0f} (32k cache/chip)"])
    results["decode_attention_us"] = us

    # ssd chunk
    Bh, nc, Q, P, N = 2, 4, 64, 32, 64
    x = jax.random.normal(ks[0], (2, nc, Q, 2, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, nc, Q, 2)))
    A = -jnp.exp(jax.random.normal(ks[2], (2,)))
    dA = dt * A[None, None, None]
    dAcs = jnp.cumsum(dA, axis=2)
    Bm = jax.random.normal(ks[1], (2, nc, Q, 2, N), jnp.float32)
    Cm = jax.random.normal(ks[2], (2, nc, Q, 2, N), jnp.float32)
    reps = _time_reps(ssd_chunk, x, dt, dA, dAcs, Bm, Cm)
    us = float(np.mean(reps))
    fl_m = 2 * nc * (2 * Q * Q * 2 * (P + N))       # chunked scan matmuls
    by_m = 2 * nc * Q * 2 * (P + 2 * N) * 4
    results["records"] += _records("ssd_scan", reps, fl_m, by_m)
    # mamba2-370m prefill_32k per chip: 32 heads/16 = 2 heads x 32k tokens
    fl_s = 2 * (32768 / 256) * (2 * 256 * 256 * (64 + 128))
    by_s = 2 * 32768 * (64 + 2 * 128) * 4
    rows.append(["ssd_scan", f"{us:.0f}",
                 f"{_tpu_roofline_us(fl_s, by_s):.0f} (32k scan/chip)"])
    results["ssd_scan_us"] = us

    # fused dequant-matmul: weight streaming at packed bytes (repro.quant)
    from repro.kernels.dequant_matmul.dequant_matmul import (
        dequant_matmul_int4_pallas, dequant_matmul_int8_pallas)
    from repro.quant import quantize_int4, quantize_int8
    M, Kd, Nd = 8, 256, 256
    xq = jax.random.normal(ks[0], (M, Kd), jnp.float32)
    wq = jax.random.normal(ks[1], (Kd, Nd), jnp.float32)
    fl_q = 2.0 * M * Kd * Nd
    # production shape: one llama-8b-class 4096x4096 decode projection
    fl_p = 2.0 * 8 * 4096 * 4096
    for fmt, quantize, kern, wbytes, wbytes_p in (
            ("int8", quantize_int8, dequant_matmul_int8_pallas,
             Kd * Nd, 4096 * 4096),
            ("int4", lambda w: quantize_int4(w, 32),
             dequant_matmul_int4_pallas, Kd * Nd // 2, 4096 * 4096 // 2)):
        qw, sc = quantize(wq)
        reps = _time_reps(kern, xq, qw, sc, interpret=True)
        us = float(np.mean(reps))
        by_q = wbytes + sc.size * 4 + (M * Kd + M * Nd) * 4
        results["records"] += _records("dequant_matmul", reps, fl_q, by_q,
                                       quant=fmt)
        by_p = wbytes_p + 8 * 2 * 4096 * 2
        rows.append([f"dequant_matmul[{fmt}]", f"{us:.0f}",
                     f"{_tpu_roofline_us(fl_p, by_p):.0f} (4k proj/chip)"])
        results[f"dequant_matmul_{fmt}_us"] = us

    if verbose:
        print(fmt_table(["kernel", "interpret us/call",
                         "modeled TPU us (prod shape)"],
                        rows, "Kernel microbenchmarks"))
        print(f"{len(results['records'])} trace records "
              f"(TraceStore-ingestible)")
    return results


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="append per-rep kernel records to this JSONL trace")
    args = ap.parse_args()
    results = run(verbose=True)
    if args.out:
        from repro.qeil2.telemetry import TraceStore
        store = TraceStore(path=args.out)
        n = store.ingest_kernel_bench(results)
        print(f"appended {n} kernel records -> {args.out}")


if __name__ == "__main__":
    main()
