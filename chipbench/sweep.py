"""Knee sweep of an open-loop cell: one set-up, then a window at each rate.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1.5,2,2.5

For each rate prints one JSON line: requests due, completed inside the
window, the backlog (sent but not completed) at the window's end and its
growth (least-squares slope over the window's second half, requests/s),
and the cell's end-to-end metrics. The knee is the highest rate whose
backlog does not grow: whose backlog at the window's end is no more than
the in-flight batches hold (``max_batch_requests`` x
``max_inflight_batches``); the slope is printed too, but over half a
window it swings with the arrivals. A cell's fixed rate is set at about
4/5 of the knee. Not part of the benchmark's runs.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def backlog_slope(recs, seconds: float) -> float:
    """Slope of (sent - completed) over the second half of the window."""
    import numpy as np
    ts = np.linspace(seconds / 2, seconds, 41)
    back = [sum(1 for r in recs if r.submitted <= t)
            - sum(1 for r in recs if r.done is not None and r.done <= t)
            for t in ts]
    return float(np.polyfit(ts, back, 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import registry
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench import harness
    from chipbench.flops import Sizes
    from chipbench.peaks import peaks_for
    from chipbench.stack import Recorder, build, listen_compiles
    from chipbench.traffic import Mix

    cell = registry.cell(args.workload)
    conf = cell.config
    rec = Recorder()
    listen_compiles(rec)
    mix = Mix(cell.traffic, conf["padded_vocab_size"], args.seed)
    stack = build(conf, mix, args.seed, rec)
    rec.enabled = False
    harness.warm_up(stack, mix, conf, args.seed)
    peaks = peaks_for(jax.devices()[0].device_kind)
    print(f"[sweep] set-up {time.perf_counter() - T_START:.1f}s",
          file=sys.stderr, flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        # the same prefixes as the warm-up, new turns for every window
        m = Mix(dict(cell.traffic, rate_per_s=rate),
                conf["padded_vocab_size"], args.seed)
        rec.reset()
        rec.enabled = True
        recs, t0 = harness.open_loop(stack.sched,
                                     m.schedule(args.seconds, block=1 + i),
                                     args.seconds,
                                     harness.Tracer(args.seconds, False))
        rec.enabled = False
        run = harness.Run(seconds=args.seconds, setup_s=0.0,
                          sizes=Sizes.of(conf), peaks=peaks, reqs=recs)
        run.calls = [harness._shift(c, t0) for c in rec.calls]
        run.compile_times = [t - t0 for t in rec.compile_times]
        harness._attach_calls(run)
        done_in = sum(1 for r in recs if r.done is not None
                      and r.done <= args.seconds)
        row = {"rate_per_s": rate, "due": len(recs),
               "completed_in_window": done_in,
               "backlog_at_end": len(recs) - done_in,
               "backlog_slope_per_s": backlog_slope(recs, args.seconds)}
        for name in ("ttft_p90_ms", "tbt_p95_ms",
                     "queue_wait_p90_ms.chat", "decode_step_ms.chat",
                     "compiles_in_window.chat"):
            row[name] = registry.reader(name)(run)
        row["batch_seqs_mean"] = registry.reader(
            "batch_seqs_mean.offline")(run)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
