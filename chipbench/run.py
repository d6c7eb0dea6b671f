"""Run one benchmark cell once, on the machine this process starts on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for. Otherwise builds the cell's served stack, warms up
its shapes, measures for ``--seconds``, checks the served tokens against
the plain reference, and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, traced, ``breakdown``; last the numbers compared with their
limits, also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import registry
    cell = registry.cell(args.workload)
    # the system under test: its absence is an error, before any device
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: needs {cell.chips} TPU chip(s); JAX finds "
              f"{len(devices)} {devices[0].platform!r} device(s)",
              file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    # every program, however quick to compile, is kept: only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(f"[device] {devices[0].device_kind} x{len(devices)} jax "
          f"{jax.__version__} compile cache {cache}", file=sys.stderr,
          flush=True)

    from chipbench.harness import run_cell
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_START)
    for name, c in out["compared"].items():
        print(f"[compared] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
