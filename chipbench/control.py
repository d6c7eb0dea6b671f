"""The readings a check limit is set from, for many seeds in one process.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s>

For each seed: one run of the cell as the benchmark makes it (untraced),
then the reference over the served tokens as in every run, and the
control: the same reference computed in the precision below the one the
configuration states (``check`` / ``control``: float8 weights and matmul
inputs for bf16, int4 weights for int8), put in the program's place and
judged by the same comparison.
Prints one JSON line per seed with the program's verdict and readings
(`chipbench.check.stats`) and each control's; the limit goes between the largest of the one and the
smallest of the other. ``--formats`` reads other control formats beside or
instead of the configuration's. Not part of the benchmark's runs.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--formats", default=None,
                    help="weight formats to read as controls (default: the "
                         "configuration's check/control)")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench import registry
    from repro.launch.compile_cache import enable_compile_cache

    import jax
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from chipbench.harness import run_cell
    cell = registry.cell(args.workload)
    formats = (args.formats.split(",") if args.formats
               else [cell.config["check"]["control"]])
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out = run_cell(cell, seed, args.seconds, False, t0,
                       controls=formats)
        print(json.dumps({
            "seed": seed, "correct": out["correct"], **out["stats"],
            "controls": {f: {"correct": c["correct"], **c["stats"]}
                         for f, c in out["controls"].items()},
            "bad_samples": out["compared"]["bad_samples"]["value"],
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
