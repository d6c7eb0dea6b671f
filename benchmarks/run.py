"""Benchmark harness entry point: one function per paper table.

Prints ``name,us_per_call,derived`` CSV (one line per benchmark) after the
human-readable tables. ``PYTHONPATH=src python -m benchmarks.run``.
"""
from __future__ import annotations

import sys
import time

BENCHES = [
    ("table1_beta_stability", "benchmarks.beta_stability", "mean_beta"),
    ("table3_heterogeneity", "benchmarks.heterogeneity", "coverage_gain_pp"),
    ("table4_components", "benchmarks.components", "final_coverage"),
    ("table5_variance", "benchmarks.variance", "max_cv_pct"),
    ("table7_energy_breakdown", "benchmarks.energy_breakdown",
     "decode_dominates"),
    ("table8_latency_breakdown", "benchmarks.latency_breakdown",
     "total_delta_pct"),
    ("table10_thermal", "benchmarks.thermal",
     "zero_events_with_protection"),
    ("table11_fault_tolerance", "benchmarks.fault_tolerance",
     "all_recovered"),
    ("table12_adversarial", "benchmarks.adversarial",
     "all_structural_blocked"),
    ("tables13_15_cross_dataset", "benchmarks.cross_dataset",
     "task_agnostic"),
    ("table16_main_results", "benchmarks.main_results",
     "energy_reduced_all"),
    ("sec5_5_edge_vs_cloud", "benchmarks.edge_vs_cloud",
     "edge_wins_small_models"),
    ("fig5_6_coverage_curves", "benchmarks.coverage_curves",
     "mean_gain_pp"),
    ("roofline_table", "benchmarks.roofline_table", "n_analyzed"),
    ("kernel_bench", "benchmarks.kernel_bench", "flash_attention_us"),
    ("pgsam_compare", "benchmarks.pgsam_compare",
     "all_within_5pct_of_oracle"),
    ("pareto_router", "benchmarks.pareto_router", "acceptance_all"),
    ("calibration_report", "benchmarks.calibration_report",
     "acceptance_all"),
    ("serving_schedule", "benchmarks.serving_schedule",
     "acceptance_all"),
    ("kv_paging", "benchmarks.kv_paging", "acceptance_all"),
    ("quant_serving", "benchmarks.quant_serving", "acceptance_all"),
    ("spec_decode", "benchmarks.spec_decode", "acceptance_all"),
    ("prefix_pool", "benchmarks.prefix_pool", "acceptance_all"),
    ("preemption", "benchmarks.preemption", "acceptance_all"),
    ("bench_compare", "benchmarks.compare", "self_check_ok"),
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import importlib
    csv_lines = ["name,us_per_call,derived"]
    failures = []
    for name, module, key in BENCHES:
        print(f"\n{'=' * 72}\nBENCH {name}\n{'=' * 72}")
        t0 = time.perf_counter()
        try:
            mod = importlib.import_module(module)
            result = mod.run(verbose=True)
            derived = result.get(key, "")
        except Exception as e:  # keep the harness going; report at the end
            import traceback
            traceback.print_exc()
            failures.append(name)
            derived = f"ERROR:{e!r}"
        us = (time.perf_counter() - t0) * 1e6
        csv_lines.append(f"{name},{us:.0f},{derived}")

    print("\n" + "\n".join(csv_lines))
    if failures:
        print(f"\nFAILED BENCHES: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
