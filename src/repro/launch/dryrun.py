import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture x input shape) combination against the
production mesh — 16x16 single-pod and 2x16x16 multi-pod — using
ShapeDtypeStruct inputs (no allocation). For each combination it records:

  * compiled.memory_analysis()  (bytes per device — proves it fits)
  * compiled.cost_analysis()    (FLOPs / bytes for the §Roofline terms)
  * collective bytes parsed from the optimized HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute)

Artifacts land in experiments/dryrun/<arch>__<shape>__<mesh>.json; the roofline
table (benchmarks/roofline_table.py) and EXPERIMENTS.md §Dry-run read them.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
"""
import argparse
import json
import time
import traceback
from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.roofline import collective_bytes_from_hlo
from repro.distributed import ShardingPolicy
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import adapt_config, get_shape, input_specs
from repro.models import Model
from repro.models.config import INPUT_SHAPES
from repro.training import AdamWConfig, make_train_step, opt_state_specs

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "experiments", "dryrun")


def _artifact_path(arch: str, shape: str, mesh_kind: str,
                   tag: str = "") -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(ARTIFACT_DIR,
                        f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def _prefill_step(model):
    def step(params, batch, cache):
        logits, cache, _ = model.forward(params, batch, cache)
        return logits[:, -1], cache
    return step


def _decode_step(model):
    def step(params, batch, cache):
        logits, cache, _ = model.forward(params, batch, cache)
        return logits[:, 0], cache
    return step


def lower_and_compile(arch: str, shape_name: str, mesh_kind: str = "single",
                      verbose: bool = True, fsdp: bool = True,
                      shard_hints: bool = False, mla_naive: bool = False,
                      ssm_split: bool = False, no_tp: bool = False,
                      microbatches: int = 1, cache_fp8: bool = False,
                      cross_cache: bool = False,
                      dtype=jnp.bfloat16) -> Dict:
    """One (arch x shape x mesh) dry-run. Returns the artifact dict.

    Variant knobs for the §Perf hillclimbs:
      shard_hints — activation sharding constraints in the SSD block
      mla_naive   — decompressed (non-absorbed) MLA decode baseline
      fsdp=False  — tensor-parallel only (weights replicated over "data")
    """
    t0 = time.time()
    shape = get_shape(shape_name)
    cfg = adapt_config(get_config(arch), shape)
    if mla_naive:
        cfg = cfg.with_overrides(mla_absorbed=False)
    if ssm_split:
        cfg = cfg.with_overrides(ssm_split_proj=True)
    if cross_cache:
        cfg = cfg.with_overrides(cross_kv_cache=True)
    from repro.distributed import hints
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    if shard_hints:
        hints.enable(mesh)
    else:
        hints.disable()
    n_chips = mesh.devices.size
    policy = ShardingPolicy(mesh, fsdp_enabled=fsdp,
                            tensor_enabled=not no_tp)

    model = Model(cfg, dtype=dtype, remat=(shape.kind == "train"))
    p_specs = model.param_specs()
    p_shard = policy.param_shardings(p_specs)
    batch_specs, cache_specs = input_specs(
        cfg, shape, dtype,
        cache_dtype=jnp.float8_e4m3fn if cache_fp8 else None)
    b_shard = policy.batch_shardings(batch_specs)

    with mesh:
        if shape.kind == "train":
            opt_cfg = AdamWConfig()
            train_step = make_train_step(model, opt_cfg,
                                         microbatches=microbatches)
            o_specs = opt_state_specs(p_specs)
            o_shard = policy.opt_state_shardings(p_specs)
            metrics_shard = {k: policy.scalar() for k in
                             ("lr", "grad_norm", "step", "loss")}
            jitted = jax.jit(
                train_step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, metrics_shard))
            lowered = jitted.lower(p_specs, o_specs, batch_specs)
        else:
            c_shard = policy.cache_shardings(cache_specs)
            extra = 2 if cfg.n_codebooks > 1 else 1
            logits_shard = policy.named(policy.logits_spec(
                shape.global_batch, cfg.vocab_size, extra_dims=extra - 1))
            step = (_prefill_step(model) if shape.kind == "prefill"
                    else _decode_step(model))
            jitted = jax.jit(
                step,
                in_shardings=(p_shard, b_shard, c_shard),
                out_shardings=(logits_shard, c_shard))
            lowered = jitted.lower(p_specs, batch_specs, cache_specs)

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    # ---- artifact assembly
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for key in ("argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes"):
            if hasattr(ma, key):
                mem[key] = int(getattr(ma, key))
        mem["repr"] = str(ma)
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = repr(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float))}
    except Exception as e:
        cost = {"error": repr(e)}

    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    coll = collective_bytes_from_hlo(hlo)

    artifact = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "n_chips": int(n_chips),
        "kind": shape.kind,
        "param_count": Model(cfg).param_count(),
        "active_param_count": Model(cfg).active_param_count(),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "attn_window": cfg.attn_window,
        "fsdp": fsdp, "shard_hints": shard_hints, "mla_naive": mla_naive,
        "ssm_split": ssm_split, "no_tp": no_tp,
        "microbatches": microbatches, "cache_fp8": cache_fp8,
        "cross_cache": cross_cache,
        "memory_analysis": mem,
        "cost_analysis": cost,
        "collective_bytes": coll,
        "hlo_bytes": len(hlo),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}"
              f" ({n_chips} chips): lower {t_lower:.1f}s compile"
              f" {t_compile:.1f}s flops={cost.get('flops', float('nan')):.3e}"
              f" coll_bytes={coll['total']:.3e}")
        print(f"  memory_analysis: {mem.get('repr', mem)}")
    return artifact


def run_one(arch: str, shape_name: str, mesh_kind: str,
            force: bool = False, tag: str = "", **kw) -> Dict:
    path = _artifact_path(arch, shape_name, mesh_kind, tag)
    if not force and os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    try:
        artifact = lower_and_compile(arch, shape_name, mesh_kind, **kw)
    except Exception as e:
        artifact = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                    "error": repr(e), "traceback": traceback.format_exc()}
        print(f"[dryrun] FAILED {arch} x {shape_name} x {mesh_kind}: {e!r}")
    with open(path, "w") as f:
        json.dump(artifact, f, indent=1)
    return artifact


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ASSIGNED_ARCHS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--hints", action="store_true",
                    help="SSD activation-sharding constraints")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--mla-naive", action="store_true")
    ap.add_argument("--ssm-split", action="store_true")
    ap.add_argument("--no-tp", action="store_true",
                    help="pure data-parallel layout (model axis joins batch)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--cache-fp8", action="store_true")
    ap.add_argument("--cross-cache", action="store_true")
    ap.add_argument("--trace", default=None,
                    help="append each artifact's compiled.cost_analysis() "
                         "FLOP/byte counts to this JSONL telemetry trace "
                         "(repro.qeil2.telemetry.TraceStore)")
    args = ap.parse_args()

    trace_store = None
    if args.trace:
        from repro.qeil2.telemetry import TraceStore
        trace_store = TraceStore(path=args.trace)

    archs = ASSIGNED_ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for mesh_kind in meshes:
        for arch in archs:
            for shape_name in shapes:
                art = run_one(arch, shape_name, mesh_kind, force=args.force,
                              tag=args.tag, fsdp=not args.no_fsdp,
                              shard_hints=args.hints,
                              mla_naive=args.mla_naive,
                              ssm_split=args.ssm_split, no_tp=args.no_tp,
                              microbatches=args.microbatches,
                              cache_fp8=args.cache_fp8,
                              cross_cache=args.cross_cache)
                if "error" in art:
                    failures.append((arch, shape_name, mesh_kind))
                elif trace_store is not None:
                    trace_store.ingest_dryrun_artifact(art)
    if failures:
        print(f"\n{len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print(f"\nall {len(archs) * len(shapes) * len(meshes)} dry-runs passed")


if __name__ == "__main__":
    main()
