"""Distributed training launcher.

Shards the same ``make_train_step`` the dry-run lowers across whatever mesh is
available. On this CPU container it runs real steps on a debug mesh with a
reduced config (``--smoke``); on a real TPU fleet the identical code path runs
the full config on the production mesh.

  PYTHONPATH=src python -m repro.launch.train --arch mamba2-370m --smoke \
      --steps 20 --mesh debug
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.data import DataConfig, data_iterator
from repro.distributed import ShardingPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import Model
from repro.training import (AdamWConfig, init_opt_state, make_train_step,
                            save_checkpoint)


def main(argv: Optional[Sequence[str]] = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ASSIGNED_ARCHS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--mesh", default="none", choices=["none", "debug",
                                                       "single", "multi"])
    ap.add_argument("--checkpoint-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    model = Model(cfg, dtype=jnp.float32 if args.smoke else jnp.bfloat16)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=5, total_steps=args.steps)

    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    batch_size=args.batch, kind="markov",
                    n_codebooks=cfg.n_codebooks)
    data = data_iterator(dc)

    params = model.init(jax.random.key(0))
    opt_state = init_opt_state(params)
    step_fn = make_train_step(model, opt_cfg)

    if args.mesh == "none":
        jitted = jax.jit(step_fn)
        ctx = None
    else:
        mesh = (make_debug_mesh() if args.mesh == "debug" else
                make_production_mesh(multi_pod=(args.mesh == "multi")))
        policy = ShardingPolicy(mesh)
        p_sh = policy.param_shardings(model.param_specs())
        o_sh = policy.opt_state_shardings(model.param_specs())
        jitted = jax.jit(step_fn, in_shardings=(p_sh, o_sh, None),
                         out_shardings=(p_sh, o_sh, None))
        ctx = mesh

    def run():
        nonlocal params, opt_state
        t0 = time.perf_counter()
        for step in range(args.steps):
            batch = next(data)
            if cfg.frontend == "vision":
                batch["vision_embeds"] = jnp.zeros(
                    (args.batch, 4, cfg.d_model), model.dtype)
            if cfg.cross_attention:
                batch["cond_memory"] = jnp.zeros(
                    (args.batch, cfg.n_cond_tokens, cfg.d_model), model.dtype)
            params, opt_state, metrics = jitted(params, opt_state, batch)
            if step % 5 == 0 or step == args.steps - 1:
                dt = time.perf_counter() - t0
                print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                      f"gnorm {float(metrics['grad_norm']):.3f}  "
                      f"lr {float(metrics['lr']):.2e}  {dt:.1f}s")

    if ctx is not None:
        with ctx:
            run()
    else:
        run()

    if args.checkpoint_dir:
        path = save_checkpoint(args.checkpoint_dir, args.steps, params)
        print("checkpoint:", path)


if __name__ == "__main__":
    main()
