"""Sharding policy unit tests + an actual small-mesh SPMD execution test
(subprocess, because the placeholder-device XLA flag must be set before jax
initializes — the main test process keeps the single real CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import ShardingPolicy
from repro.launch.specs import adapt_config, input_specs
from repro.models import Model
from repro.models.config import INPUT_SHAPES


class FakeMesh:
    """Shape-only stand-in so the policy logic tests need no real devices."""

    def __init__(self, shape, names):
        import numpy as np
        self.axis_names = names
        self.devices = np.empty(shape, object)


def _policy(multi=False):
    if multi:
        return ShardingPolicy(FakeMesh((2, 16, 16), ("pod", "data", "model")))
    return ShardingPolicy(FakeMesh((16, 16), ("data", "model")))


def test_param_specs_divisible_everywhere():
    """Every emitted PartitionSpec must evenly divide its tensor dim for
    every assigned architecture — the invariant behind 80/80 dry-run passes."""
    import numpy as np
    for arch in ("qwen2-72b", "mamba2-370m", "granite-moe-3b-a800m",
                 "deepseek-v2-lite-16b", "jamba-v0.1-52b", "musicgen-medium"):
        cfg = get_config(arch)
        pol = _policy()
        specs = Model(cfg).param_specs()

        def check(path, leaf):
            spec = pol.param_spec(path, leaf)
            for dim, ax in zip(leaf.shape, spec):
                if ax is None:
                    continue
                n = pol.axis_sizes[ax] if isinstance(ax, str) else \
                    int(np.prod([pol.axis_sizes[a] for a in ax]))
                assert dim % n == 0, (arch, path, leaf.shape, spec)
            # no axis used twice
            used = [a for a in spec if a is not None]
            flat = []
            for a in used:
                flat.extend(a if isinstance(a, tuple) else (a,))
            assert len(flat) == len(set(flat)), (arch, path, spec)

        jax.tree_util.tree_map_with_path(check, specs)


def test_moe_experts_shard_on_model_axis():
    pol = _policy()
    cfg = get_config("deepseek-v2-lite-16b")
    specs = Model(cfg).param_specs()
    gate = specs["blocks"]["l0"]["moe"]["gate"]   # (L, E, d, ff)
    path = (jax.tree_util.DictKey("blocks"), jax.tree_util.DictKey("l0"),
            jax.tree_util.DictKey("moe"), jax.tree_util.DictKey("gate"))
    spec = pol.param_spec(path, gate)
    assert spec[1] == "model", spec              # 64 experts / 16 = 4


def test_granite_experts_fall_back_to_ff_sharding():
    pol = _policy()
    cfg = get_config("granite-moe-3b-a800m")     # 40 experts: 40 % 16 != 0
    specs = Model(cfg).param_specs()
    gate = specs["blocks"]["l0"]["moe"]["gate"]
    path = (jax.tree_util.DictKey("blocks"), jax.tree_util.DictKey("l0"),
            jax.tree_util.DictKey("moe"), jax.tree_util.DictKey("gate"))
    spec = pol.param_spec(path, gate)
    assert spec[1] is None
    assert spec[2] == "data" or spec[3] == "model", spec


def test_batch_axes_divisibility():
    pol = _policy(multi=True)
    assert pol.batch_axes(256) == ("pod", "data")   # train_4k: 256 % 32 == 0
    assert pol.batch_axes(32) == ("pod", "data")    # prefill_32k
    assert pol.batch_axes(1) is None                # long_500k: replicate
    assert pol.batch_axes(24) == "pod"              # divisible by 2 only


def test_input_specs_exist_for_all_40_pairs():
    from repro.configs import ASSIGNED_ARCHS
    for arch in ASSIGNED_ARCHS:
        for shape in INPUT_SHAPES.values():
            cfg = adapt_config(get_config(arch), shape)
            batch, cache = input_specs(get_config(arch), shape)
            assert "tokens" in batch
            if shape.kind == "decode":
                assert cache, (arch, shape.name)
                if shape.name == "long_500k" and "a" in cfg.pattern:
                    assert cfg.attn_window == 4096


SMALL_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, json
    from repro.configs import get_config
    from repro.distributed import ShardingPolicy
    from repro.launch.mesh import make_debug_mesh
    from repro.models import Model
    from repro.training import AdamWConfig, init_opt_state, make_train_step

    mesh = make_debug_mesh(4, 2)
    cfg = get_config("granite-moe-3b-a800m").reduced()
    model = Model(cfg, dtype=jnp.float32)
    policy = ShardingPolicy(mesh)
    p_sh = policy.param_shardings(model.param_specs())
    step = make_train_step(model, AdamWConfig(lr=1e-3, total_steps=5))
    with mesh:
        params = jax.jit(model.init, out_shardings=p_sh)(jax.random.key(0))
        opt = init_opt_state(params)
        toks = jnp.zeros((8, 16), jnp.int32)
        # params/opt are already committed to their NamedShardings (params via
        # out_shardings above, opt built from the sharded params), so jit
        # infers in_shardings; an explicit (p_sh, None, None) would wrongly
        # constrain the sharded opt state to replicated and fail.
        jitted = jax.jit(step)
        losses = []
        for i in range(3):
            params, opt, m = jitted(params, opt,
                                    {"tokens": toks + i, "labels": toks})
            losses.append(float(m["loss"]))
    print(json.dumps({"losses": losses,
                      "n_devices": jax.device_count()}))
""")


def test_real_spmd_execution_small_mesh():
    """Execute 3 sharded train steps on an 8-device host mesh (subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SMALL_MESH_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["n_devices"] == 8
    assert all(l > 0 and l == l for l in result["losses"])


DEBUG_MESH_TRAIN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    import jax, json
    from repro.launch import train
    train.main(["--arch", "chatglm3-6b", "--smoke", "--steps", "2",
                "--batch", "4", "--seq", "16", "--mesh", "debug"])
    print(json.dumps({"n_devices": jax.device_count()}))
""")


def test_train_launcher_debug_mesh_four_devices():
    """`repro.launch.train --mesh debug` runs sharded steps on the 2x2
    `make_debug_mesh()` over 4 virtual devices (subprocess)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", DEBUG_MESH_TRAIN_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["n_devices"] == 4
    losses = [float(l.split()[3]) for l in lines if l.startswith("step")]
    assert len(losses) == 2 and all(0 < l < 1e3 for l in losses), lines


def test_dryrun_artifacts_all_pass():
    """The 80 recorded dry-run artifacts (40 pairs x 2 meshes) are error-free
    and contain the roofline inputs."""
    art_dir = os.path.join(os.path.dirname(__file__), "..", "experiments",
                           "dryrun")
    if not os.path.isdir(art_dir):
        pytest.skip("dry-run artifacts not generated yet")
    files = [f for f in os.listdir(art_dir) if f.endswith(".json")
             and ("__single.json" in f or "__multi.json" in f)]
    singles = [f for f in files if f.endswith("__single.json")]
    multis = [f for f in files if f.endswith("__multi.json")]
    assert len(singles) == 40, f"expected 40 single-pod artifacts: {len(singles)}"
    assert len(multis) == 40, f"expected 40 multi-pod artifacts: {len(multis)}"
    for f in files:
        with open(os.path.join(art_dir, f)) as fh:
            art = json.load(fh)
        assert "error" not in art, (f, art.get("error"))
        assert art["cost_analysis"].get("flops", 0) > 0, f
        assert art["n_chips"] == (512 if "__multi" in f else 256)


from _hypothesis_compat import given, settings, st


@given(d_in=st.integers(8, 4096), d_out=st.integers(8, 4096),
       stacked=st.booleans())
@settings(max_examples=150, deadline=None)
def test_param_spec_divisibility_property(d_in, d_out, stacked):
    """Property: for ANY weight shape, every sharded dim divides its axis."""
    import numpy as np
    pol = _policy()
    shape = (4, d_in, d_out) if stacked else (d_in, d_out)
    leaf = jax.ShapeDtypeStruct(shape, "float32")
    keys = ["blocks", "l0", "attn", "wq"] if stacked else ["lm_head", "w"]
    path = tuple(jax.tree_util.DictKey(k) for k in keys)
    spec = pol.param_spec(path, leaf)
    for dim, ax in zip(shape, spec):
        if ax is None:
            continue
        n = pol.axis_sizes[ax] if isinstance(ax, str) else \
            int(np.prod([pol.axis_sizes[a] for a in ax]))
        assert dim % n == 0


@given(batch=st.integers(1, 1024))
@settings(max_examples=100, deadline=None)
def test_batch_axes_divisibility_property(batch):
    import numpy as np
    pol = _policy(multi=True)
    axes = pol.batch_axes(batch)
    if axes is None:
        return
    n = pol.axis_sizes[axes] if isinstance(axes, str) else \
        int(np.prod([pol.axis_sizes[a] for a in axes]))
    assert batch % n == 0 and batch >= n
