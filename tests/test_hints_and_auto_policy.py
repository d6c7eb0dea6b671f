"""Sharding hints no-op safety + auto layout selection."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.distributed import ShardingPolicy
from repro.distributed import hints
from repro.models import Model


class FakeMesh:
    def __init__(self, shape, names):
        self.axis_names = names
        self.devices = np.empty(shape, object)


def test_hints_disabled_is_identity():
    hints.disable()
    x = jnp.ones((4, 8))
    assert hints.constrain(x, (None, "tensor")) is x


def test_hints_enabled_outside_mesh_graceful():
    """With no mesh in scope, constrain must not crash (dry-run safety)."""
    hints.enable()
    try:
        x = jnp.ones((4, 8))
        y = hints.constrain(x, (None, "tensor"))
        assert y.shape == x.shape
    finally:
        hints.disable()


def test_hints_on_a_mesh_apply_or_raise():
    """On a mesh the hint is applied, and a constraint that cannot be
    applied raises instead of silently returning its input."""
    import pytest
    from repro.launch.mesh import make_debug_mesh
    hints.enable(make_debug_mesh(1, 1))
    try:
        x = jnp.arange(32.0).reshape(4, 8)
        y = jax.jit(lambda a: hints.constrain(a, (None, "tensor")))(x)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
        with pytest.raises(ValueError):
            # a 2-entry spec for a rank-1 array is no valid sharding
            hints.constrain(jnp.ones((4,)), ("tensor", None))
    finally:
        hints.disable()


def test_auto_policy_small_model_goes_dp_only():
    mesh = FakeMesh((16, 16), ("data", "model"))
    small = get_config("mamba2-370m")
    pol = ShardingPolicy.auto(mesh, small, global_batch=256)
    assert pol.tensor_axis is None
    assert "model" in pol.dp_axes


def test_auto_policy_large_model_keeps_tp():
    mesh = FakeMesh((16, 16), ("data", "model"))
    big = get_config("qwen2-72b")
    pol = ShardingPolicy.auto(mesh, big, global_batch=256)
    assert pol.tensor_axis == "model"


def test_auto_policy_small_batch_keeps_tp():
    """batch 32 cannot fill 256 chips DP-only — replication would waste 8x."""
    mesh = FakeMesh((16, 16), ("data", "model"))
    small = get_config("mamba2-370m")
    pol = ShardingPolicy.auto(mesh, small, global_batch=32)
    assert pol.tensor_axis == "model"
