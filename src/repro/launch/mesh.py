"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``ShardingPolicy`` relies on Auto-mode propagation under ``with mesh:``;
    ``jax.make_mesh`` defaults to Explicit axes, so ask for Auto ones."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips over ("data", "model").
    Multi-pod: 2x16x16 = 512 chips over ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, *,
                    multi_pod: bool = False):
    """Small mesh for CI-scale sharding tests (requires >= n placeholder
    devices — see tests/test_distributed.py which sets the XLA flag in a
    subprocess)."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
