"""Activation sharding hints (§Perf optimization, off by default).

GSPMD infers most internal shardings from the jit-boundary constraints, but
the Mamba-2 SSD block defeats it: B/C come from slicing a tensor-sharded
projection at non-shard-aligned offsets, so propagation gives up and
replicates the whole chunked scan over the "model" axis (measured: per-device
HLO FLOPs ~16x the sharded ideal, see EXPERIMENTS.md §Perf pair 1).

``constrain(x, dim_axes)`` inserts a with_sharding_constraint pinning chosen
dims to mesh axes while leaving the rest unconstrained. Enabled globally via
``enable(mesh)`` (the dry-run's --hints flag) so the baseline stays measurable.
With no mesh there is nothing to pin and ``constrain`` is the identity; on a
mesh a constraint that cannot be applied raises.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

_ENABLED = False
_MESH: Optional[Mesh] = None
_TENSOR_AXIS = "model"


def enable(mesh: Optional[Mesh] = None, tensor_axis: str = "model") -> None:
    global _ENABLED, _MESH, _TENSOR_AXIS
    _ENABLED = True
    _MESH = mesh
    _TENSOR_AXIS = tensor_axis


def disable() -> None:
    global _ENABLED, _MESH
    _ENABLED = False
    _MESH = None


def enabled() -> bool:
    return _ENABLED


def constrain(x: jax.Array, dim_axes: Tuple[Optional[str], ...],
              divisible_dim: Optional[int] = None) -> jax.Array:
    """Pin dims named "tensor" to the tensor axis; None dims unconstrained.

    divisible_dim: index whose size must divide the axis (skip hint if not).
    """
    if not _ENABLED or _MESH is None or _TENSOR_AXIS not in _MESH.shape:
        return x
    mesh_size = _MESH.shape[_TENSOR_AXIS]
    spec = []
    for i, a in enumerate(dim_axes):
        if a == "tensor":
            if x.shape[i] % mesh_size != 0:
                return x  # not divisible: skip the hint entirely
            spec.append(_TENSOR_AXIS)
        else:
            spec.append(P.UNCONSTRAINED)
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, P(*spec)))
