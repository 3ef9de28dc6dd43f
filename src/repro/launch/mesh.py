"""Production mesh construction (+ Algorithm-2 device ordering).

`make_production_mesh` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: 16×16 = 256 chips (data, model).
Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the 'pod' axis is the
DCN boundary and carries only data-parallel gradient all-reduces.

`vertex_cut_device_order` feeds a shard-communication matrix through the
paper's memory-centric mapping (core.planner.mesh_device_order) so that
heavily-communicating model shards sit on ICI-adjacent chips.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_mesh_with_order"]


def make_production_mesh(*, multi_pod: bool = False):
    """Auto-sharded axes: GSPMD propagates the models' `maybe_shard`
    hints (`jax.make_mesh` defaults to Explicit axes, under which a
    sharding constraint acts as an assertion)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_with_order(shard_comm: np.ndarray | None = None, *,
                         multi_pod: bool = False):
    """Mesh whose device order is chosen by the paper's Algorithm 2.

    `shard_comm[i,j]`: traffic between logical 'model' shards i and j
    (e.g. collective bytes from a dry-run).  Shards are mapped to mesh
    columns so communicating shards are ICI neighbours; identity order
    when no matrix is given."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    devices = np.array(jax.devices())
    n = int(np.prod(shape))
    if len(devices) < n:
        raise RuntimeError(f"need {n} devices, have {len(devices)}")
    devices = devices[:n]
    if shard_comm is not None:
        from repro.core.planner import mesh_device_order
        m = shape[-1]
        order = mesh_device_order(shard_comm[:m, :m], 1, m)
        # permute the model-axis columns of every (pod, data) row
        grid = devices.reshape(-1, m)
        inv = np.argsort(order)
        grid = grid[:, inv]
        devices = grid.reshape(-1)
    from jax.sharding import Mesh
    return Mesh(devices.reshape(shape), axes)
