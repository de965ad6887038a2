"""Mesh construction (functions, never module-level constants — importing
this module must not touch jax device state)."""
from __future__ import annotations

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import AxisType, Mesh


def make_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto``: the LM path places
    activations with ``with_sharding_constraint``, which takes Auto axes
    only (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Production mesh: 16x16 = 256 chips/pod; multi-pod adds a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2, pod: int = 0):
    """Small mesh over however many (possibly fake) devices tests have."""
    if pod:
        return make_mesh((pod, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def _rw_devices(mesh: Mesh | None) -> np.ndarray:
    """``mesh``'s devices flattened, or else every device in the ring order
    JAX picks for the chip's interconnect (on a v5e 2x2: 0, 1, 3, 2, so
    every neighbour on the ring is a direct link)."""
    if mesh is not None:
        return np.asarray(mesh.devices).reshape(-1)
    return mesh_utils.create_device_mesh((jax.device_count(),))


def make_rw_mesh(mesh: Mesh | None = None) -> Mesh:
    """1-D mesh over all devices for the walk engine's flattened ``rw`` axis
    (walks are data-parallel over every chip of the production mesh)."""
    return Mesh(_rw_devices(mesh), ("rw",))


def make_table_mesh(mesh: Mesh | None = None,
                    max_shards: int | None = None) -> Mesh:
    """1-D ``rw`` mesh for vertex-range-sharded SGNS tables (DESIGN.md §16).

    Same axis name and device order as :func:`make_rw_mesh`, so table shard
    *s* owns the same vertex range as the walk engine's graph shard *s* —
    after ``relabel=degree`` the hot vertices are spread across table shards
    the same deliberate way they are spread across graph shards.
    ``max_shards`` restricts to a device prefix (benches compare shard
    counts inside one multi-device process this way).
    """
    devices = _rw_devices(mesh)
    if max_shards is not None:
        devices = devices[:max_shards]
    return Mesh(devices, ("rw",))
