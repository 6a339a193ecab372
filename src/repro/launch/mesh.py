"""Mesh construction.

Kept as functions (never module-level constants) so importing this module
does not touch jax device state — a multi-device test sets
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` before any jax
initialization and then calls these.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_local_mesh", "make_fleet_mesh", "mesh_axis_sizes"]


def _auto_mesh(shape: tuple, axes: tuple):
    """``jax.make_mesh`` with every axis ``Auto``: the drivers leave layout
    between their shard_map regions to the partitioner (the default axis
    type is ``Explicit``, which makes sharded results reject unannotated
    gathers)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests/examples)."""
    return _auto_mesh((data, model), ("data", "model"))


def make_fleet_mesh(region: int | None = None, data: int = 1):
    """Two-level fleet mesh: 'region' carries the cross-host hierarchy axis
    (one shard per group of regions, the only axis the per-refresh merge
    collectives cross — DESIGN.md Sec. 13), 'data' the intra-shard networks
    axis.  ``region=None`` spreads the region axis over every local device
    (the multi-host simulation shape: ``XLA_FLAGS
    --xla_force_host_platform_device_count=N`` forced before jax init).
    """
    n = (jax.device_count() // data) if region is None else region
    return _auto_mesh((n, data), ("region", "data"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
