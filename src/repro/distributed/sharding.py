"""Sharding policy: logical-axis rules + activation constraint context.

Single source of truth for how logical axes map onto the production mesh
(DESIGN.md Sec. 5):

* parameters: FSDP over ("pod","data") on the embed dimension, TP over
  "model" on heads / mlp / vocab / experts;
* activations: batch over ("pod","data"), head/mlp/vocab over "model",
  optional sequence parallelism over "data" for long prefill.

Model code never names mesh axes: it calls :func:`shard_activation` with
logical axes; inside an :func:`activation_sharding` context this becomes a
``with_sharding_constraint``, outside (smoke tests, single device) it is a
no-op.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "PARAM_RULES", "ACT_RULES", "param_rules", "act_rules",
    "activation_sharding", "shard_activation", "logical_to_pspec",
    "network_axis_spec", "shard_networks", "shard_regions",
]

# -- parameter logical axes -------------------------------------------------
# "embed" carries FSDP (ZeRO-3) sharding; everything wide goes to TP.
def param_rules(multi_pod: bool, fsdp: bool = True) -> dict:
    fsdp_axes = (("pod", "data") if multi_pod else ("data",)) if fsdp else None
    return {
        "embed": fsdp_axes,
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "head_dim": None,
        "mlp": "model",
        "experts": "model",
        "ssm_inner": "model",
        "ssm_heads": "model",
        "state": None,
        "conv": None,
        "layers": None,
        "expert_mlp": None,          # per-expert ffn dim (sharded via experts)
    }


def act_rules(multi_pod: bool, seq_shard: bool = False) -> dict:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    return {
        "batch": batch_axes,
        "seq": "data" if seq_shard else None,
        "kv_seq": None,
        "act_embed": None,
        "act_heads": "model",
        "act_kv_heads": "model",
        "act_mlp": "model",
        "act_vocab": "model",
        "act_experts": "model",
        "act_ssm_inner": "model",
        "act_ssm_heads": "model",
        "act_state": None,
        "capacity": None,
    }


PARAM_RULES = param_rules(multi_pod=False)
ACT_RULES = act_rules(multi_pod=False)


# -- activation constraint context ------------------------------------------
class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Mesh | None = None
        self.rules: dict | None = None


_CTX = _Ctx()


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: dict):
    """Enable with_sharding_constraint for shard_activation calls within."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def logical_to_pspec(axes: Sequence[str | None], rules: dict,
                     mesh: Mesh | None = None,
                     dims: Sequence[int] | None = None) -> PartitionSpec:
    """Map logical axis names to a PartitionSpec under divisibility checks."""
    entries = []
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) if mesh else {}
    for i, ax in enumerate(axes):
        mesh_axes = rules.get(ax) if ax is not None else None
        if mesh_axes is None:
            entries.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        if dims is not None and sizes:
            total = 1
            for m in mesh_axes:
                total *= sizes.get(m, 1)
            if total == 0 or dims[i] % total != 0:
                entries.append(None)
                continue
        entries.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    return PartitionSpec(*entries)


def current_mesh() -> Mesh | None:
    """The mesh of the active activation_sharding context (None outside)."""
    return _CTX.mesh


def network_axis_spec(mesh: Mesh, axis: str = "data") -> PartitionSpec:
    """PartitionSpec sharding the leading *networks* axis of a streaming batch.

    The streaming subsystem (DESIGN.md Sec. 8.3) is embarrassingly parallel
    across simulated sensor networks, so the batch axis maps onto the mesh
    data axis; every per-network pytree leaf (covariance band, basis, metrics)
    carries the networks axis first and shares this spec.
    """
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}: {mesh.axis_names}")
    return PartitionSpec(axis)


def shard_networks(mesh: Mesh, tree, axis: str = "data"):
    """Device_put a networks-leading pytree with the streaming sharding."""
    sharding = NamedSharding(mesh, network_axis_spec(mesh, axis))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def shard_regions(mesh: Mesh, tree, axis: str = "region"):
    """Device_put a regions-leading pytree of a two-level fleet (DESIGN.md
    Sec. 13) with its regions axis over the cross-host ``region`` mesh
    axis."""
    sharding = NamedSharding(mesh, network_axis_spec(mesh, axis))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)


def shard_activation(x: jax.Array, axes: Sequence[str | None]) -> jax.Array:
    """Constrain an activation to its logical sharding (no-op outside ctx)."""
    if _CTX.mesh is None or _CTX.rules is None:
        return x
    spec = logical_to_pspec(axes, _CTX.rules, _CTX.mesh, dims=x.shape)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_CTX.mesh, spec))
