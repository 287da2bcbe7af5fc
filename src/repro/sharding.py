"""Logical-axis -> mesh-axis mapping (MaxText-style rules).

Models annotate parameters with *logical* axes; this module resolves them to
mesh ``PartitionSpec``s with divisibility-aware fallback (a dimension that
does not divide its target mesh axis is replicated instead — e.g. kv_heads=8
on a 16-way model axis).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.layers import LP, is_lp


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Which mesh axes play which role."""

    batch: Tuple[str, ...]        # batch / fsdp data axes, e.g. ("pod","data")
    data: str = "data"            # fsdp weight axis
    model: str = "model"          # tensor/expert-parallel axis

    @staticmethod
    def for_mesh(mesh: Mesh) -> "MeshAxes":
        names = mesh.axis_names
        if "pod" in names:
            return MeshAxes(batch=("pod", "data"))
        return MeshAxes(batch=("data",))


# Logical axis -> mesh axis role. Resolved against a MeshAxes instance.
LOGICAL_RULES = {
    "vocab": "model",
    "embed": "data",        # fsdp on the d_model dim of weight matrices
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "rnn": "model",         # recurrent-width dim (rwkv / rg-lru)
    "expert": "model",      # expert parallelism
    "expert_mlp": "data",   # fsdp on per-expert hidden dim
    "layers": None,
    "conv": None,
    "lora": None,
    None: None,
}


def _axis_size(mesh: Mesh, name: Optional[str]) -> int:
    if name is None:
        return 1
    return mesh.shape[name]


def spec_for(mesh: Mesh, axes: MeshAxes, logical: Tuple[Optional[str], ...],
             shape: Tuple[int, ...]) -> P:
    """Resolve logical axes to a PartitionSpec.

    Rules: non-divisible dims are replicated; if two dims resolve to the same
    mesh axis (e.g. a (layers, E, d, f) expert weight mapping both d and f to
    the fsdp axis, or a square (d, d) projection), only the largest dim keeps
    the mesh axis — a mesh axis may shard at most one dim.
    """
    entries = []
    for dim, name in zip(shape, logical):
        target = LOGICAL_RULES.get(name)
        if target is None:
            entries.append(None)
            continue
        mesh_axis = axes.model if target == "model" else axes.data
        if mesh_axis in mesh.axis_names and dim % _axis_size(mesh, mesh_axis) == 0:
            entries.append(mesh_axis)
        else:
            entries.append(None)
    # dedupe: keep the largest dim per mesh axis
    for axis in set(e for e in entries if e is not None):
        idxs = [i for i, e in enumerate(entries) if e == axis]
        if len(idxs) > 1:
            keep = max(idxs, key=lambda i: shape[i])
            for i in idxs:
                if i != keep:
                    entries[i] = None
    return P(*entries)


def shardings_for_lp_tree(mesh: Mesh, axes: MeshAxes, lp_tree):
    """LP tree -> matching tree of NamedShardings."""
    def one(p: LP):
        return NamedSharding(mesh, spec_for(mesh, axes, p.axes, p.value.shape))
    return jax.tree.map(one, lp_tree, is_leaf=is_lp)


def specs_for_lp_tree(mesh: Mesh, axes: MeshAxes, lp_tree):
    def one(p: LP):
        return spec_for(mesh, axes, p.axes, p.value.shape)
    return jax.tree.map(one, lp_tree, is_leaf=is_lp)


def batch_spec(axes: MeshAxes, ndim: int, batch_dim: int = 0) -> P:
    entries = [None] * ndim
    entries[batch_dim] = axes.batch if len(axes.batch) > 1 else axes.batch[0]
    return P(*entries)


def constrain(x, mesh: Mesh, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
