"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; it needs the 256 (or 512)
devices of a pod slice.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.sharding import MeshAxes


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over available devices (smoke tests / CPU training)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=jax.devices()[: data * model])


def axes_for(mesh: Mesh) -> MeshAxes:
    return MeshAxes.for_mesh(mesh)
