"""Production mesh builders (functions, never module-level constants — see
multi-pod dry-run spec: importing this module must not touch jax device
state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from ..sharding import MeshCtx


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading 2-pod axis
    (2 x 16 x 16 = 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh_ctx(*, multi_pod: bool = False) -> MeshCtx:
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshCtx(mesh=mesh, data_axes=data_axes, model_axis="model")


def make_local_mesh_ctx(data: int = 1, model: int = 1) -> MeshCtx:
    """Small mesh over however many devices exist (tests)."""
    mesh = jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    return MeshCtx(mesh=mesh, data_axes=("data",), model_axis="model")
