"""Production mesh construction (topology-aware — the "Closest" rule).

Mesh layout maps the paper's architecture-aware placement onto ICI
topology: the ``model`` (tensor-parallel) axis is innermost so its
heavy collectives ride contiguous single-pod ICI rings; the ``data``
axis spans the pod; the ``pod`` axis is outermost so only the
infrequent gradient all-reduce (optionally int8-compressed) crosses the
pod interconnect.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

__all__ = ["make_production_mesh", "auto_axes", "MeshAxes", "axes_for"]


def auto_axes(mesh):
    """``mesh`` with every axis Auto, whatever axis types it carries.

    The sharding rules are GSPMD-style (the compiler propagates
    activation shardings), not JAX's default Explicit typing."""
    auto = jax.sharding.AxisType.Auto
    return mesh.update(axis_types=(auto,) * len(mesh.axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_axes(jax.make_mesh(shape, axes))


@dataclass(frozen=True)
class MeshAxes:
    """Logical roles of the mesh axes for the sharding rules."""

    data: tuple[str, ...]       # axes carrying batch (DP / FSDP)
    model: str                  # axis carrying TP / EP
    pod: str | None = None

    @property
    def data_size_of(self):
        raise NotImplementedError

    def data_size(self, mesh) -> int:
        n = 1
        for a in self.data:
            n *= mesh.shape[a]
        return n

    def model_size(self, mesh) -> int:
        return mesh.shape[self.model]


def axes_for(mesh) -> MeshAxes:
    names = mesh.axis_names
    if "pod" in names:
        return MeshAxes(data=("pod", "data"), model="model", pod="pod")
    return MeshAxes(data=("data",), model="model")
