"""Production mesh construction.

The mesh mirrors the paper's cluster architecture (§3.1): the ``model`` axis
is the intra-pod electrical domain (TP/EP traffic confined in-pod), the
``data`` axis spans a pod's DP groups, and the ``pod`` axis crosses the OCS
optical core — exactly the traffic Cross Wiring engineers.

Every axis is ``AxisType.Auto``: the train step's GSPMD specs and the
``shard_map_dp`` manual axes are written for Auto axes, and ``jax.make_mesh``
defaults to Explicit axes, under which a matmul over a sharded contracting
dimension is a type error.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType

PodMesh = Tuple[int, int]


def make_mesh(
    shape: Tuple[int, ...],
    axes: Tuple[str, ...],
    devices: Optional[Sequence] = None,
):
    """Mesh of ``shape`` with Auto axes, over ``devices`` (default: all)."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) single-pod or (2, 16, 16) two-pod production mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None):
    """(data, model) mesh over whatever devices exist."""
    n = len(jax.devices())
    model = model or 1
    return make_mesh((n // model, model), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def dp_axes(mesh) -> Tuple[str, ...]:
    """Axes that carry data parallelism (pod × data when multi-pod)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
