"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The single-pod mesh is 16x16 = 256 chips
('data', 'model'); the multi-pod mesh adds a leading 'pod' axis
(2 x 16 x 16 = 512 chips).  `pod` x `data` together form the DP/FSDP
domain; `model` carries TP / EP / MARS index partitions.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto: sharding follows the
    arguments' NamedShardings and ``with_sharding_constraint``, as the
    sharding rules in ``distributed/sharding.py`` expect.  (Without
    ``axis_types``, JAX >= 0.9 makes Explicit axes.)"""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, layout: str = "2d"):
    """layout='2d' (default): ('data','model') TP+FSDP.  layout='fsdp':
    pure data/FSDP parallelism — the 'model' axis is renamed 'data2' so the
    sharding rules treat every axis as a DP/FSDP axis (dense-model
    hillclimb variant, EXPERIMENTS.md §Perf)."""
    if layout == "fsdp":
        shape = (2, 16, 16) if multi_pod else (16, 16)
        axes = (("pod", "data", "data2") if multi_pod
                else ("data", "data2"))
        return _auto_mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh for tests / elastic restarts (e.g. (4,2) on 8 CPU
    devices)."""
    return _auto_mesh(shape, axes)


def dp_axes(mesh) -> Tuple[str, ...]:
    """Data-parallel (FSDP) axes of a mesh: everything except 'model'."""
    return tuple(a for a in mesh.axis_names if a != "model")


def tp_axis(mesh) -> Optional[str]:
    return "model" if "model" in mesh.axis_names else None


def axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
