"""Mesh construction for the sharded chunk program.

A function, not a module-level constant, so importing this module never
touches jax device state.  Reads shard over every mesh axis; a
partitioned index lives on the 'model' axis (``core/index.INDEX_AXIS``).
"""
from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """``jax.make_mesh`` with every axis Auto (e.g. (2, 2) as ('data',
    'model') on four devices): sharding follows the arguments'
    NamedShardings, as ``core/distributed.py``'s layouts expect.  (Without
    ``axis_types``, JAX >= 0.9 makes Explicit axes.)"""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
