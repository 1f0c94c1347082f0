"""Lane-axis building blocks shared by the Pallas kernels.

Every kernel keeps a read's samples, events or anchors on the lane (last)
axis of a ``(rows, L)`` block.  Shifts, rotations and scans along that axis
are lane rotations (``pltpu.roll``, one XLU op in Mosaic) plus an iota mask
for the positions that fall off the edge — no unaligned slices or
concatenations, which Mosaic cannot always lay out.  In interpret mode
``pltpu.roll`` is ``jnp.roll``.

Rows are independent reads.  A block is ``SUBLANES`` rows (one sublane
tile), or all rows when there are fewer: the two block heights Mosaic
accepts for an ``(R, L)`` array.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8


def row_block(n_rows: int) -> int:
    """Rows per kernel program for an ``(n_rows, L)`` operand."""
    return min(n_rows, SUBLANES)


def pad_rows(x: jnp.ndarray, rb: int) -> jnp.ndarray:
    """Zero-pad axis 0 up to a multiple of ``rb`` (pad rows are computed
    and sliced off by the caller)."""
    rem = -x.shape[0] % rb
    if rem == 0:
        return x
    return jnp.pad(x, [(0, rem)] + [(0, 0)] * (x.ndim - 1))


def lane_iota(x: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)


def roll_left(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Circular ``x[..., (i + d) % L]`` (static d)."""
    n = x.shape[-1]
    d %= n
    return x if d == 0 else pltpu.roll(x, n - d, x.ndim - 1)


def roll_right(x: jnp.ndarray, d: int) -> jnp.ndarray:
    """Circular ``x[..., (i - d) % L]`` (static d)."""
    d %= x.shape[-1]
    return x if d == 0 else pltpu.roll(x, d, x.ndim - 1)


def shift_left(x: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """``x[..., i + d]``, with ``fill`` past the right edge (static d)."""
    if d == 0:
        return x
    return jnp.where(lane_iota(x) < x.shape[-1] - d, roll_left(x, d), fill)


def shift_right(x: jnp.ndarray, d: int, fill) -> jnp.ndarray:
    """``x[..., i - d]``, with ``fill`` before the left edge (static d)."""
    if d == 0:
        return x
    return jnp.where(lane_iota(x) >= d, roll_right(x, d), fill)


def prefix_sum(x: jnp.ndarray, seg: int = 0) -> jnp.ndarray:
    """Inclusive Hillis-Steele prefix sum along lanes (integer x).

    ``seg > 0`` restarts the sum every ``seg`` lanes (segments aligned at
    multiples of ``seg``): a step adds the value ``d`` lanes back only when
    it lies in the same segment."""
    span = seg or x.shape[-1]
    pos = lane_iota(x) % span if seg else None
    d = 1
    while d < span:
        back = shift_right(x, d, 0)
        x = x + (back if pos is None else jnp.where(pos >= d, back, 0))
        d *= 2
    return x
