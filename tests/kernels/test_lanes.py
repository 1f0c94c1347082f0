"""Lane helpers (kernels/lanes.py) inside a Pallas kernel vs numpy."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.kernels import lanes

L = 256


def _seg_cumsum(x, seg):
    out = x.copy()
    for s in range(0, x.shape[-1], seg):
        out[..., s:s + seg] = np.cumsum(x[..., s:s + seg], axis=-1)
    return out


CASES = {
    "roll_left": (lambda x: lanes.roll_left(x, 5),
                  lambda x: np.roll(x, -5, axis=-1)),
    "roll_right": (lambda x: lanes.roll_right(x, 7),
                   lambda x: np.roll(x, 7, axis=-1)),
    "shift_left": (lambda x: lanes.shift_left(x, 3, -1),
                   lambda x: np.concatenate(
                       [x[:, 3:], np.full((x.shape[0], 3), -1)], axis=1)),
    "shift_right": (lambda x: lanes.shift_right(x, 130, -1),
                    lambda x: np.concatenate(
                        [np.full((x.shape[0], 130), -1), x[:, :-130]],
                        axis=1)),
    "prefix_sum": (lambda x: lanes.prefix_sum(x),
                   lambda x: np.cumsum(x, axis=-1)),
    "prefix_sum_seg16": (lambda x: lanes.prefix_sum(x, seg=16),
                         lambda x: _seg_cumsum(x, 16)),
    "prefix_sum_seg3": (lambda x: lanes.prefix_sum(x[:, :255], seg=3),
                        lambda x: _seg_cumsum(x[:, :255], 3)),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_lane_helper_matches_numpy(name):
    fn, want = CASES[name]
    x = np.random.default_rng(len(name)).integers(
        -50, 50, size=(8, L)).astype(np.int32)
    out_shape = jax.eval_shape(fn, jax.ShapeDtypeStruct(x.shape, x.dtype))

    def kernel(x_ref, o_ref):
        o_ref[...] = fn(x_ref[...])

    got = pl.pallas_call(kernel, out_shape=out_shape, interpret=True)(
        jnp.asarray(x))
    np.testing.assert_array_equal(np.asarray(got), want(x))


@pytest.mark.parametrize("rows,block", [(1, 1), (8, 8), (13, 8)])
def test_row_block_and_pad(rows, block):
    assert lanes.row_block(rows) == block
    x = jnp.ones((rows, 4), jnp.int32)
    padded = lanes.pad_rows(x, block)
    assert padded.shape[0] % block == 0 and padded.shape[0] - rows < block
    np.testing.assert_array_equal(np.asarray(padded[rows:]), 0)
