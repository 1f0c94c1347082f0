"""The plain reference's anchor sort and position range, without a chip.

The reference sorts anchors on the (t, q) pair, so its answers do not
depend on how many bits a packed key would leave for t: mapping against an
index whose every position is raised by a multiple of the vote table's
span (2^voting_window_log2 x vote_bins) raises each mapped position by as
much and leaves everything else alone, however far past 2^23 it goes.
"""
import json
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import reference, traffic  # noqa: E402

PARAMS = json.loads((DATA / "tiny_config.json").read_text())["params"]
N_READS = 16


@pytest.fixture(scope="module")
def mapped():
    """A few seeded reads of a test-sized genome, their reference index
    and their answers against it."""
    genome = traffic.make_genome(20000, traffic.rng_for(3, traffic.GENOME))
    signals = traffic.sample_reads(genome, N_READS, PARAMS["signal_len"],
                                   traffic.rng_for(3, traffic.POOL))[0]
    index = reference.build_index(genome.events_concat, genome.n_events,
                                  PARAMS)
    return signals, index, reference.map_reads(signals, index, PARAMS,
                                               block=N_READS)


@pytest.mark.parametrize("shift", [2 ** 23, 2 ** 24, 2 ** 30])
def test_answers_follow_a_shifted_index(mapped, shift):
    signals, index, base = mapped
    span = PARAMS["vote_bins"] << PARAMS["voting_window_log2"]
    assert shift % span == 0
    far = reference.map_reads(signals, dict(index, pos=index["pos"] + shift),
                              PARAMS, block=N_READS)
    assert base["mapped"].sum() >= N_READS // 2
    # a read with no anchor reports the empty slot's diagonal, unshifted
    has = base["n_sorted"] > 0
    want = base["t_start"].astype(np.int64) + np.where(has, shift, 0)
    np.testing.assert_array_equal(far["t_start"], want)
    for k in ("score", "mapped", "n_events") + reference.COUNTERS:
        np.testing.assert_array_equal(far[k], base[k], err_msg=k)


def _anchors(rng, keep_frac, t_max):
    E, H = PARAMS["max_events"], PARAMS["max_hits_per_seed"]
    t = rng.integers(0, t_max, size=(E, H), dtype=np.int64)
    # repeat some positions so that q decides among equal t
    t[::3] = t[::3, :1]
    q = rng.integers(0, 2 * E, size=(E, H), dtype=np.int64)
    keep = rng.random((E, H)) < keep_frac
    return t.astype(np.int32), q.astype(np.int32), keep


def _sorted(t, q, keep):
    import jax.numpy as jnp
    st, sq, sv = reference.sort_anchors(jnp.asarray(t), jnp.asarray(q),
                                        jnp.asarray(keep),
                                        PARAMS["max_anchors"])
    return np.asarray(st), np.asarray(sq), np.asarray(sv)


@pytest.mark.parametrize("keep_frac", [0.05, 0.5])
@pytest.mark.parametrize("seed", [0, 2 ** 31 + 5])
def test_sort_matches_lexsort(seed, keep_frac):
    """Up to 2^30, the kept anchors in numpy's (t, q) order, the first
    max_anchors of them, then empty slots with the DP's sentinels."""
    t, q, keep = _anchors(np.random.default_rng(seed), keep_frac, 2 ** 30)
    A, q_max = PARAMS["max_anchors"], (1 << reference.Q_BITS) - 1
    kt, kq = t[keep], np.minimum(q[keep], q_max)
    order = np.lexsort((kq, kt))[:A]
    n = order.shape[0]
    want_t = np.full(A, reference.INVALID_KEY >> reference.Q_BITS)
    want_q = np.full(A, q_max)
    want_t[:n], want_q[:n] = kt[order], kq[order]
    st, sq, sv = _sorted(t, q, keep)
    np.testing.assert_array_equal(st, want_t)
    np.testing.assert_array_equal(sq, want_q)
    np.testing.assert_array_equal(sv, np.arange(A) < n)


@pytest.mark.parametrize("keep_frac", [0.05, 0.5])
def test_sort_matches_packed_key_below_2_23(keep_frac):
    """Below 2^23 the pair sort decodes to what the one-int32 key
    [t : 23 bits | q : 8 bits] gave, sentinels and all."""
    t, q, keep = _anchors(np.random.default_rng(7), keep_frac, 2 ** 23 - 1)
    A, q_max = PARAMS["max_anchors"], (1 << reference.Q_BITS) - 1
    key = np.where(keep, (t.astype(np.int64) << reference.Q_BITS)
                   | np.minimum(q, q_max), reference.INVALID_KEY)
    skey = np.sort(key.reshape(-1))[:A]
    st, sq, sv = _sorted(t, q, keep)
    np.testing.assert_array_equal(st, skey >> reference.Q_BITS)
    np.testing.assert_array_equal(sq, skey & q_max)
    np.testing.assert_array_equal(sv, skey != reference.INVALID_KEY)


@pytest.mark.parametrize("n_events", [reference.MAX_CONCAT_EVENTS + 1,
                                      2 ** 31, 2 ** 32])
def test_build_index_refuses_positions_past_int32(n_events):
    """The length is checked before any work: a zero-stride array stands
    in for a double genome of that many events."""
    huge = np.lib.stride_tricks.as_strided(np.zeros(1, np.float32),
                                           shape=(n_events,), strides=(0,))
    with pytest.raises(ValueError, match="int32"):
        reference.build_index(huge, n_events // 2, PARAMS)
