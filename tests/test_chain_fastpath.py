"""Chaining fast path parity: the filter-aware sort/dp/compaction
optimizations must be bit-identical to the seed implementations.

Three layers, mirroring the fast path's structure:

  (a) select-then-sort (count- and topk-selection) vs the full anchor sort;
  (b) ring-buffer ``chain_dp`` (and the Pallas kernel) vs the dynamic-slice
      ``chain_dp_reference`` across band/anchor-count edge cases;
  (c) compacted ``map_chunk`` / ``map_chunk_sharded`` vs the uncompacted
      chunk program on chunks with 0% / ~50% / 100% vote-filter survival.
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MarsConfig, build_index, chaining, index,
                        map_chunk, stages)
from repro.core.index import index_arrays
from repro.signal import simulate

REPO = pathlib.Path(__file__).resolve().parents[1]


def _anchor_grid(rng, E, H, n_valid, t_range=20_000):
    q = np.tile(np.arange(E, dtype=np.int32)[:, None], (1, H))
    t = rng.integers(0, t_range, (E, H)).astype(np.int32)
    v = np.zeros((E, H), bool)
    flat = rng.choice(E * H, size=n_valid, replace=False)
    v.reshape(-1)[flat] = True
    return jnp.asarray(q), jnp.asarray(t), jnp.asarray(v)


# --------------------------------------------------------------------------- #
# (a) select-then-sort vs full sort
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n_valid", [0, 1, 40, 64, 100])
@pytest.mark.parametrize("width", [64, 128])
def test_select_then_sort_matches_full_sort_prefix(n_valid, width):
    """When the surviving anchor count fits the width, select-then-sort
    equals the full sort's first ``width`` slots — for both strategies."""
    rng = np.random.default_rng(n_valid * 1000 + width)
    cfg = MarsConfig()
    q, t, v = _anchor_grid(rng, cfg.max_events, cfg.max_hits_per_seed,
                           n_valid, t_range=60)   # many equal t: q decides
    h = cfg.max_hits_per_seed
    pairs = chaining.anchor_pairs(q, t, v)
    full = [x[:width] for x in chaining.sort_pairs(*pairs)]
    count_sel = chaining.sort_pairs(
        *chaining.select_smallest_count(pairs[0], width, h))
    topk_sel = chaining.sort_pairs(
        *chaining.select_smallest_topk(pairs[0], width, h))
    for a, b, c in zip(full, count_sel, topk_sel):
        if n_valid <= width:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # topk selection is exact for ANY count
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_sort_anchors_width_matches_reference():
    rng = np.random.default_rng(7)
    cfg = MarsConfig()
    q, t, v = _anchor_grid(rng, cfg.max_events, cfg.max_hits_per_seed, 50)
    ref = chaining.sort_anchors_reference(q, t, v, cfg)
    for select in ("count", "topk"):
        got = chaining.sort_anchors(q, t, v, cfg.replace(anchor_select=select),
                                    width=64)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a)[:64], np.asarray(b))


def test_packing_fields_round_trip():
    """The sort pairs keep t whole: positions up to the index guard's
    largest (``MAX_CONCAT_EVENTS`` - 1, far past 2^23) come back as they
    went in, q clamped to 8 bits, and every one sorts before an empty slot."""
    t_max = index.MAX_CONCAT_EVENTS - 1
    t = jnp.asarray([[t_max, 0, (1 << 23) + 5, 1 << 30]], jnp.int32)
    q = jnp.asarray([[5, 255, 300, 7]], jnp.int32)
    v = jnp.asarray([[True, True, True, False]])
    st, sq = chaining.sort_pairs(*chaining.anchor_pairs(q, t, v))
    sq, st, sv = chaining.decode_pairs(st, sq)
    np.testing.assert_array_equal(np.asarray(st),
                                  [0, (1 << 23) + 5, t_max, chaining.EMPTY_T])
    np.testing.assert_array_equal(np.asarray(sq), [255, 255, 5,
                                                   chaining.EMPTY_Q])
    np.testing.assert_array_equal(np.asarray(sv), [True, True, True, False])


def test_index_build_rejects_key_overflow():
    """``build_index`` refuses a double genome past 2^31 - 2^20 events
    (int32 positions and vote diagonals) before touching its events, and
    the guard admits every length up to that, 2^23 and 2^24 among them.
    Reads of more than 256 events are refused: the sort pair clamps q to
    8 bits."""
    cfg = MarsConfig()
    too_big = np.broadcast_to(np.float32(0), (index.MAX_CONCAT_EVENTS + 1,))
    with pytest.raises(ValueError, match="2\\^31 - 2\\^20"):
        build_index(too_big, too_big.shape[0] // 2, cfg)
    with pytest.raises(ValueError, match="2\\^31 - 2\\^20"):
        index.build_index_streaming(too_big, too_big.shape[0] // 2, cfg, 4)
    for n in (1 << 23, 1 << 24, index.MAX_CONCAT_EVENTS):
        index.check_index_limits(n, cfg)
    index.check_index_limits(64, cfg.replace(max_events=chaining.Q_MAX + 1))
    for build in (build_index,
                  lambda *a: index.build_index_streaming(*a, 4)):
        with pytest.raises(ValueError, match="q_pos"):
            build(np.zeros(64, np.float32), 32, cfg.replace(max_events=512))


# --------------------------------------------------------------------------- #
# (b) ring-buffer DP vs dynamic-slice reference
# --------------------------------------------------------------------------- #
def _sorted_anchors(rng, A, p_valid=0.8, t_range=4000, dup_every=0):
    t = np.sort(rng.integers(0, t_range, size=A)).astype(np.int32)
    q = rng.integers(0, 180, size=A).astype(np.int32)
    order = np.lexsort((q, t))
    t, q = t[order], q[order]
    if dup_every:
        for i in range(dup_every, A, dup_every):
            t[i], q[i] = t[i - 1], q[i - 1]     # exact duplicates: argmax ties
    v = rng.random(A) < p_valid
    return jnp.asarray(q), jnp.asarray(t), jnp.asarray(v)


# band/anchor-count edge cases: B > A, A == B (exactly one band), A not a
# multiple of B, A a multiple, band 1, wide band
@pytest.mark.parametrize("A,B", [(8, 32), (32, 32), (100, 32), (512, 32),
                                 (64, 1), (48, 16), (96, 64)])
def test_ring_dp_matches_reference(A, B):
    cfg = MarsConfig(max_anchors=A, chain_band=B)
    rng = np.random.default_rng(A * 100 + B)
    q, t, v = _sorted_anchors(rng, A, dup_every=7)
    f_r, d_r = chaining.chain_dp_reference(q, t, v, cfg)
    f_n, d_n = chaining.chain_dp(q, t, v, cfg)
    np.testing.assert_array_equal(np.asarray(f_r), np.asarray(f_n))
    np.testing.assert_array_equal(np.asarray(d_r), np.asarray(d_n))


def test_ring_dp_all_invalid_is_empty_result():
    cfg = MarsConfig(max_anchors=64, chain_band=16)
    sq, st, sv = chaining.decode_pairs(
        jnp.full((64,), chaining.INVALID_T, jnp.int32),
        jnp.full((64,), chaining.Q_MAX, jnp.int32))
    f_r, d_r = chaining.chain_dp_reference(sq, st, sv, cfg)
    f_n, d_n = chaining.chain_dp(sq, st, sv, cfg)
    np.testing.assert_array_equal(np.asarray(f_r), np.asarray(f_n))
    np.testing.assert_array_equal(np.asarray(d_r), np.asarray(d_n))
    res = chaining.best_chain(f_n, d_n, sv, cfg)
    empty = chaining.empty_chain_result(cfg)
    for a, b in zip(res, empty):
        assert np.asarray(a) == np.asarray(b), (res, empty)


def test_ring_dp_vmapped_batch():
    cfg = MarsConfig(max_anchors=128, chain_band=32)
    rng = np.random.default_rng(3)
    qs, ts, vs = zip(*[_sorted_anchors(rng, 128, dup_every=5)
                       for _ in range(6)])
    q, t, v = jnp.stack(qs), jnp.stack(ts), jnp.stack(vs)
    ref = jax.vmap(lambda a, b, c: chaining.chain_dp_reference(a, b, c, cfg))
    new = jax.vmap(lambda a, b, c: chaining.chain_dp(a, b, c, cfg))
    for x, y in zip(ref(q, t, v), new(q, t, v)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# --------------------------------------------------------------------------- #
# (c) compacted vs uncompacted map_chunk
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def chunk_setup():
    cfg = MarsConfig(hash_bits=12).with_mode("ms_fixed")
    ref = simulate.make_reference(6_000, seed=9)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    return cfg, ref, arrays


def _signals(ref, cfg, junk_frac, seed=21, n=8):
    reads = simulate.sample_reads(ref, n, signal_len=cfg.signal_len,
                                  seed=seed, junk_frac=junk_frac)
    return jnp.asarray(reads.signals)


def _assert_identical(a, b):
    np.testing.assert_array_equal(np.asarray(a.t_start), np.asarray(b.t_start))
    np.testing.assert_array_equal(np.asarray(a.score), np.asarray(b.score))
    np.testing.assert_array_equal(np.asarray(a.mapped), np.asarray(b.mapped))
    np.testing.assert_array_equal(np.asarray(a.n_events),
                                  np.asarray(b.n_events))
    ca = {k: int(v) for k, v in a.counters.items()}
    cb = {k: int(v) for k, v in b.counters.items()}
    assert set(ca) == set(stages.CHUNK_COUNTER_SCHEMA)
    assert ca == cb


# survival fractions: 1.0 junk -> ~0% of reads keep anchors post-vote,
# 0.5 -> ~half, 0.0 -> ~all
@pytest.mark.parametrize("junk_frac", [1.0, 0.5, 0.0])
@pytest.mark.parametrize("use_kernels", [False, True])
def test_compacted_chunk_matches_uncompacted(chunk_setup, junk_frac,
                                             use_kernels):
    cfg, ref, arrays = chunk_setup
    sig = _signals(ref, cfg, junk_frac)
    base = map_chunk(sig, arrays, cfg.replace(chain_compaction=False),
                     use_kernels=use_kernels)
    fast = map_chunk(sig, arrays, cfg, use_kernels=use_kernels)
    _assert_identical(base, fast)
    # sanity: the survival mix matches the scenario
    n_anchors = int(base.counters["n_anchors_postvote"])
    if junk_frac == 1.0:
        assert n_anchors == 0
    else:
        assert n_anchors > 0


@pytest.mark.parametrize("kw", [dict(anchor_select="topk"),
                                dict(chain_widths=()),
                                dict(chain_widths=(16, 64, 128, 256)),
                                dict(chain_capacity_frac=0.25),
                                dict(chain_capacity_frac=1.0)])
def test_fastpath_config_variants_are_identical(chunk_setup, kw):
    """Every selection strategy / ladder shape / capacity bound must be
    invisible in the outputs (only the runtime branch taken changes)."""
    cfg, ref, arrays = chunk_setup
    sig = _signals(ref, cfg, junk_frac=0.5)
    base = map_chunk(sig, arrays, cfg.replace(chain_compaction=False))
    fast = map_chunk(sig, arrays, cfg.replace(**kw))
    _assert_identical(base, fast)


def test_compacted_chunk_with_pad_rows(chunk_setup):
    cfg, ref, arrays = chunk_setup
    sig = _signals(ref, cfg, junk_frac=0.5)
    base = map_chunk(sig, arrays, cfg.replace(chain_compaction=False),
                     n_valid=5)
    fast = map_chunk(sig, arrays, cfg, n_valid=5)
    _assert_identical(base, fast)
    assert not np.asarray(fast.mapped)[5:].any()


SHARD_SCRIPT = """
import numpy as np, jax.numpy as jnp
from repro.core import MarsConfig, build_index, map_chunk, map_chunk_sharded
from repro.core.index import index_arrays
from repro.launch.mesh import make_mesh
from repro.signal import simulate

mesh = make_mesh((2, 2), ("pod", "data"))
cfg = MarsConfig(hash_bits=12).with_mode("ms_fixed")
ref = simulate.make_reference(6_000, seed=9)
reads = simulate.sample_reads(ref, 8, signal_len=cfg.signal_len, seed=21,
                              junk_frac=0.5)
idx = build_index(ref.events_concat, ref.n_events, cfg)
arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
sig = jnp.asarray(reads.signals)
base = map_chunk(sig, arrays, cfg.replace(chain_compaction=False))
for n_valid in (None, 5):
    b = map_chunk_sharded(sig, arrays, cfg, mesh, n_valid=n_valid)
    if n_valid is None:
        assert np.array_equal(np.asarray(base.t_start), np.asarray(b.t_start))
        assert np.array_equal(np.asarray(base.score), np.asarray(b.score))
        assert np.array_equal(np.asarray(base.mapped), np.asarray(b.mapped))
    a = map_chunk(sig, arrays, cfg, n_valid=n_valid)
    assert np.array_equal(np.asarray(a.t_start), np.asarray(b.t_start))
    assert np.array_equal(np.asarray(a.score), np.asarray(b.score))
    assert np.array_equal(np.asarray(a.mapped), np.asarray(b.mapped))
    ca = {k: int(v) for k, v in a.counters.items()}
    cb = {k: int(v) for k, v in b.counters.items()}
    assert ca == cb, (n_valid, ca, cb)
print("ok")
"""


def test_sharded_compacted_chunk_matches(chunk_setup):
    """Sharded + compacted == single-device + compacted == uncompacted,
    even when shards take different capacity/width branches locally."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok" in r.stdout
