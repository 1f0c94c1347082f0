"""Cheap-phase fast path parity: packed-entry gathers, prefix-sum event
reduction and batch-level detect/query/vote must be bit-identical to the
seed implementations.

Mirrors the fast path's structure (and tests/test_chain_fastpath.py):

  (a) event reduction: one-sort ``robust_normalize`` vs the two-median
      reference, its rank selection vs a sorted union, its lowered
      program (no loop, no gather); cumsum-at-boundary ``segment_means`` vs the segment-sum
      reference; full ``detect_events`` vs ``detect_events_reference`` —
      swept over the fixed-point x early-quant x float mode grid;
  (b) the int32 overflow guard of the integer boundary test (satellite:
      ``diff * diff * w`` wraps beyond tstat_window=12 at frac_bits=8);
  (c) packed-entry query (two fused gathers) vs the unpacked four-gather
      ``query_index_reference``, per-read and whole-chunk batched;
  (d) the fused batch vote filter vs the per-read reference, plus the
      diag clip guard + ``n_votes_clipped`` debug counter;
  (e) the batched cheap phase vs the per-read vmap of the stage bodies,
      for reference AND pallas plans, and whole-chunk ``map_chunk`` across
      backends (the sharded + ring/a2a parity of the same program runs in
      tests/test_distributed_stages.py under a multi-device mesh).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (MarsConfig, build_index, map_chunk, seeding, stages,
                        vote)
from repro.core import events, pipeline
from repro.core.index import index_arrays, index_arrays_unpacked
from repro.signal import simulate

MODES = ("ms_fixed", "ms_float", "rh2")


@pytest.fixture(scope="module", params=MODES)
def mode_setup(request):
    cfg = MarsConfig(hash_bits=12).with_mode(request.param)
    ref = simulate.make_reference(6_000, seed=9)
    reads = simulate.sample_reads(ref, 6, signal_len=cfg.signal_len,
                                  seed=10, junk_frac=0.3)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    return cfg, jnp.asarray(reads.signals), idx


# --------------------------------------------------------------------------- #
# (a) prefix-sum event reduction vs reference oracles
# --------------------------------------------------------------------------- #
def _kth_cases():
    """(run lengths, fill, ranks): every rank at the small lengths, the
    ends and the middle at the long ones."""
    for na, nb in [(0, 1), (1, 1), (1, 2), (511, 513), (512, 512)]:
        n = na + nb
        ks = (range(n) if n <= 3 else
              sorted({0, 1, n // 2 - 1, n // 2, n - 2, n - 1}))
        for fill in ("normal", "rounded", "equal"):
            yield pytest.param(na, nb, fill, tuple(ks),
                               id=f"{na}-{nb}-{fill}")


@pytest.mark.parametrize("na,nb,fill,ks", list(_kth_cases()))
def test_kth_of_two_sorted_matches_sorted_union(na, nb, fill, ks):
    """The MAD's rank selection == the k-th entry of the sorted union,
    with ties inside and across the two runs."""
    rng = np.random.default_rng(1000 * na + nb)
    a, b = (rng.normal(0, 3, n).astype(np.float32) for n in (na, nb))
    if fill == "rounded":
        a, b = np.round(a), np.round(b)
    elif fill == "equal":
        a, b = np.full(na, 2.0, np.float32), np.full(nb, 2.0, np.float32)
    a, b = np.sort(a), np.sort(b)
    union = np.sort(np.concatenate([a, b]))
    for k in ks:
        got = events._kth_of_two_sorted(jnp.asarray(a), jnp.asarray(b), k)
        assert np.asarray(got) == union[k], (k, got, union[k])


def _normalize_case(S, kind, rng):
    """Rows for ``test_robust_normalize_matches_reference``: normal
    samples; a constant signal (MAD 0, scale _EPS); or one where over half
    the samples equal the median (MAD 0 with distinct values around)."""
    x = np.round(rng.normal(100, 25, (3, S))).astype(np.float32)
    if kind == "constant":
        x[:] = 97.0
    elif kind == "median_ties":
        x[:, rng.permutation(S)[:S // 2 + 1]] = 101.0
    return x


@pytest.mark.parametrize("S,kind", [
    *(pytest.param(S, "random", id=str(S))
      for S in (7, 8, 255, 256, 1024, 1, 2, 3)),
    *(pytest.param(S, kind, id=f"{S}-{kind}")
      for S in (1, 2, 3, 8, 1024) for kind in ("constant", "median_ties"))])
def test_robust_normalize_matches_reference(S, kind):
    """One shared sort + rank-selected MAD == two jnp.median sorts, bitwise
    (odd/even lengths, heavy ties, MAD 0), eager against eager and jit
    against jit (the two differ in the division's last ulp)."""
    rng = np.random.default_rng(S)
    for trial in range(4):
        if kind == "random":
            x = rng.normal(100, 25, (3, S)).astype(np.float32)
            if trial % 2:
                x = np.round(x)                # ties across the two runs
        else:
            x = _normalize_case(S, kind, rng)
        x = jnp.asarray(x)
        for run in (lambda f: f, jax.jit):
            got = run(events.robust_normalize)(x)
            want = run(events.robust_normalize_reference)(x)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_robust_normalize_lowers_without_loops_or_gathers():
    """The MAD's selection is static slices, not a batched binary search:
    the chunk-sized program holds one sort and no while loop or gather."""
    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    text = jax.jit(events.robust_normalize).lower(x).as_text()
    assert "stablehlo.sort" in text
    assert "while" not in text
    assert "gather" not in text


def test_segment_means_matches_reference_fixed():
    """Cumsum-at-boundary gathers == segment-sum scatters on the integer
    (fixed-point) path, including valid_len masking and the E-1 overflow
    clip."""
    rng = np.random.default_rng(1)
    S, E = 512, 48
    for valid_len, p in [(S, 0.05), (S // 3, 0.05), (S, 0.6), (17, 0.3)]:
        x = rng.integers(-2048, 2048, S).astype(np.int32)
        b = rng.random(S) < p
        got = events.segment_means(jnp.asarray(x), jnp.asarray(b),
                                   valid_len, E, max_abs=2048)
        want = events.segment_means_reference(jnp.asarray(x), jnp.asarray(b),
                                              valid_len, E)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_segment_means_guards_prefix_sum_exactness():
    """The f32 prefix sum is only exact below 2^24: an uncertified bound or
    S * max_abs beyond it must fall back to the scatter reference (whose
    jaxpr carries a scatter-add; the fast path is gather-only)."""
    import jax
    S, E = 1 << 14, 48                      # 2^14 * 2048 = 2^25 > 2^24
    args = (jnp.ones(S, jnp.int32), jnp.zeros(S, bool), S, E)

    def has_scatter(max_abs):
        jaxpr = jax.make_jaxpr(
            lambda x, b: events.segment_means(x, b, S, E, max_abs=max_abs)
        )(args[0], args[1])
        return "scatter" in str(jaxpr)

    assert has_scatter(None)                # uncertified bound
    assert has_scatter(2048)                # bound certified but too large
    S2 = 1024
    jaxpr = jax.make_jaxpr(
        lambda x, b: events.segment_means(x, b, S2, E, max_abs=2048)
    )(jnp.ones(S2, jnp.int32), jnp.zeros(S2, bool))
    assert "scatter" not in str(jaxpr)      # in-range -> gather fast path


def test_detect_events_matches_reference(mode_setup):
    """Full detect (normalize + boundary + reduce) vs the pre-fast-path
    reference, per mode.  Float modes keep the scatter-based reduction, so
    equality is bitwise there too."""
    cfg, signals, _ = mode_setup
    got = jax.vmap(lambda s: events.detect_events(s, cfg))(signals)
    want = jax.vmap(lambda s: events.detect_events_reference(s, cfg))(signals)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --------------------------------------------------------------------------- #
# (b) integer boundary test: int32 overflow guard
# --------------------------------------------------------------------------- #
def test_boundary_mask_fixed_safe_at_bound_matches_int64_oracle():
    """tstat_window=12 is the largest safe window at frac_bits=8: the
    adversarial max-amplitude step signal stays below 2^31 and the int32
    mask equals an unbounded int64 numpy evaluation."""
    cfg = MarsConfig(signal_len=256, tstat_window=12).with_mode("ms_fixed")
    assert events.fixed_tstat_in_range(cfg)
    S, w = 256, cfg.tstat_window
    xq = np.full(S, -2048, np.int16)
    xq[S // 2:] = 2047                        # extreme step at the midpoint
    got = events.boundary_mask_fixed(jnp.asarray(xq), cfg)

    # unbounded int64 oracle of the same integer test + peak pick
    x = xq.astype(np.int64)
    c = np.concatenate([[0], np.cumsum(x)])
    c2 = np.concatenate([[0], np.cumsum(x * x)])
    i = np.arange(S)
    lo, hi = np.maximum(i - w, 0), np.minimum(i + w, S)
    sum_l, sum_r = c[i] - c[lo], c[hi] - c[i]
    sq_l, sq_r = c2[i] - c2[lo], c2[hi] - c2[i]
    diff = (sum_r - sum_l) >> 2
    ssd = (w * sq_l - sum_l**2) + (w * sq_r - sum_r**2)
    tau2 = int(round(cfg.tstat_threshold ** 2))
    eps = 1 << (2 * cfg.frac_bits - 8)
    lhs = diff * diff * w
    rhs = tau2 * ((ssd >> 4) + eps)
    assert lhs.max() >= (1 << 30), "signal must stress the bound"
    above = lhs > rhs
    score = lhs.astype(np.float32) / (rhs.astype(np.float32) + 1.0)
    want = np.asarray(events._peak_pick(jnp.asarray(score),
                                        jnp.asarray(above), cfg))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_boundary_mask_fixed_rejects_overflowing_window():
    """One past the bound: diff^2 * w exceeds int31 in the worst case and
    the guard fails statically instead of wrapping."""
    cfg = MarsConfig(signal_len=256, tstat_window=13).with_mode("ms_fixed")
    assert not events.fixed_tstat_in_range(cfg)
    assert events.fixed_tstat_bounds(cfg)["lhs"] >= (1 << 31)
    with pytest.raises(ValueError, match="tstat_window"):
        events.boundary_mask_fixed(jnp.zeros(256, jnp.int16), cfg)
    # the Pallas detect backend refuses the same configs, so plans fall
    # back instead of running the kernel's identical int32 expressions
    plan = dict(stages.resolve_plan(cfg, stages.PALLAS))
    assert plan["detect"] == stages.REFERENCE


# --------------------------------------------------------------------------- #
# (c) packed-entry query vs the unpacked four-gather oracle
# --------------------------------------------------------------------------- #
def test_query_packed_matches_unpacked(mode_setup):
    cfg, _, idx = mode_setup
    packed = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    unpacked = {k: jnp.asarray(v)
                for k, v in index_arrays_unpacked(idx).items()}
    rng = np.random.default_rng(2)
    E = cfg.max_events
    hit_keys = rng.choice(idx.entries_key, (3, E)).astype(np.uint32)
    miss_keys = rng.integers(0, 1 << 32, (1, E)).astype(np.uint32)
    keys = jnp.asarray(np.concatenate([hit_keys, miss_keys]))
    valid = jnp.asarray(rng.random(keys.shape) < 0.8)
    # batched (R, E) call
    tp1, hv1, c1 = seeding.query_index(keys, valid, packed, cfg)
    tp0, hv0, c0 = seeding.query_index_reference(keys, valid, unpacked, cfg)
    np.testing.assert_array_equal(np.asarray(hv0), np.asarray(hv1))
    np.testing.assert_array_equal(np.asarray(tp0), np.asarray(tp1))
    for k in c0:
        np.testing.assert_array_equal(np.asarray(c0[k]), np.asarray(c1[k]))
    # per-read calls agree with the batched rows
    for r in range(keys.shape[0]):
        tpr, hvr, cr = seeding.query_index(keys[r], valid[r], packed, cfg)
        np.testing.assert_array_equal(np.asarray(hvr), np.asarray(hv1[r]))
        for k in cr:
            assert int(cr[k]) == int(np.asarray(c1[k])[r]), k


def test_packed_plane_count_overflow_guard():
    """A count that does not fit the bucket-implied spare bits must fail at
    build/pack time, not corrupt a neighbour's key distinguisher."""
    from repro.core.index import pack_entries
    cfg = MarsConfig(hash_bits=12)
    keys = np.asarray([0x12345678], np.uint32)
    pos = np.asarray([7], np.int32)
    ok = pack_entries(keys, pos, np.asarray([cfg.n_buckets - 1], np.int64),
                      cfg)
    assert ok.shape == (2, 1)
    with pytest.raises(ValueError, match="spare bits"):
        pack_entries(keys, pos, np.asarray([cfg.n_buckets], np.int64), cfg)


# --------------------------------------------------------------------------- #
# (d) fused batch vote + clip guard
# --------------------------------------------------------------------------- #
def test_vote_filter_batch_matches_reference():
    cfg = MarsConfig(thresh_voting=3)
    rng = np.random.default_rng(3)
    R, E, H = 5, 64, 8
    q = np.tile(np.arange(E)[None, :, None], (R, 1, H)).astype(np.int32)
    t = rng.integers(0, 1 << 20, (R, E, H)).astype(np.int32)
    t[0, :, 0] = 5000 + q[0, :, 0]             # one colinear cluster
    v = rng.random((R, E, H)) < 0.4
    keep_b, c_b = vote.vote_filter(jnp.asarray(q), jnp.asarray(t),
                                   jnp.asarray(v), cfg)
    for r in range(R):
        keep_r, c_r = vote.vote_filter_reference(
            jnp.asarray(q[r]), jnp.asarray(t[r]), jnp.asarray(v[r]), cfg)
        np.testing.assert_array_equal(np.asarray(keep_b)[r],
                                      np.asarray(keep_r))
        for k in c_r:
            assert int(np.asarray(c_b[k])[r]) == int(c_r[k]), (r, k)
    assert "n_votes_clipped" in c_b
    assert "n_votes_clipped" not in stages.CHUNK_COUNTER_SCHEMA
    assert int(np.asarray(c_b["n_votes_clipped"]).sum()) == 0


def test_vote_filter_clips_underflowing_diag():
    """A diag below -2^20 must clip into bin 0 (counted), not wrap through
    the arithmetic shift into an arbitrary window."""
    cfg = MarsConfig(thresh_voting=1)
    E, H = 8, 2
    q = np.full((E, H), 1 << 21, np.int32)     # diag = -2^21 << -DIAG_SHIFT
    t = np.zeros((E, H), np.int32)
    v = np.ones((E, H), bool)
    keep, c = vote.vote_filter(jnp.asarray(q), jnp.asarray(t),
                               jnp.asarray(v), cfg)
    assert int(c["n_votes_clipped"]) == E * H
    # all clipped anchors land in the same (zero) window -> all survive at
    # thresh 1 and the vote tally is consistent
    assert np.asarray(keep).all()
    # in-range diags do not clip
    _, c2 = vote.vote_filter(jnp.asarray(np.zeros((E, H), np.int32)),
                             jnp.asarray(t), jnp.asarray(v), cfg)
    assert int(c2["n_votes_clipped"]) == 0


# --------------------------------------------------------------------------- #
# (e) batched cheap phase / whole-chunk parity across backends
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("backend", [stages.REFERENCE, stages.PALLAS])
def test_cheap_phase_batch_matches_vmap(mode_setup, backend):
    """The batch-level cheap phase (batch detect kernel, whole-chunk packed
    gathers, fused vote) == the per-read vmap of the same plan's stage
    bodies — outputs AND per-read counters."""
    cfg, signals, idx = mode_setup
    arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    plan = stages.resolve_plan(cfg, backend)
    assert stages.cheap_primitives(plan, cfg) is not None
    fast = jax.jit(lambda s: pipeline.cheap_phase(s, arrays, cfg, plan))
    slow = jax.jit(lambda s: pipeline.cheap_phase_vmap(s, arrays, cfg, plan))
    q1, t1, h1, c1 = fast(signals)
    q0, t0, h0, c0 = slow(signals)
    np.testing.assert_array_equal(np.asarray(q0), np.asarray(q1))
    np.testing.assert_array_equal(np.asarray(t0), np.asarray(t1))
    np.testing.assert_array_equal(np.asarray(h0), np.asarray(h1))
    assert set(c0) == set(c1)
    for k in c0:
        np.testing.assert_array_equal(np.asarray(c0[k]), np.asarray(c1[k]),
                                      err_msg=k)


def test_map_chunk_parity_across_backends(mode_setup):
    """Whole-chunk outputs + the unchanged counter schema, reference vs
    pallas plans, fast path on and off."""
    cfg, signals, idx = mode_setup
    arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    outs = {}
    for compaction in (True, False):
        c = cfg.replace(chain_compaction=compaction)
        for backend in (stages.REFERENCE, stages.PALLAS):
            plan = stages.resolve_plan(c, backend)
            outs[(compaction, backend)] = map_chunk(signals, arrays, c,
                                                    plan=plan)
    base = outs[(True, stages.REFERENCE)]
    assert set(base.counters) == set(stages.CHUNK_COUNTER_SCHEMA)
    for tag, out in outs.items():
        assert set(out.counters) == set(stages.CHUNK_COUNTER_SCHEMA), tag
        np.testing.assert_array_equal(np.asarray(base.t_start),
                                      np.asarray(out.t_start), err_msg=str(tag))
        np.testing.assert_array_equal(np.asarray(base.mapped),
                                      np.asarray(out.mapped), err_msg=str(tag))
        np.testing.assert_allclose(np.asarray(base.score),
                                   np.asarray(out.score), rtol=1e-5,
                                   err_msg=str(tag))
        for k in stages.CHUNK_COUNTER_SCHEMA:
            assert int(base.counters[k]) == int(out.counters[k]), (tag, k)


@pytest.mark.slow
def test_cheap_phase_property_sweep():
    """Property sweep: random references/read mixes across the mode grid;
    batch cheap phase == per-read vmap every time."""
    for seed in range(3):
        for mode in MODES:
            cfg = MarsConfig(hash_bits=11, signal_len=512,
                             max_events=96).with_mode(mode)
            ref = simulate.make_reference(3_000, seed=20 + seed)
            reads = simulate.sample_reads(ref, 4, signal_len=cfg.signal_len,
                                          seed=30 + seed, junk_frac=0.5)
            idx = build_index(ref.events_concat, ref.n_events, cfg)
            arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
            plan = stages.resolve_plan(cfg, stages.REFERENCE)
            sig = jnp.asarray(reads.signals)
            got = pipeline.cheap_phase(sig, arrays, cfg, plan)
            want = pipeline.cheap_phase_vmap(sig, arrays, cfg, plan)
            for g, w in zip(got[:3], want[:3]):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
            for k in want[3]:
                np.testing.assert_array_equal(np.asarray(got[3][k]),
                                              np.asarray(want[3][k]),
                                              err_msg=(mode, seed, k))
