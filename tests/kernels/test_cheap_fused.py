"""Fused cheap-phase mega-kernel vs the per-stage programs.

The contract under test: for every supported config the ONE-launch
mega-kernel (detect -> quantize -> seed -> query -> vote, intermediates
kernel-resident, the probed index rows gathered from VMEM, or fetched
from HBM by DMA past the VMEM line) is bit-identical
to ``pipeline.cheap_phase(..., use_fused=False)`` (the per-stage batch
program) and to ``pipeline.cheap_phase_vmap`` (the per-read reference
ladder) — arrays AND every counter.  Unsupported configs must resolve to
``prims.fused is None`` and fall through the ladder unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MarsConfig, build_index, pipeline, stages
from repro.core.index import bucket_bounds, index_arrays
from repro.kernels.cheap_fused import cheap_fused
from repro.kernels.cheap_fused import ref as fused_ref
from repro.signal import simulate


@pytest.fixture(scope="module")
def setup():
    cfg = MarsConfig(hash_bits=12).with_mode("ms_fixed")
    ref = simulate.make_reference(6_000, seed=9)
    reads = simulate.sample_reads(ref, 6, signal_len=cfg.signal_len,
                                  seed=10, junk_frac=0.3)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    return cfg, jnp.asarray(reads.signals), index_arrays(idx)


def _assert_cheap_equal(got, want):
    gq, gt, gv, gc = got
    wq, wt, wv, wc = want
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gq), np.asarray(wq))
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(wt))
    assert set(gc) == set(wc)
    for k in wc:
        np.testing.assert_array_equal(np.asarray(gc[k]), np.asarray(wc[k]),
                                      err_msg=f"counter {k!r}")


def test_fused_engages_on_supported_plan(setup):
    """A pallas plan on the fixed/early-quant config must resolve the
    whole-phase kernel, not just per-stage primitives."""
    cfg, _, _ = setup
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    assert stages.fused_cheap_backend(plan, cfg) is not None
    prims = stages.cheap_primitives(plan, cfg)
    assert prims is not None and prims.fused is not None


def test_fused_matches_per_stage_and_vmap(setup):
    """cheap_phase (fused) == cheap_phase(use_fused=False) ==
    cheap_phase_vmap, arrays and all counters."""
    cfg, signals, arrays = setup
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    fused = pipeline.cheap_phase(signals, arrays, cfg, plan)
    per_stage = pipeline.cheap_phase(signals, arrays, cfg, plan,
                                     use_fused=False)
    vmapped = pipeline.cheap_phase_vmap(signals, arrays, cfg, plan)
    _assert_cheap_equal(fused, per_stage)
    # the vmap ladder carries the same uniform counters; compare on the
    # intersection (batch programs may add debug counters)
    fq, ft, fv, fc = fused
    vq, vt, vv, vc = vmapped
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(vv))
    np.testing.assert_array_equal(np.asarray(fq), np.asarray(vq))
    np.testing.assert_array_equal(np.asarray(ft), np.asarray(vt))
    for k in set(fc) & set(vc):
        np.testing.assert_array_equal(np.asarray(fc[k]), np.asarray(vc[k]),
                                      err_msg=f"counter {k!r}")


def _assert_matches_both(signals, arrays, cfg):
    """The fused kernel == the per-stage program == the vmap ladder, on
    every array and every counter."""
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    got = cheap_fused(signals, arrays, cfg)
    _assert_cheap_equal(got, pipeline.cheap_phase(signals, arrays, cfg, plan,
                                                  use_fused=False))
    _assert_cheap_equal(got, pipeline.cheap_phase_vmap(signals, arrays, cfg,
                                                       plan))


def _probes(signals, arrays, cfg):
    """(R, E) bucket id of every seed slot (the kernel gathers all E,
    valid or not)."""
    plan = stages.resolve_plan(cfg, stages.REFERENCE)

    def keys(signal):
        st = stages.execute_stages({"signal": signal, "counters": {}},
                                   arrays, cfg, plan,
                                   ("detect", "quantize", "seed"))
        return st["keys"]
    return np.asarray(jax.vmap(keys)(signals) & (cfg.n_buckets - 1))


def _starts(arrays, cfg):
    """The (n_buckets + 1,) prefix offsets the device bounds hold."""
    b = np.asarray(arrays["bucket_bounds"])[:, :cfg.n_buckets]
    return np.append(b[0], b[1, -1]).astype(np.int64)


def _with_starts(arrays, bucket_start):
    return dict(arrays, bucket_bounds=jnp.asarray(bucket_bounds(bucket_start)))


@pytest.mark.parametrize("n_reads", [1, 3, 5])
def test_fused_odd_shapes_and_tiles(setup, n_reads):
    """Odd read counts (one read per grid step) stay bit-exact."""
    cfg, signals, arrays = setup
    _assert_matches_both(signals[:n_reads], arrays, cfg)


def _boundary_case(setup, case):
    """(cfg, signals, arrays) for one of the gather's edge cases, each
    asserted to occur among the probes."""
    cfg, signals, arrays = setup
    h = cfg.max_hits_per_seed
    if case == "ends":
        cfg = cfg.replace(hash_bits=6)
        ref = simulate.make_reference(6_000, seed=9)
        arrays = index_arrays(build_index(ref.events_concat, ref.n_events,
                                          cfg))
        signals = signals[:3]
    bs = _starts(arrays, cfg)
    n = int(bs[-1])
    probed = _probes(signals, arrays, cfg)
    if case == "tail":
        # the median probed bucket runs to the table's end (at least its
        # last 3 entries), every later bucket is empty there
        b = int(np.median(probed))
        bs = np.minimum(bs, n - 3)
        bs[b + 1:] = n
        arrays = _with_starts(arrays, bs)
        assert (bs[probed] + h > n).sum() > 1
    elif case == "empty":
        bs[1:-1:2] = bs[0:-2:2]               # every even bucket empty
        arrays = _with_starts(arrays, bs)
        assert (bs[probed + 1] == bs[probed]).sum() > 10
    elif case == "straddle":
        assert ((bs[probed] % 128) > 128 - h).sum() > 10
    else:
        assert {0, cfg.n_buckets - 1} <= set(probed.ravel().tolist())
    return cfg, signals, arrays


@pytest.mark.parametrize("case", ["straddle", "tail", "empty", "ends"])
def test_fused_index_tile_boundary_probes(setup, case):
    """The gather's edge cases, each shown to occur among the probes:
    a seed's H-entry window straddling a 128-entry table row; buckets at
    the table's last entries, whose slots past the end read entry N-1;
    empty buckets; buckets 0 and n_buckets - 1 (a 64-bucket index, which
    also leaves the bucket table shorter than one row)."""
    cfg, signals, arrays = _boundary_case(setup, case)
    _assert_matches_both(signals, arrays, cfg)


def _hbm_launches(monkeypatch):
    """Force the HBM launch (every index past the VMEM line) and count
    its calls."""
    from repro.kernels.cheap_fused import ops
    calls = []
    launch = ops.cheap_fused_hbm

    def counted(*a, **kw):
        calls.append(a[0].shape[0])
        return launch(*a, **kw)
    monkeypatch.setattr(ops, "TABLE_BYTES_MAX", 0)
    monkeypatch.setattr(ops, "cheap_fused_hbm", counted)
    return calls


@pytest.mark.parametrize("case", ["straddle", "tail", "empty", "ends",
                                  "one_read"])
def test_hbm_launch_matches_vmem_launch(setup, case, monkeypatch):
    """The HBM launch (bound and entry tiles by DMA, pipelined by one
    read) == the VMEM launch == the per-stage program == the vmap ladder,
    on every array and counter, on the gather's edge cases and on a
    single read (a one-step pipeline)."""
    if case == "one_read":
        cfg, signals, arrays = setup
        signals = signals[:1]
    else:
        cfg, signals, arrays = _boundary_case(setup, case)
    vmem = cheap_fused(signals, arrays, cfg)
    calls = _hbm_launches(monkeypatch)
    hbm = cheap_fused(signals, arrays, cfg)
    assert calls == [signals.shape[0]]
    _assert_cheap_equal(hbm, vmem)
    _assert_matches_both(signals, arrays, cfg)


def test_index_past_vmem_falls_back(setup, monkeypatch):
    """An index whose tables would not fit the kernel's VMEM takes the HBM
    launch, inside the cheap phase's ladder, with the per-stage program's
    answers."""
    cfg, signals, arrays = setup
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    calls = _hbm_launches(monkeypatch)
    got = pipeline.cheap_phase(signals[:2], arrays, cfg, plan)
    assert calls == [2]
    _assert_cheap_equal(got, pipeline.cheap_phase(signals[:2], arrays, cfg,
                                                  plan, use_fused=False))


def test_supports_gate_rejects_tstat_overflow():
    """tstat_window=13 overflows the int32 fixed-point boundary test — the
    fused kernel's supports gate must reject it (the reference path fails
    fast at trace time for the same reason, so no ladder run here)."""
    cfg = MarsConfig(hash_bits=12, tstat_window=13).with_mode("ms_fixed")
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    assert stages.fused_cheap_backend(plan, cfg) is None
    prims = stages.cheap_primitives(plan, cfg)
    assert prims is None or prims.fused is None


@pytest.mark.parametrize("mode", ["ms_float", "rh2"])
def test_supports_gate_falls_back(mode):
    """Configs the kernel cannot serve bit-exactly must resolve to no fused
    backend, and the ladder must still agree with the vmap reference."""
    cfg = MarsConfig(hash_bits=12).with_mode(mode)
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    assert stages.fused_cheap_backend(plan, cfg) is None
    prims = stages.cheap_primitives(plan, cfg)
    assert prims is None or prims.fused is None
    ref = simulate.make_reference(4_000, seed=11)
    reads = simulate.sample_reads(ref, 3, signal_len=cfg.signal_len,
                                  seed=12, junk_frac=0.3)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    arrays = index_arrays(idx)
    signals = jnp.asarray(reads.signals)
    got = pipeline.cheap_phase(signals, arrays, cfg, plan)   # use_fused=True
    want = pipeline.cheap_phase_vmap(signals, arrays, cfg, plan)
    gq, gt, gv, _ = got
    wq, wt, wv, _ = want
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    np.testing.assert_array_equal(np.asarray(gq), np.asarray(wq))
    np.testing.assert_array_equal(np.asarray(gt), np.asarray(wt))


def test_tiered_plan_never_fuses(setup):
    """The tiered query consumes the hot-tile index view, which the fused
    kernel cannot stream — the plan must not resolve a fused backend."""
    cfg, _, _ = setup
    plan = stages.resolve_plan(cfg, "tiered")
    assert stages.fused_cheap_backend(plan, cfg) is None


def test_minimizer_radius_supported(setup):
    """Minimizer winnowing changes the seed plane; the fused kernel
    replicates it (not gated out)."""
    cfg, signals, arrays0 = setup
    cfg2 = cfg.replace(minimizer_radius=2)
    ref = simulate.make_reference(6_000, seed=9)
    idx = build_index(ref.events_concat, ref.n_events, cfg2)
    arrays = index_arrays(idx)
    got = cheap_fused(signals, arrays, cfg2)
    want = fused_ref.cheap_fused_ref(signals, arrays, cfg2)
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
