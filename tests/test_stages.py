"""Stage-graph engine: registry semantics, backend parity, sharded parity."""
import inspect
import os
import pathlib
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MarsConfig, build_index, map_chunk, stages
from repro.core.index import index_arrays
from repro.signal import simulate

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = MarsConfig(hash_bits=12).with_mode("ms_fixed")
    ref = simulate.make_reference(5_000, seed=5)
    reads = simulate.sample_reads(ref, 4, signal_len=cfg.signal_len, seed=6)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    return cfg, jnp.asarray(reads.signals), arrays


# --------------------------------------------------------------------------- #
# Registry / plan resolution
# --------------------------------------------------------------------------- #
def test_reference_plan_covers_every_stage():
    plan = stages.resolve_plan(MarsConfig(), stages.REFERENCE)
    assert tuple(s for s, _ in plan) == stages.STAGE_ORDER
    assert all(b == stages.REFERENCE for _, b in plan)


def test_pallas_plan_uses_registered_kernels():
    plan = dict(stages.resolve_plan(MarsConfig().with_mode("ms_fixed"),
                                    stages.PALLAS))
    assert plan["detect"] == stages.PALLAS
    assert plan["query"] == stages.PALLAS
    assert plan["sort"] == stages.PALLAS
    assert plan["dp"] == stages.PALLAS
    # stages without an accelerated backend fall back to reference
    assert plan["quantize"] == stages.REFERENCE
    assert plan["finalize"] == stages.REFERENCE


def test_unsupported_backend_falls_back():
    """The fixed-point event-detect kernel cannot serve float configs."""
    plan = dict(stages.resolve_plan(MarsConfig().with_mode("rh2"),
                                    stages.PALLAS))
    assert plan["detect"] == stages.REFERENCE
    assert plan["query"] == stages.PALLAS   # config-independent kernels stay


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown backend"):
        stages.resolve_plan(MarsConfig(), "bogus")


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError):
        stages.register_backend("vote", stages.REFERENCE, lambda s, c, i: s)
    with pytest.raises(ValueError):
        stages.register_backend("no_such_stage", "x", lambda s, c, i: s)


def test_map_chunk_accepts_no_per_stage_callables():
    """Acceptance criterion: backend selection flows only through the
    registry/config — no gather/sorter/dp/detector kwargs."""
    params = set(inspect.signature(map_chunk.__wrapped__).parameters)
    assert params.isdisjoint({"gather", "sorter", "dp", "detector"})
    assert {"plan", "use_kernels", "n_valid"} <= params


# The plan table: which backend serves each stage, and whether the fused
# cheap-phase kernel engages, for the configs of scripts/kernel_support.py.
# A silent fallback keeps every parity test green but changes what runs on
# the chip, so the whole map is pinned here.
_R, _P = stages.REFERENCE, stages.PALLAS
_PLAN_CONFIGS = {
    "ms_fixed": {},
    "ms_float": {},
    "rh2": {},
    "ms_fixed_w13": {"tstat_window": 13},
}
_PALLAS_ALL = dict(detect=_P, quantize=_R, seed=_R, query=_P, vote=_R,
                   sort=_P, dp=_P, finalize=_R)
_PALLAS_NO_DETECT = {**_PALLAS_ALL, "detect": _R}
PLAN_TABLE = {
    ("ms_fixed", "pallas"): (_PALLAS_ALL, "pallas"),
    ("ms_float", "pallas"): (_PALLAS_NO_DETECT, None),
    ("rh2", "pallas"): (_PALLAS_NO_DETECT, None),
    ("ms_fixed_w13", "pallas"): (_PALLAS_NO_DETECT, None),
    **{(c, b): ({s: (b if s == "query" else _R) for s in stages.STAGE_ORDER},
                None)
       for c in _PLAN_CONFIGS for b in ("ring", "a2a", "tiered")},
}


@pytest.mark.parametrize("config,backend", sorted(PLAN_TABLE))
def test_plan_table(config, backend):
    mode = config.removesuffix("_w13")
    cfg = MarsConfig(hash_bits=12).with_mode(mode).replace(
        **_PLAN_CONFIGS[config])
    plan = stages.resolve_plan(cfg, backend)
    want, fused = PLAN_TABLE[config, backend]
    assert tuple(s for s, _ in plan) == stages.STAGE_ORDER
    assert dict(plan) == want
    got = stages.fused_cheap_backend(plan, cfg)
    assert (got.name if got else None) == fused

# --------------------------------------------------------------------------- #
# Backend parity
# --------------------------------------------------------------------------- #
def test_counter_schema_uniform(tiny_setup):
    cfg, sig, arrays = tiny_setup
    for use_kernels in (False, True):
        out = map_chunk(sig, arrays, cfg, use_kernels)
        assert set(out.counters) == set(stages.CHUNK_COUNTER_SCHEMA)


@pytest.mark.parametrize("stage", ["detect", "query", "sort", "dp"])
def test_single_stage_pallas_parity(tiny_setup, stage):
    """Each accelerated backend, swapped in alone, reproduces the full
    reference pipeline output on the same inputs."""
    cfg, sig, arrays = tiny_setup
    ref_plan = stages.resolve_plan(cfg, stages.REFERENCE)
    mixed = tuple((s, stages.PALLAS if s == stage else b)
                  for s, b in ref_plan)
    out_ref = map_chunk(sig, arrays, cfg, plan=ref_plan)
    out_mix = map_chunk(sig, arrays, cfg, plan=mixed)
    np.testing.assert_array_equal(np.asarray(out_ref.t_start),
                                  np.asarray(out_mix.t_start))
    np.testing.assert_array_equal(np.asarray(out_ref.mapped),
                                  np.asarray(out_mix.mapped))
    np.testing.assert_allclose(np.asarray(out_ref.score),
                               np.asarray(out_mix.score), rtol=1e-5)
    for k in stages.CHUNK_COUNTER_SCHEMA:
        assert int(out_ref.counters[k]) == int(out_mix.counters[k]), k


def test_padded_rows_do_not_inflate_counters(tiny_setup):
    cfg, sig, arrays = tiny_setup
    out_full = map_chunk(sig, arrays, cfg)
    out_masked = map_chunk(sig, arrays, cfg, n_valid=2)
    assert int(out_masked.counters["n_reads"]) == 2
    assert int(out_masked.counters["n_samples"]) == 2 * sig.shape[1]
    for k in stages.COUNTER_SCHEMA:
        assert int(out_masked.counters[k]) <= int(out_full.counters[k]), k
    # pad rows never report as mapped
    assert not np.asarray(out_masked.mapped)[2:].any()


# --------------------------------------------------------------------------- #
# Sharded map_chunk == single-device map_chunk (8 virtual devices)
# --------------------------------------------------------------------------- #
SHARD_SCRIPT = """
import numpy as np, jax.numpy as jnp
from repro.core import MarsConfig, build_index, map_chunk, map_chunk_sharded
from repro.core.index import index_arrays
from repro.launch.mesh import make_mesh
from repro.signal import simulate

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = MarsConfig(hash_bits=12).with_mode("ms_fixed")
ref = simulate.make_reference(20_000, seed=3)
reads = simulate.sample_reads(ref, 16, signal_len=cfg.signal_len, seed=4,
                              junk_frac=0.1)
idx = build_index(ref.events_concat, ref.n_events, cfg)
arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
sig = jnp.asarray(reads.signals)
for n_valid in (None, 13):
    a = map_chunk(sig, arrays, cfg, n_valid=n_valid)
    b = map_chunk_sharded(sig, arrays, cfg, mesh, n_valid=n_valid)
    assert np.array_equal(np.asarray(a.t_start), np.asarray(b.t_start))
    assert np.array_equal(np.asarray(a.score), np.asarray(b.score))
    assert np.array_equal(np.asarray(a.mapped), np.asarray(b.mapped))
    assert np.array_equal(np.asarray(a.n_events), np.asarray(b.n_events))
    ca = {k: int(v) for k, v in a.counters.items()}
    cb = {k: int(v) for k, v in b.counters.items()}
    assert ca == cb, (n_valid, ca, cb)
print("ok")
"""


def test_sharded_map_chunk_matches_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", SHARD_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=420)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "ok" in r.stdout
