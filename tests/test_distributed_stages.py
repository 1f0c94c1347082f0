"""Bit-exact parity: the partitioned-index `query:ring` / `query:a2a` stage
backends vs single-device map_chunk — results AND every CHUNK_COUNTER_SCHEMA
counter, with and without the chaining fast path (chain_compaction), plus
pad-row (n_valid) masking — on a multi-device CPU mesh (subprocess)."""
import os
import pathlib
import subprocess
import sys

import pytest

# run_tier1.sh runs this whole file in its dedicated distributed pass
# (under 4 forced CPU devices) after the fast pass — not twice
pytestmark = pytest.mark.slow

REPO = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import MarsConfig, Mapper, build_index, driver, stages
from repro.core import partition_index
from repro.core.index import index_arrays
from repro.core.pipeline import map_chunk, map_chunk_sharded
from repro.core.distributed import partitioned_index_shardings
from repro.launch.mesh import make_mesh
from repro.signal import simulate

mesh = make_mesh((2, 2), ("data", "model"))
ref = simulate.make_reference(50_000, seed=3)

def check(cfg, reads, idx, n_valid=None):
    arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
    out_ref = map_chunk(jnp.asarray(reads.signals), arrays, cfg,
                        n_valid=n_valid)
    parts = partition_index(idx, mesh.shape["model"])
    sh = partitioned_index_shardings(mesh)
    parts_dev = {k: jax.device_put(jnp.asarray(v), sh[k])
                 for k, v in parts.items()}
    for backend in ("ring", "a2a"):
        plan = stages.resolve_plan(cfg, backend)
        # only the query stage is distributed; everything else is the
        # reference per-read program
        assert dict(plan)["query"] == backend, plan
        assert stages.plan_index_kind(plan) == "partitioned"
        assert all(b == stages.REFERENCE for s, b in plan if s != "query")
        out = map_chunk_sharded(jnp.asarray(reads.signals), parts_dev, cfg,
                                mesh, plan=plan, n_valid=n_valid)
        tag = (backend, cfg.chain_compaction, n_valid)
        # counter pytree is derived from the schema — it can never drift
        assert set(out.counters) == set(stages.CHUNK_COUNTER_SCHEMA), tag
        np.testing.assert_array_equal(np.asarray(out_ref.t_start),
                                      np.asarray(out.t_start), err_msg=str(tag))
        np.testing.assert_array_equal(np.asarray(out_ref.score),
                                      np.asarray(out.score), err_msg=str(tag))
        np.testing.assert_array_equal(np.asarray(out_ref.mapped),
                                      np.asarray(out.mapped), err_msg=str(tag))
        np.testing.assert_array_equal(np.asarray(out_ref.n_events),
                                      np.asarray(out.n_events), err_msg=str(tag))
        for k in stages.CHUNK_COUNTER_SCHEMA:
            assert int(out.counters[k]) == int(out_ref.counters[k]), (tag, k)

for compaction in (True, False):
    cfg = MarsConfig(hash_bits=14,
                     chain_compaction=compaction).with_mode("ms_fixed")
    reads = simulate.sample_reads(ref, 16, signal_len=cfg.signal_len, seed=4,
                                  junk_frac=0.25)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    check(cfg, reads, idx)
    if compaction:
        check(cfg, reads, idx, n_valid=13)      # pad rows masked identically

# Mapper + unified driver host loop over the partitioned backend
cfg = MarsConfig(hash_bits=14).with_mode("ms_fixed")
reads = simulate.sample_reads(ref, 16, signal_len=cfg.signal_len, seed=4,
                              junk_frac=0.25)
idx = build_index(ref.events_concat, ref.n_events, cfg)
got = Mapper(idx, cfg, backend="ring", mesh=mesh).map_signals(
    reads.signals[:14], chunk=8)
want = driver.collect(driver.stream_map(
    Mapper(idx, cfg).chunk_fn(), driver.array_chunks(reads.signals[:14], 8)))
np.testing.assert_array_equal(got.t_start, want.t_start)
np.testing.assert_array_equal(got.mapped, want.mapped)
assert got.counters == want.counters
print("ok")
"""


def test_partitioned_query_backends_match_single_device():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "ok" in r.stdout


POD_MESH_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
from repro.core import MarsConfig, build_index, stages
from repro.core.distributed import (partition_index,
                                    partitioned_index_shardings)
from repro.core.index import index_arrays
from repro.core.pipeline import map_chunk, map_chunk_sharded
from repro.launch.mesh import make_mesh
from repro.signal import simulate

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
cfg = MarsConfig(hash_bits=14).with_mode("ms_fixed")
ref = simulate.make_reference(50_000, seed=3)
reads = simulate.sample_reads(ref, 16, signal_len=cfg.signal_len, seed=4,
                              junk_frac=0.1)
idx = build_index(ref.events_concat, ref.n_events, cfg)
arrays = {k: jnp.asarray(v) for k, v in index_arrays(idx).items()}
out_ref = map_chunk(jnp.asarray(reads.signals), arrays, cfg)
parts = partition_index(idx, mesh.shape["model"])
part_sh = partitioned_index_shardings(mesh)
parts_dev = {k: jax.device_put(jnp.asarray(v), part_sh[k])
             for k, v in parts.items()}
for sched in ("ring", "a2a"):
    out = map_chunk_sharded(jnp.asarray(reads.signals), parts_dev, cfg, mesh,
                            plan=stages.resolve_plan(cfg, sched))
    assert np.array_equal(np.asarray(out_ref.mapped), np.asarray(out.mapped)), sched
    assert np.array_equal(np.asarray(out_ref.t_start), np.asarray(out.t_start)), sched
    assert np.array_equal(np.asarray(out_ref.score), np.asarray(out.score)), sched
    assert set(out.counters) == set(stages.CHUNK_COUNTER_SCHEMA), sched
    for k in stages.CHUNK_COUNTER_SCHEMA:
        assert int(out.counters[k]) == int(out_ref.counters[k]), (sched, k)
print("ok")
"""


def test_partitioned_query_on_pod_mesh_matches_single_device():
    """Both schedules on a 3-axis (pod, data, model) mesh of 8 devices:
    reads shard over all three axes, the index over 'model' alone."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", POD_MESH_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=420)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "ok" in r.stdout


PREPASS_SCRIPT = """
import numpy as np
import jax
from jax.sharding import Mesh
from repro.core import MarsConfig, build_index
from repro.core.pipeline import Mapper
from repro.signal import simulate

cfg = MarsConfig(hash_bits=12).with_mode("ms_fixed")
ref = simulate.make_reference(20_000, seed=3)
reads = simulate.sample_reads(ref, 16, signal_len=cfg.signal_len, seed=4,
                              junk_frac=0.3)
idx = build_index(ref.events_concat, ref.n_events, cfg)
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))

outs = {}
for name, kw in (("mesh_reuse", dict(mesh=mesh, reuse_prepass=True)),
                 ("mesh_noreuse", dict(mesh=mesh, reuse_prepass=False)),
                 ("single", dict(reuse_prepass=True))):
    m = Mapper(idx, cfg, backend="tiered", tiles=8, cache_slots=4, **kw)
    # the sharded path no longer forces reuse_prepass off under a mesh
    assert m.cache.reuse_prepass == kw["reuse_prepass"], name
    outs[name] = m.chunk_fn()(reads.signals, 16)

base = outs["single"]
for name in ("mesh_reuse", "mesh_noreuse"):
    o = outs[name]
    for f in ("t_start", "score", "mapped", "n_events"):
        np.testing.assert_array_equal(np.asarray(getattr(o, f)),
                                      np.asarray(getattr(base, f)),
                                      err_msg=f"{name}.{f}")
    for k in base.counters:
        np.testing.assert_array_equal(np.asarray(o.counters[k]),
                                      np.asarray(base.counters[k]),
                                      err_msg=f"{name}.{k}")
print("ok")
"""


def test_tiered_prepass_reuse_sharded_parity():
    """Satellite of the fused-kernel PR: the tiered prepass planes
    (t_pre_keys / t_pre_valid / t_pre_nev) now flow through shard_map
    in_specs sharded per-read over the mesh 'data' axis — reuse on the
    sharded path must be bit-identical to reuse off AND to the
    single-device mapper."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", PREPASS_SCRIPT],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=560)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "ok" in r.stdout


def test_partitioned_plan_rejected_single_device():
    """A partitioned-index plan must not silently run against a replicated
    table on one device."""
    import jax.numpy as jnp
    from repro.core import MarsConfig, stages
    from repro.core.pipeline import map_chunk

    cfg = MarsConfig(hash_bits=14)
    plan = stages.resolve_plan(cfg, "ring")
    sig = jnp.zeros((4, cfg.signal_len), jnp.float32)
    with pytest.raises(ValueError, match="partitioned"):
        map_chunk(sig, {}, cfg, plan=plan)


def test_no_duplicated_per_read_program():
    """The drift that motivated this PR: core/distributed.py must hold no
    second per-read program or hand-listed counter pytree — schedules are
    registered `query` backends and the counter specs flow from
    stages.CHUNK_COUNTER_SCHEMA via the shared sharded chunk program."""
    import inspect
    import repro.core.distributed as D
    from repro.core import stages

    src = inspect.getsource(D)
    assert "out_specs" not in src        # no hand-rolled shard_map program
    assert "chain_anchors" not in src    # no duplicated post-query tail
    assert "vote_filter" not in src
    for name in ("ring", "a2a"):
        b = stages.get_backend("query", name)
        assert b.index_kind == "partitioned"
