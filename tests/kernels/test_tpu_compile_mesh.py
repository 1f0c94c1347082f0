"""The sharded chunk program compiles for four TPU v5e chips — no chip
needed.

``pipeline.sharded_chunk_fn`` on a (2, 2) ``("data", "model")`` mesh of a
described ``v5e:2x2`` topology, at D1's and D2's real index shapes (the
shapes of ``test_tpu_compile.py``), for the three ways a four-chip cell
can hold its index: replicated on the Pallas plan, or partitioned over
the 'model' axis for ``query:a2a`` and ``query:ring``.  Each program's
collective inventory is pinned: the Pallas plan keeps the fused kernel and
reduces its counters once; ``a2a`` returns hits home by all-to-all;
``ring`` moves everything by collective-permute, and so puts more bytes
on the wire than ``a2a`` (``analysis/hlo.analyze``).

The topology is described in a module fixture, never at import, and the
persistent compile cache is off while these compile (the ``mosaic``
fixture, the notes in ``test_tpu_compile.py``).  Its own file, so the test workers can run it
beside the one-chip compiles; two workers that both describe the topology
each load the TPU library, which takes ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``
in their environment (without it the second one's fixture skips).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import hlo
from repro.core import build_index, pipeline, stages
from repro.core.index import index_arrays, partition_index
from repro.signal import datasets
from test_tpu_compile import D2_CFG, D2_ENTRIES, R

N_MODEL = 2


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices).reshape(2, N_MODEL),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)


@pytest.fixture(scope="module")
def shapes():
    """Per index size: its config, and the (shape, dtype) of every leaf of
    the replicated and of the partitioned index pytree."""
    spec = datasets.DATASETS["D1"]
    cfg = datasets.config_for(spec).with_mode("ms_fixed")
    ref, _ = datasets.build(spec, cfg, 1)
    idx = build_index(ref.events_concat, ref.n_events, cfg)
    d1 = (cfg,
          {k: (v.shape, v.dtype) for k, v in index_arrays(idx).items()},
          {k: (v.shape, v.dtype)
           for k, v in partition_index(idx, N_MODEL).items()})
    cfg2 = datasets.config_for(datasets.DATASETS["D2"]).with_mode(
        "ms_fixed").replace(**D2_CFG)
    bl = cfg2.n_buckets // N_MODEL
    d2 = (cfg2,
          {"bucket_bounds": ((2, cfg2.n_buckets), jnp.int32),
           "entries_packed": ((2, (D2_ENTRIES // 128 + 2) * 128), jnp.int32)},
          {"p_bucket_start": ((N_MODEL, bl + 1), jnp.int32),
           "p_entries_packed": ((N_MODEL, 2, -(-D2_ENTRIES // N_MODEL)),
                                jnp.int32)})
    return {"D1": d1, "D2": d2}


@pytest.fixture(scope="module")
def programs():
    """(backend, size) -> compiled HLO text, shared by the cases."""
    return {}


def _compile(mesh, shapes, programs, backend, size):
    if (backend, size) not in programs:
        cfg, replicated, partitioned = shapes[size]
        plan = stages.resolve_plan(cfg, backend)
        if stages.plan_index_kind(plan) == "partitioned":
            leaves, spec = partitioned, P("model")
        else:
            leaves, spec = replicated, P()
        index = {k: jax.ShapeDtypeStruct(s, d,
                                         sharding=NamedSharding(mesh, spec))
                 for k, (s, d) in leaves.items()}
        signals = jax.ShapeDtypeStruct(
            (R, cfg.signal_len), jnp.float32,
            sharding=NamedSharding(mesh, P(("data", "model"), None)))
        n_valid = jax.ShapeDtypeStruct((), jnp.int32,
                                       sharding=NamedSharding(mesh, P()))
        fn = pipeline.sharded_chunk_fn(cfg, mesh, plan)
        programs[backend, size] = fn.lower(signals, index,
                                           n_valid).compile().as_text()
    return programs[backend, size]


def _count(text, op):
    """Collective ops of one kind in the program (async pairs once)."""
    return hlo.analyze(text)[f"count_{op}"]


@pytest.mark.parametrize("size", ["D1", "D2"])
@pytest.mark.parametrize("backend", ["pallas", "a2a", "ring"])
def test_sharded_chunk_program_compiles_for_four_chips(
        mesh, shapes, programs, mosaic, backend, size):
    text = _compile(mesh, shapes, programs, backend, size)
    if backend == "pallas":
        launch = "cheap_fused_hbm" if size == "D2" else "cheap_fused"
        assert launch in text and "tpu_custom_call" in text
        if size == "D1":
            assert "cheap_fused_hbm" not in text
        assert _count(text, "all-reduce") == 1
        assert _count(text, "all-to-all") == 0
        assert _count(text, "collective-permute") == 0
    elif backend == "a2a":
        assert _count(text, "all-to-all") > 0
    else:
        assert _count(text, "collective-permute") > 0
        assert _count(text, "all-to-all") == 0
        # ring carries hits and counter partials around every step; a2a
        # sends them home once
        a2a = _compile(mesh, shapes, programs, "a2a", size)
        assert hlo.analyze(a2a)["total"] < hlo.analyze(text)["total"]
