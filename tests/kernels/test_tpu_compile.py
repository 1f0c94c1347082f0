"""The main path compiles for a TPU v5e — no chip needed.

Interpret mode accepts what Mosaic refuses (block shapes off the (8, 128)
tiling, layouts it cannot cast, more VMEM than a kernel may use), so the
interpret-mode parity suites cannot tell whether a kernel runs on the
chip.  These tests ask the TPU compiler itself, for one chip of a
described ``v5e:2x2`` topology, at D1 widths: S = 1024 samples, E = 192
events, H = 16 hits per seed, 2^18 buckets, D1's index (about 6e4 packed
entries) and 256-read chunks.  They cover the five Pallas kernels with
``interpret=False`` and the whole ``ms_fixed`` chunk program on the Pallas
plan, which must contain Mosaic kernels (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers each
import every test file.  A described-chip compile cannot be read back
from JAX's persistent cache, so the cache is off while these tests run.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels as K
from repro.core import build_index, map_chunk, stages
from repro.core.index import index_arrays
from repro.signal import datasets

R = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def d1():
    """D1's config and packed index (host arrays; only shapes are used)."""
    spec = datasets.DATASETS["D1"]
    cfg = datasets.config_for(spec).with_mode("ms_fixed")
    ref, _ = datasets.build(spec, cfg, 1)
    return cfg, index_arrays(build_index(ref.events_concat, ref.n_events,
                                         cfg))


@pytest.fixture
def mosaic(monkeypatch):
    """Kernels lower through Mosaic, with jit caches and JAX's persistent
    compilation cache out of the way (no interpret-mode trace is reused,
    and nothing unreadable is written)."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(K, "INTERPRET", False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_event_detect_compiles(one_chip, d1, mosaic):
    from repro.kernels.event_detect.event_detect import event_detect_fixed
    cfg, _ = d1
    _compile(lambda x: event_detect_fixed(
        x, E=cfg.max_events, w=cfg.tstat_window,
        tau2=int(round(cfg.tstat_threshold ** 2)),
        eps=1 << (2 * cfg.frac_bits - 8), peak_r=cfg.peak_window,
        frac_bits=cfg.frac_bits, interpret=False),
        one_chip, ((R, cfg.signal_len), jnp.int32))


@pytest.mark.parametrize("width", [128, 4096])
def test_bitonic_sort_compiles(one_chip, mosaic, width):
    """The select-then-sort ladder's smallest block and the full E*H sort
    (3072 (t, q) pairs padded to 4096)."""
    from repro.kernels.bitonic_sort.bitonic_sort import bitonic_sort
    _compile(lambda k, v: bitonic_sort(k, v, interpret=False), one_chip,
             ((R, width), jnp.int32), ((R, width), jnp.int32))


@pytest.mark.parametrize("anchors", [64, 512])
def test_chain_dp_compiles(one_chip, d1, mosaic, anchors):
    """The ladder's narrowest width and cfg.max_anchors."""
    from repro.kernels.chain_dp.chain_dp import chain_dp_kernel
    cfg, _ = d1
    _compile(lambda q, t, v: chain_dp_kernel(
        q, t, v, B=cfg.chain_band, max_gap=cfg.max_gap,
        gap_cost=cfg.gap_cost, skip_cost=cfg.skip_cost,
        anchor_score=cfg.anchor_score, interpret=False),
        one_chip, ((R, anchors), jnp.int32), ((R, anchors), jnp.int32),
        ((R, anchors), jnp.bool_))


def test_pluto_lookup_rows_compiles(one_chip, d1, mosaic):
    from repro.kernels.pluto_lookup.ops import _pad_to
    from repro.kernels.pluto_lookup.pluto_lookup import BT, pluto_lookup_rows
    cfg, arrays = d1
    n = arrays["entries_packed"].shape[1]
    n_pad = n + (-n % BT)
    _compile(lambda t, i: pluto_lookup_rows(t, i, interpret=False), one_chip,
             ((2, n_pad), jnp.int32),
             ((R * cfg.max_events * cfg.max_hits_per_seed,), jnp.int32))


def _fused_params(cfg):
    clip_q = int(round(cfg.quant_clip_sigma * (1 << cfg.frac_bits)))
    return dict(
        n_ev_max=cfg.max_events, hits=cfg.max_hits_per_seed,
        tw=cfg.tstat_window, tau2=int(round(cfg.tstat_threshold ** 2)),
        eps=1 << (2 * cfg.frac_bits - 8), peak_r=cfg.peak_window,
        frac_bits=cfg.frac_bits, seed_w=cfg.seed_width,
        seed_q=cfg.quant_bits, minimizer_r=cfg.minimizer_radius,
        levels=cfg.quant_levels, clip_q=clip_q,
        step_q=(2 * clip_q) // cfg.quant_levels, n_buckets=cfg.n_buckets,
        thresh_freq=cfg.thresh_freq,
        use_freq=cfg.use_freq_filter, use_vote=cfg.use_vote_filter,
        vlog2=cfg.voting_window_log2, nbins=cfg.vote_bins,
        thresh_vote=cfg.thresh_voting, interpret=False)


# E. coli at MARS Table 2 row D2's 5 Mbp (bench/configs/ecoli_d2.json):
# 2^22 buckets, 9,999,974 packed entries, far past the VMEM launch's line
D2_CFG = dict(hash_bits=22, seed_width=9, thresh_freq=2000, thresh_voting=5)
D2_ENTRIES = 9_999_974


def _d2_index(one_chip):
    cfg = datasets.config_for(datasets.DATASETS["D2"]).with_mode(
        "ms_fixed").replace(**D2_CFG)
    index = {"bucket_bounds": ((2, cfg.n_buckets), jnp.int32),
             "entries_packed": ((2, (D2_ENTRIES // 128 + 2) * 128),
                                jnp.int32)}
    return cfg, {k: jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                 for k, (s, d) in index.items()}


def test_cheap_fused_hbm_compiles(one_chip, mosaic):
    """The HBM launch at D2's index, its own trace name in the program."""
    from repro.kernels.cheap_fused.cheap_fused import cheap_fused_hbm
    cfg, index = _d2_index(one_chip)
    bounds, ent = index["bucket_bounds"], index["entries_packed"]
    compiled = _compile(
        lambda x, b, e: cheap_fused_hbm(x, b, e, **_fused_params(cfg)),
        one_chip, ((R, cfg.signal_len), jnp.int32),
        (bounds.shape, bounds.dtype), (ent.shape, ent.dtype))
    assert "cheap_fused_hbm" in compiled.as_text()


def test_cheap_fused_compiles(one_chip, d1, mosaic):
    """The fused kernel at D1's index: 2^18 buckets and about 6e4 entries,
    both tables resident in VMEM in the kernel's byte-plane layout."""
    from repro.kernels.cheap_fused.cheap_fused import (cheap_fused_fixed,
                                                       index_planes)
    cfg, arrays = d1
    bounds, ent = jax.eval_shape(index_planes, arrays["bucket_bounds"],
                                 arrays["entries_packed"])
    _compile(lambda x, b, e: cheap_fused_fixed(x, b, e, **_fused_params(cfg)),
        one_chip, ((R, cfg.signal_len), jnp.int32),
        (bounds.shape, bounds.dtype), (ent.shape, ent.dtype))


def test_pallas_chunk_program_compiles(one_chip, d1, mosaic):
    """``map_chunk`` on the resolved Pallas plan — fused cheap phase,
    bitonic sort and chain DP kernels inside one XLA program — as
    ``Mapper(use_kernels=True)`` runs it."""
    cfg, arrays = d1
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    assert stages.fused_cheap_backend(plan, cfg) is not None
    index = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=one_chip)
             for k, v in arrays.items()}
    signals = jax.ShapeDtypeStruct((R, cfg.signal_len), jnp.float32,
                                   sharding=one_chip)
    n_valid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = map_chunk.lower(signals, index, cfg, n_valid=n_valid,
                               plan=plan).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_chunk_program_compiles_past_vmem(one_chip, mosaic):
    """``map_chunk`` on the Pallas plan at D2's index: the fused cheap
    phase takes its HBM launch, and no ``pluto_lookup`` sweep is left."""
    cfg, index = _d2_index(one_chip)
    plan = stages.resolve_plan(cfg, stages.PALLAS)
    signals = jax.ShapeDtypeStruct((R, cfg.signal_len), jnp.float32,
                                   sharding=one_chip)
    n_valid = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    text = map_chunk.lower(signals, index, cfg, n_valid=n_valid,
                           plan=plan).compile().as_text()
    assert "cheap_fused_hbm" in text and "pluto" not in text
