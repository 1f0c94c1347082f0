"""The discrete-event simulator's contracts (core/sim/ + core/costmodel.py).

Three pillars:

  * **Determinism** — a run is a pure function of its inputs: same
    workload/config (or same recorded trace + seed) -> identical event
    log, stats and totals.
  * **Degenerate identity** — on no-contention configs the simulator
    reproduces the analytic closed forms (``mars_latency`` /
    ``mars_array_latency`` / ``dram_size_sensitivity``) to <1%, swept
    over channel/die counts.  This is the calibration contract that keeps
    the two CostModel backends from drifting apart.
  * **Trace replay** — ``ServeDriver.events`` is sufficient input for the
    serving simulator: replaying the recorded dispatch law reproduces
    every recorded completion exactly (max_drift == 0).

Plus the CostModel interface itself (registry, routing, shed signal) and
the measured-queue-delay shed scenario the analytic offered-load signal
cannot see.
"""
import dataclasses
import math

import numpy as np
import pytest

from repro.core import Mapper, ServeDriver, costmodel, ssd_model
from repro.core.sim import (replay_chunk_trace, simulate_array_latency,
                            simulate_batch, simulate_dram_sensitivity,
                            simulate_serving, simulate_serving_virtual)
from repro.core.workload import Workload


def make_workload(n_reads: int = 50_000) -> Workload:
    """A pinned mid-size raw-signal workload (no pipeline run needed)."""
    r = n_reads
    return Workload(
        n_reads=r, n_samples=4_000 * r, n_events=450 * r, n_seeds=420 * r,
        n_lookups=420 * r, n_hits_raw=3_400 * r, n_hits_exact=3_800 * r,
        n_hits_postfreq=900 * r, n_votes=900 * r,
        n_anchors_postvote=260 * r, n_sorted=260 * r, n_dp_pairs=4_160 * r,
        bytes_raw=8_000 * r, bytes_index=512 << 20,
        bytes_intermediate=30_000 * r, fixed_point=True)


# --------------------------------------------------------------------------- #
# Determinism
# --------------------------------------------------------------------------- #
def test_batch_sim_deterministic():
    w = make_workload()
    a = simulate_batch(w)
    b = simulate_batch(w)
    assert a["event_log"] == b["event_log"]
    assert a["total"] == b["total"]
    assert a["components"] == b["components"]
    assert a["controller"] == b["controller"]


def test_serving_sim_deterministic_per_seed():
    a = simulate_serving_virtual(8, 4.0, seed=3)
    b = simulate_serving_virtual(8, 4.0, seed=3)
    assert a == b
    c = simulate_serving_virtual(8, 4.0, seed=4)
    assert c["p50"] != a["p50"]         # the seed is actually consumed


def test_event_log_shape():
    w = make_workload()
    log = simulate_batch(w, n_stripes=4)["event_log"]
    assert log, "simulator produced no events"
    times = [t for t, _, _, _ in log]
    assert times == sorted(times)       # logged in simulated-time order
    kinds = {k for _, _, k, _ in log}
    assert kinds == {"enqueue", "start", "done"}


# --------------------------------------------------------------------------- #
# Degenerate identity vs the closed forms
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("channels,chips", [(1, 1), (1, 8), (2, 2),
                                            (4, 4), (8, 8)])
def test_degenerate_matches_analytic(channels, chips):
    w = make_workload()
    ssd = dataclasses.replace(ssd_model.SSDConfig(), channels=channels,
                              chips_per_channel=chips)
    want = ssd_model.mars_latency(w, ssd)["total"]
    got = simulate_batch(w, ssd)["total"]
    assert abs(got - want) / want < 0.01


def test_degenerate_matches_compute_bound():
    """A compute-dominated workload (tiny byte volume) hits the other arm
    of the max/min overlap law."""
    w = make_workload()
    w = dataclasses.replace(w, bytes_raw=w.bytes_raw // 200,
                            bytes_index=w.bytes_index // 200)
    want = ssd_model.mars_latency(w)["total"]
    got = simulate_batch(w)["total"]
    assert abs(got - want) / want < 0.01


def test_array_matches_analytic():
    w = make_workload()
    for n_failed in (0, 1):
        arr = ssd_model.SSDArrayConfig(n_ssds=4, n_failed=n_failed)
        want = ssd_model.mars_array_latency(w, arr)["total"]
        got = simulate_array_latency(w, arr)["total"]
        assert abs(got - want) / want < 0.01


def test_dram_sensitivity_matches_analytic():
    w = make_workload()
    want = ssd_model.dram_size_sensitivity(w)
    got = simulate_dram_sensitivity(w)
    assert set(got) == set(want)
    for size in want:
        assert abs(got[size] - want[size]) / want[size] < 0.01


def test_serving_twins_agree_below_saturation():
    a = ssd_model.serving_latency_virtual(8, 4.0)
    s = simulate_serving_virtual(8, 4.0)
    assert not s["saturated"]
    assert abs(s["p50"] - a["p50"]) / a["p50"] < 0.10
    w = make_workload()
    arr = ssd_model.SSDArrayConfig(n_ssds=4)
    cap = w.n_reads / ssd_model.mars_array_latency(w, arr)["total"]
    aa = ssd_model.serving_latency(w, 0.5 * cap, arr)
    ss = simulate_serving(w, 0.5 * cap, arr)
    assert abs(ss["p50"] - aa["p50"]) / aa["p50"] < 0.10


def test_serving_sim_saturation_contract():
    with pytest.raises(ValueError):
        simulate_serving_virtual(8, 0.0)
    out = simulate_serving_virtual(8, 9.0)      # rho > 1
    assert out["saturated"] and math.isinf(out["p50"])


# --------------------------------------------------------------------------- #
# Component decomposition
# --------------------------------------------------------------------------- #
def test_component_stats_decomposition():
    w = make_workload()
    res = simulate_batch(w)
    comps = res["components"]
    names = set(comps)
    assert {"arith_units", "query_units", "sorter", "internal_dram"} <= names
    assert sum(1 for n in names if n.startswith("ch")) == 2 * 8  # ch + dies
    for name, c in comps.items():
        assert 0.0 <= c["utilization"] <= 1.0 + 1e-9, name
        assert c["busy_time"] >= 0.0 and c["queue_delay"] >= 0.0, name
        assert c["busy_time"] + c["idle_time"] == pytest.approx(
            res["total"] * (8 if name.endswith(".dies") else 1)), name
    ctrl = res["controller"]
    assert ctrl["busy_time"] == pytest.approx(res["compute"], rel=1e-6)
    assert ctrl["stall_flash"] >= 0.0


def test_contention_shows_in_breakdown():
    """Starve the flash side: the channels saturate and the compute units
    go idle — the observability the closed form cannot express."""
    w = make_workload()
    ssd = dataclasses.replace(ssd_model.SSDConfig(), channels=1,
                              chips_per_channel=1)
    comps = simulate_batch(w, ssd)["components"]
    assert comps["ch0"]["utilization"] > 0.95
    assert comps["arith_units"]["utilization"] < 0.5


# --------------------------------------------------------------------------- #
# ServeDriver trace -> simulator replay
# --------------------------------------------------------------------------- #
def test_serve_trace_replays_exactly(small_index, cfg_fixed, small_reads):
    mapper = Mapper(small_index, cfg_fixed)
    sd = ServeDriver(mapper, chunk=4)
    for k, sig in enumerate(small_reads.signals):
        sd.submit(f"s{k % 3}", sig)
    sd.drain()
    kinds = [e[0] for e in sd.events]
    assert kinds.count("dispatch") == sd.n_chunks
    assert kinds.count("complete") == sd.n_chunks
    rep = replay_chunk_trace(sd.events, chunk_cost=sd.chunk_cost)
    assert rep["n_chunks"] == sd.n_chunks
    assert rep["max_drift"] == 0.0
    assert rep["n_reads_arrived"] == small_reads.signals.shape[0]
    assert rep["makespan"] == pytest.approx(sd.clock)
    assert 0.0 < rep["dispatch_busy"] <= 1.0


# --------------------------------------------------------------------------- #
# CostModel interface
# --------------------------------------------------------------------------- #
def test_get_model_registry():
    assert costmodel.get_model(None).name == "analytic"
    assert costmodel.get_model("analytic").name == "analytic"
    assert costmodel.get_model("sim").name == "sim"
    m = costmodel.SimModel()
    assert costmodel.get_model(m) is m
    with pytest.raises(ValueError, match="unknown cost model"):
        costmodel.get_model("mqsim")


def test_costmodel_backends_agree():
    w = make_workload()
    ana = costmodel.get_model("analytic")
    sim = costmodel.get_model("sim")
    for system in ssd_model.SYSTEMS:
        a = ana.system_latency_energy(system, w)
        s = sim.system_latency_energy(system, w)
        if system != "MARS":        # host baselines share the analytic path
            assert a == s
        else:
            assert abs(s["total"] - a["total"]) / a["total"] < 0.01
            assert abs(s["energy"] - a["energy"]) / a["energy"] < 0.01
            # dynamic energy is shared by construction; only the static
            # term follows the backend's clock
            assert s["energy_dynamic"] == pytest.approx(a["energy_dynamic"],
                                                        rel=1e-6)


def test_shed_signal_offered_load_and_delay():
    for m in (costmodel.get_model("analytic"), costmodel.get_model("sim")):
        # saturation by offered load alone
        assert m.shed_signal(8, 1.0, offered_load=16.0)
        # healthy: below saturation, small measured delays
        assert not m.shed_signal(8, 1.0, offered_load=2.0,
                                 queue_delays=(0.5, 1.0))
        # capacity loss: low offered load but tripped measured delays
        assert m.shed_signal(8, 1.0, offered_load=2.0,
                             queue_delays=(10.0,) * 8)
        # zero-load edge (no serving_latency_virtual blow-up)
        assert not m.shed_signal(8, 1.0, offered_load=0.0)


def test_driver_sheds_on_measured_queue_delay(small_index, cfg_fixed,
                                              small_reads):
    """A burst backlog stretches dispatch delays while the offered load
    stays below saturation — only the measured-delay term can see it."""
    sigs = small_reads.signals

    def run(**kw):
        mapper = Mapper(small_index, cfg_fixed)
        sd = ServeDriver(mapper, chunk=2, shed_window=64.0, **kw)
        trace = [(0.0, f"a{k}", sigs[k % sigs.shape[0]]) for k in range(24)]
        trace += [(9.0, f"b{k}", sigs[k % sigs.shape[0]]) for k in range(4)]
        sd.serve_trace(trace)
        return sd

    # load never saturates: 28 arrivals / 64-unit window << 2 reads/unit
    sd = run(shed=True, shed_delay_limit=2.0)
    assert sd.n_shed > 0
    calm = run(shed=True, shed_delay_limit=1e6)
    assert calm.n_shed == 0
    off = run(shed=False)
    assert off.n_shed == 0 and off.n_chunks == calm.n_chunks


# --------------------------------------------------------------------------- #
# Skewed traffic + hot-tile replication pricing
# --------------------------------------------------------------------------- #
def test_skew_factors_closed_form():
    # uniform traffic: no imbalance, replication buys nothing
    assert costmodel.skew_factors([5, 5, 5, 5]) == (1.0, 1.0)
    assert costmodel.skew_factors([5, 5, 5, 5], replicas=2) == (1.0, 1.0)
    # fully concentrated: factor = n_tiles; one replica halves it
    f, fr = costmodel.skew_factors([0, 0, 80, 0, 0, 0, 0, 0], replicas=1)
    assert f == 8.0 and fr == 4.0
    # replicas=0 leaves the replicated factor equal to the skewed one
    f, fr = costmodel.skew_factors([1, 9], replicas=0)
    assert f == fr == 2 * 0.9
    # replicating a cold tile cannot push the factor below uniform 1.0
    f, fr = costmodel.skew_factors([1, 1], replicas=2)
    assert f == 1.0 and fr == 1.0
    # tie-break: highest traffic first, then lowest tile id — the same
    # order HotTileCache._refresh_replicas pins replicas in
    f, fr = costmodel.skew_factors([4, 4, 4, 0], replicas=1, copies=2)
    assert fr == 4 * 4 / 12               # tile 0 halved; tiles 1,2 still hot
    # degenerate inputs price as uniform
    assert costmodel.skew_factors([]) == (1.0, 1.0)
    assert costmodel.skew_factors([0, 0, 0]) == (1.0, 1.0)
    with pytest.raises(ValueError, match="replicas"):
        costmodel.skew_factors([1], replicas=-1)
    with pytest.raises(ValueError, match="copies"):
        costmodel.skew_factors([1], copies=0)


def test_query_scale_default_is_bit_exact():
    w = make_workload()
    a = simulate_batch(w, n_stripes=4)
    b = simulate_batch(w, n_stripes=4, query_scale=1.0)
    assert a["event_log"] == b["event_log"]
    assert a["total"] == b["total"]
    with pytest.raises(ValueError, match="query_scale"):
        simulate_batch(w, query_scale=0.0)


@pytest.mark.parametrize("model", ["analytic", "sim"])
def test_skewed_serving_uniform_equals_batch_latency(model):
    """Degenerate identity: uniform traffic prices exactly like the plain
    batch on BOTH backends, and replication reports speedup 1."""
    w = make_workload()
    m = costmodel.get_model(model)
    out = m.skewed_serving(w, [7, 7, 7, 7], replicas=2)
    assert out["factor"] == out["factor_replicated"] == 1.0
    assert out["replication_speedup"] == 1.0
    assert out["total"] == out["total_replicated"] == m.latency(w)["total"]


@pytest.mark.parametrize("model", ["analytic", "sim"])
def test_skewed_serving_prices_replication_win(model):
    """Hot-bucket skew costs; replicating the hot tiles wins it back —
    monotonically in K on both backends."""
    w = make_workload()
    m = costmodel.get_model(model)
    traffic = [100, 80, 8, 8, 8, 8, 8, 8]        # two hot tiles + cold tail
    base = m.latency(w)["total"]
    totals = []
    for k in (0, 1, 2):
        out = m.skewed_serving(w, traffic, replicas=k)
        assert out["total"] > base               # skew always costs
        assert out["replication_speedup"] >= 1.0
        totals.append(out["total_replicated"])
        assert out["total"] == totals[0]         # K only moves the repl arm
    assert totals[0] > totals[1] > totals[2]     # each replica helps here
    assert m.skewed_serving(w, traffic, replicas=1)["replication_speedup"] > 1


def test_skewed_serving_backends_agree():
    """Calibration: the DES twin agrees with the closed form to <1% on the
    default (no-contention) config, skewed or not."""
    w = make_workload()
    ana = costmodel.get_model("analytic")
    sim = costmodel.get_model("sim")
    for traffic, k in (([1, 1, 1, 1], 0), ([90, 5, 5, 0], 0),
                       ([90, 5, 5, 0], 1), ([50, 30, 10, 10], 2)):
        a = ana.skewed_serving(w, traffic, replicas=k)
        s = sim.skewed_serving(w, traffic, replicas=k)
        assert s["factor"] == a["factor"]
        assert s["factor_replicated"] == a["factor_replicated"]
        for key in ("total", "total_replicated"):
            assert abs(s[key] - a[key]) / a[key] < 0.01, (traffic, k, key)


def test_skewed_serving_consumes_cache_histogram(small_index, cfg_fixed,
                                                 small_reads):
    """End to end: HotTileCache.tile_traffic() is valid input — the
    measured skew of a real tiered run prices on both backends."""
    m = Mapper(small_index, cfg_fixed, backend="tiered", tiles=8,
               cache_slots=2, cache_replicas=2)
    m.map_signals(small_reads.signals, chunk=4)
    traffic = m.cache.tile_traffic()
    assert traffic.sum() > 0
    w = make_workload(1_000)
    for model in ("analytic", "sim"):
        out = costmodel.get_model(model).skewed_serving(
            w, traffic, replicas=m.cache.n_replicas)
        assert out["n_tiles"] == 8 and out["replicas"] == 2
        assert out["factor"] >= out["factor_replicated"] >= 1.0
        assert math.isfinite(out["total"])
        assert out["total"] >= out["total_replicated"]
