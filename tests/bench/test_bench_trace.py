"""The trace reduction, the roofline counts and the F1 arithmetic, without
a chip: on hand-built traces, on a small trace recorded on the chip
(``data/recorded_trace.json``) and on hand-worked shapes."""
import json
import math
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import roofline, tracing, traffic  # noqa: E402


def _record(ops, spans):
    return {"ops": {"/device:TPU:0": ops}, "spans": spans}


# window 0..100; ops overlap at 10..30 and 25..40, one sits past the end
OPS = [["a", 10, 20], ["b", 25, 15], ["a", 60, 10], ["c", 95, 20]]
SPANS = [["bench.window", 0, 100], ["driver.stream_map", 0, 100],
         ["bench.chunk_source", 40, 15], ["serve.drain", 70, 30]]


def test_busy_union_and_gaps():
    assert tracing.merged(OPS, 0, 100) == [(10, 40), (60, 70), (95, 100)]
    assert tracing.busy_ns(OPS, 0, 100) == 30 + 10 + 5
    assert tracing.idle_gaps(OPS, 0, 100) == [(0, 10), (40, 60), (70, 95)]
    assert tracing.idle_gaps([], 0, 100) == [(0, 100)]
    assert tracing.busy_ns(OPS, 12, 28) == 16


def test_gap_attribution():
    # innermost open span at each gap's midpoint; the window never counts
    assert tracing.gap_owner((0, 10), SPANS) == "driver.stream_map"
    assert tracing.gap_owner((40, 60), SPANS) == "bench.chunk_source"
    assert tracing.gap_owner((70, 95), SPANS) == "serve.drain"
    assert tracing.gap_owner((0, 10), SPANS[:1]) == "none"


def test_op_totals_and_summary():
    # b starts inside a (25..30 nested), so a's self time loses 5 ns
    assert tracing.op_totals(OPS, 0, 100) == {"a": 25, "b": 15, "c": 5}
    nested = [["loop", 0, 100], ["body", 10, 20], ["body", 50, 20],
              ["inner", 55, 5]]
    assert tracing.op_totals(nested, 0, 100) == {"loop": 60, "body": 35,
                                                "inner": 5}
    assert tracing.op_name("%cheap_fused_fixed.1 = (s32[8]) custom-call("
                           "s32[8] %x), custom_call_target=\"t\"") == (
        "cheap_fused_fixed.1")
    s = tracing.summarize(_record(OPS, SPANS))
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(45e-9)
    assert s["device_ops"][0] == ["a", pytest.approx(25e-9)]
    idle = dict(s["idle_gaps"])
    assert idle == {"driver.stream_map": pytest.approx(10e-9),
                    "bench.chunk_source": pytest.approx(20e-9),
                    "serve.drain": pytest.approx(25e-9)}
    assert sum(idle.values()) + s["busy_s"] == pytest.approx(s["window_s"])
    got = tracing.op_seconds(_record(OPS, SPANS), lambda op: op[0] == "c")
    assert got == pytest.approx(5e-9)
    assert tracing.op_seconds(_record(OPS, SPANS), lambda op: False) is None


def test_two_devices_average():
    rec = {"ops": {"/device:TPU:0": [["x", 0, 50]],
                   "/device:TPU:1": [["x", 0, 100]]},
           "spans": [["bench.window", 0, 100]]}
    s = tracing.summarize(rec)
    assert s["busy_s"] == pytest.approx(75e-9)


def test_window_must_be_unique():
    with pytest.raises(ValueError):
        tracing.window(_record(OPS, SPANS + [["bench.window", 0, 5]]))


def test_recorded_trace():
    """Two chunks of the D1 batch cell's trace, recorded on the chip and
    cut to the neutral record; ``expect`` holds the busy time found by an
    independent sweep over the operations' endpoints when it was cut."""
    rec = json.loads((DATA / "recorded_trace.json").read_text())
    expect = rec.pop("expect")
    s = tracing.summarize(rec)
    assert s["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-9)
    assert s["window_s"] == pytest.approx(expect["window_s"], rel=1e-9)
    assert s["busy_s"] <= s["window_s"]
    assert [n for n, _ in s["device_ops"]][:3] == expect["top_ops"]
    assert {n for n, _ in s["idle_gaps"]} <= {
        sp[0] for sp in rec["spans"]} | {"none"}


def test_load_reads_host_spans(tmp_path):
    """``load`` on a trace the profiler writes here: no TPU planes, and the
    harness's spans are found on the host plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.chunk_source"):
            f(jnp.ones(8)).block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    rec = tracing.load(str(path))
    names = [s[0] for s in rec["spans"]]
    assert names.count(tracing.WINDOW_SPAN) == 1
    assert "bench.chunk_source" in names
    assert rec["ops"] == {}
    with pytest.raises(ValueError):
        tracing.summarize(rec)


D1 = dict(signal_len=1024, max_events=192, max_hits_per_seed=16,
          peak_window=3, seed_width=7)


def test_roofline_counts_by_hand():
    # bytes: 1024*4 signal + 192*(8 + 16*8) probes + 2*192*16*4 outputs
    #        + 9*4 counters
    assert roofline.cheap_bytes(D1) == 4096 + 26112 + 24576 + 36
    # ops: 2*1024*10 selects + 1024*(4+16+8+2) per sample + 192 divides
    #      + 10*192+72 quantize + 192*(14+8) seeding + 192*16*14 slots
    assert roofline.cheap_ops(D1) == (20480 + 30720 + 192 + 1992 + 4224
                                      + 43008)
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    t, bound = roofline.least_seconds(D1, 1000, peaks)
    assert bound == "memory"
    assert t == pytest.approx(1000 * 54820 / 819e9)
    tiny = dict(D1, max_hits_per_seed=1, max_events=1)
    t2, bound2 = roofline.least_seconds(tiny, 1, {"hbm_bytes_per_s": 1e20,
                                                  "bf16_flops_per_s": 1.0})
    assert bound2 == "compute" and t2 == roofline.cheap_ops(tiny)


def test_peaks_table():
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in table["source"]


def test_f1_by_hand():
    n_ev = 1000
    # read 0 right (forward, 50 events off), 1 right (reverse: forward
    # start 999 - (500 + 100 - 1) = 400), 2 wrong place, 3 junk mapped,
    # 4 mappable but unmapped, 5 junk unmapped
    t_start = np.array([100, n_ev + 500, 700, 5, 0, 0])
    mapped = np.array([1, 1, 1, 1, 0, 0], bool)
    true_pos = np.array([150, 400, 100, -1, 300, -1])
    strand = np.array([0, 1, 0, 0, 0, 0])
    mappable = np.array([1, 1, 1, 0, 1, 0], bool)
    n_bases = np.array([200, 100, 200, 0, 200, 0])
    acc = traffic.score_accuracy(t_start, mapped, true_pos, strand, mappable,
                                 n_bases, n_ev)
    assert (acc["tp"], acc["fp"], acc["fn"]) == (2, 2, 1)
    assert acc["precision"] == pytest.approx(0.5)
    assert acc["recall"] == pytest.approx(2 / 3)
    assert acc["f1"] == pytest.approx(2 * 0.5 * (2 / 3) / (0.5 + 2 / 3))
    none = traffic.score_accuracy(t_start[:0], mapped[:0], true_pos[:0],
                                  strand[:0], mappable[:0], n_bases[:0], n_ev)
    assert none["f1"] == 0.0 and not math.isnan(none["precision"])
