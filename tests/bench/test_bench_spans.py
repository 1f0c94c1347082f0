"""The reduction of the program's spans (``bench/spans.py``) and the three
readers built on it, without a chip: on hand-built records with answers
worked by hand, and on a slice of a served trace recorded on the chip
(``data/recorded_served_trace.json``)."""
import importlib.util
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import spans, tracing  # noqa: E402

READERS = ("driver.dispatch_fetch_idle_pct", "serve.pack_route_idle_pct",
           "serve.chunk_turnaround_ms")


def reader(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# One served cycle in a window of 0..100 ns.  The device runs 0..12 and
# 58..80; the host, inside the harness's drain, fetches chunk 0, routes
# it, packs chunk 1 and dispatches it.  The gap 12..58 runs through four
# spans; the gap 80..100 outlives the drain.
CYCLE_OPS = [["k", 0, 12], ["k", 58, 22]]
CYCLE_SPANS = [["bench.window", 0, 100], ["serve.drain", 8, 82],
               ["driver.fetch", 10, 20], ["serve.route", 30, 10],
               ["serve.pack", 40, 10], ["driver.dispatch", 50, 10]]


def _record(ops, sp):
    return {"ops": {"/device:TPU:0": ops}, "spans": sp}


def test_owners_timeline():
    assert spans.owners(CYCLE_SPANS) == [
        (8, 10, "serve.drain"), (10, 30, "driver.fetch"),
        (30, 40, "serve.route"),
        (40, 50, "serve.pack"), (50, 60, "driver.dispatch"),
        (60, 90, "serve.drain")]
    # a span nested in another takes its own time back from it, and the
    # outer span resumes after it
    nested = [["a", 0, 10], ["b", 2, 3], ["c", 20, 0]]
    assert spans.owners(nested) == [(0, 2, "a"), (2, 5, "b"), (5, 10, "a")]


def test_idle_split_across_spans():
    rec = _record(CYCLE_OPS, CYCLE_SPANS)
    got = spans.idle_by_span(rec, 0, 100)
    assert got == {"driver.fetch": 18, "serve.route": 10, "serve.pack": 10,
                   "driver.dispatch": 8, "serve.drain": 10, "none": 10}
    assert sum(got.values()) == 100 - tracing.busy_ns(CYCLE_OPS, 0, 100)
    # the midpoint rule gives the whole first gap to one span
    assert tracing.gap_owner((12, 58), CYCLE_SPANS) == "serve.route"


def test_idle_split_three_spans_two_devices():
    # a gap 5..25 crossing three spans on device 0; device 1 never idles
    sp = [["bench.window", 0, 30], ["x", 0, 10], ["y", 10, 10],
          ["z", 20, 10]]
    rec = {"ops": {"/device:TPU:0": [["k", 0, 5], ["k", 25, 5]],
                   "/device:TPU:1": [["k", 0, 30]]}, "spans": sp}
    assert spans.idle_by_span(rec, 0, 30) == {"x": 2.5, "y": 5.0, "z": 2.5}
    # window edges cut the gaps
    assert spans.idle_by_span(rec, 8, 22) == {"x": 1.0, "y": 5.0, "z": 1.0}


# Four chunks: the first is packed before the window opens and routed
# after; chunk 3 is routed after the window closes.
PAIR_SPANS = [["bench.window", 0, 100],
              ["serve.pack", -20, 5], ["serve.route", 2, 3],
              ["serve.pack", 20, 5], ["serve.pack", 40, 5],
              ["serve.route", 50, 4], ["serve.pack", 70, 5],
              ["serve.route", 75, 5], ["serve.route", 110, 5]]


def test_paired_by_order():
    rec = _record([], PAIR_SPANS)
    got = spans.paired(rec, "serve.pack", "serve.route")
    assert [(p[1], r[1]) for p, r in got] == [(-20, 2), (20, 50), (40, 75),
                                              (70, 110)]
    assert spans.paired(rec, "serve.pack", "serve.nothing") == []


def test_readers_by_hand():
    rec = _record(CYCLE_OPS, CYCLE_SPANS)
    ctx = {"record": rec}
    assert reader("driver.dispatch_fetch_idle_pct")(ctx) == pytest.approx(
        26.0)
    assert reader("serve.pack_route_idle_pct")(ctx) == pytest.approx(20.0)
    # turnaround: chunks 1-3 (34, 40, 45 ns); chunk 0 began before the
    # window
    pairs = _record([["k", 0, 100]], PAIR_SPANS)
    assert reader("serve.chunk_turnaround_ms")({"record": pairs}) == (
        pytest.approx(40e-6))


def test_readers_silent_without_program_spans():
    # what a program that opens none of these spans leaves in the trace:
    # the harness's own spans only
    harness_only = [["bench.window", 0, 100], ["serve.drain", 10, 80],
                    ["serve.submit", 0, 10]]
    ctx = {"record": _record(CYCLE_OPS, harness_only)}
    for name in READERS:
        assert reader(name)(ctx) is None


def test_recorded_served_trace():
    """141 ms of the served cell's trace, recorded on the chip and cut to
    the neutral record: the end of one chunk, packed before the slice,
    then one ``drain()`` of two chunks.  ``expect`` holds the values
    found when it was cut, by a sweep over every span and operation
    endpoint that shares no code with ``bench/spans.py``."""
    rec = json.loads((DATA / "recorded_served_trace.json").read_text())
    expect = rec.pop("expect")
    lo, hi = tracing.window(rec)
    idle = spans.idle_by_span(rec, lo, hi)
    for name, ns in expect["idle_ns"].items():
        assert idle.get(name, 0) == pytest.approx(ns, abs=0.5)
    assert sum(idle.values()) == pytest.approx(
        hi - lo - tracing.busy_ns(rec["ops"]["/device:TPU:0"], lo, hi),
        abs=0.5)
    ctx = {"record": rec}
    for name in READERS:
        assert reader(name)(ctx) == pytest.approx(expect[name], rel=1e-9)
