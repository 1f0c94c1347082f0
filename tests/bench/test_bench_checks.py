"""The comparison that decides ``correct``, at a size a CPU test holds.

Drives whole runs of the harness past its look for a chip (a test-sized
genome on the reference plan, in both a closed-loop and an open-loop
cell): the program as configured comes out correct; the control (the
program's signal one precision lower, Q3.4 for Q7.8) and each fault a cell
can have, planted under the timed path, come out not correct.
"""
import gc
import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
sys.path.insert(0, str(ROOT))

from bench import control, harness  # noqa: E402

SECONDS = 0.5
LOOPS = {"closed": ("tiny_batch", "sarscov2_d1.batch"),
         "open": ("tiny_served", "sarscov2_d1.served")}


class FaultyMapper:
    """A stand-in for the mapper whose chunk program is broken in one way:

    ``stale``  every chunk after the first returns the first one's output;
    ``half``   the second half of the chunk's reads is left out (unmapped,
               zero, and dropped from the counters);
    ``alter``  the first read's mapping position is moved by one.
    """

    def __init__(self, mapper, fault: str):
        self.mapper, self.fault, self.cfg = mapper, fault, mapper.cfg

    def chunk_fn(self):
        fn, fault, first = self.mapper.chunk_fn(), self.fault, []

        def broken(sig, n_valid):
            if fault == "stale":
                out = fn(sig, n_valid)
                if not first:
                    first.append(out)
                return first[0]
            if fault == "half":
                keep = n_valid // 2
                out = fn(sig, keep)
                return out._replace(t_start=out.t_start.at[keep:].set(0),
                                    score=out.score.at[keep:].set(0.0))
            if fault == "alter":
                out = fn(sig, n_valid)
                return out._replace(t_start=out.t_start.at[0].add(1))
            raise ValueError(f"unknown fault {fault!r}")
        return broken


def _cell(loop):
    mix, name = LOOPS[loop]
    return harness.Cell(
        name=name, chips=1, spec=harness.load_spec(ROOT),
        config=json.loads((DATA / "tiny_config.json").read_text()),
        traffic=json.loads((DATA / f"{mix}.json").read_text()))


def _run(loop, seed, **kw):
    return harness.run(_cell(loop), seed, SECONDS, False, time.perf_counter(),
                       **kw)


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_program_is_correct(loop):
    r = _run(loop, 2 ** 31 + 3)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert all(v["limit"] == 0 for v in r["checks"].values())


@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_control_is_not_correct(loop):
    r = _run(loop, 5, program_params=control.CONTROL)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("fault", ["stale", "half", "alter"])
@pytest.mark.parametrize("loop", sorted(LOOPS))
def test_fault_is_not_correct(loop, fault):
    r = _run(loop, 9, wrap=lambda m: FaultyMapper(m, fault))
    assert not r["correct"], (fault, r["checks"])


def test_refused_reads_fail_but_stay_correct(monkeypatch):
    """Reads the serving driver refuses (its bounded queue) count as failed
    and leave ``correct`` alone; only admitted reads must be answered."""
    make = harness.stamped_serve_driver

    def small_queue(mapper, chunk):
        sd = make(mapper, chunk)
        sd.max_queue = 4
        return sd

    monkeypatch.setattr(harness, "stamped_serve_driver", small_queue)
    cell = _cell("open")
    cell.traffic = dict(cell.traffic, rate_per_s=400)
    r = harness.run(cell, 11, SECONDS, False, time.perf_counter())
    assert r["failed"] > 0
    assert r["correct"], r["checks"]


def test_pad_rows_count_only_the_window(monkeypatch):
    """The served window's chunk and padding counts leave out the chunks
    served while arrivals settle before it."""
    made = []
    make = harness.stamped_serve_driver

    def keep(mapper, chunk):
        made.append(make(mapper, chunk))
        return made[-1]

    monkeypatch.setattr(harness, "stamped_serve_driver", keep)
    cell = _cell("open")
    setup = harness.set_up(cell, 13)
    win = harness.run_window(cell, setup, SECONDS, 13)
    sd = made[-1]
    assert 0 < win.extra["n_chunks"] < sd.n_chunks
    assert win.extra["n_pad_rows"] <= sd.n_pad_rows


def test_stall_watch_records_waits():
    """A gap past the threshold is kept with the CPU time spent in it; a
    sleep spends almost none.  Closing unhooks the collector callback."""
    w = harness.StallWatch(threshold_s=0.05)
    w.tick()
    time.sleep(0.2)
    w.tick()
    w.tick()
    gaps = w.close(0.0)
    assert len(gaps) == 1
    assert gaps[0]["gap_s"] >= 0.2 and gaps[0]["cpu_s"] < 0.1
    assert w._on_gc not in gc.callbacks


@pytest.mark.parametrize("lat, want", [(None, None), ([], None),
                                      (list(range(1, 101)), 99.01)])
def test_latency_p99_reader(lat, want):
    extra = {} if lat is None else {"latency_ms": np.array(lat, float)}
    win = harness.Window(start=0.0, seconds=1.0, rows=np.zeros(0, int),
                         out={}, counters=[], counter_rows=[], attempted=0,
                         failed=0, extra=extra)
    got = harness.metric_reader("serve.latency_p99_ms")({"window": win})
    assert got == (None if want is None else pytest.approx(want))
