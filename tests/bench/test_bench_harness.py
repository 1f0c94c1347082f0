"""The benchmark's definition and its generator, without a chip.

``BENCHMARK.json`` resolves to its files and keeps to the benchmark's
format; the copied generator reproduces a frozen digest; the entry point
refuses to run without a TPU.
"""
import hashlib
import json
import pathlib
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_text_ok(w) for w in SPEC["command"])
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
            if "unit" in entry:
                assert UNIT.match(entry["unit"]), entry["unit"]
                assert entry["better"] in ("lower", "higher")
    metric_names = [n for is_metric, n in names if is_metric]
    assert len(metric_names) == len(set(metric_names))
    for group in ("configs", "workloads"):
        ns = [e["name"] for e in SPEC[group]]
        assert len(ns) == len(set(ns))


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used
        assert _text_ok(c["source"]) and _text_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k)
            assert not k.endswith(("_dim", "_rank"))
        assert "Table 2" in c["source"]
        assert body["params"]["mode"] == body["mode"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = {x["name"]: x for x in SPEC["workloads"]}[cell]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and _text_ok(w["why"])
    assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    c = harness.load_cell(cell, ROOT)
    assert c.traffic["loop"] in ("closed", "open")
    harness.mars_config(c.params)
    e2e = [m["name"] for m in harness.end_to_end_metrics(SPEC, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.per_layer_metrics(SPEC, cell)
    assert layer
    for m in layer:
        assert callable(harness.metric_reader(m["name"]))


def test_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    pairs = {(w["config"], w["traffic"]) for w in SPEC["workloads"]}
    assert len(pairs) == len(SPEC["workloads"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and _text_ok(m["layer"])
        moved = next(x for x in SPEC["end_to_end"] if x["name"] == m["moves"])
        for cell in m.get("workloads", []):
            assert cell in moved.get("workloads", CELLS)
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.setdefault(m["layer"].split(" (")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_generator_digest():
    """Seed 0's first reads, arrivals and genome: frozen, so that a change
    to the program's own simulator cannot change the benchmark's inputs."""
    g = traffic.make_genome(29903, traffic.rng_for(0, traffic.GENOME))
    pool = traffic.make_pool(
        g, {"pool_reads": 16, "junk_frac": 0.125, "offtarget_frac": 0.25,
            "background_len": 50000}, 1024, 0)
    h = hashlib.sha256()
    for a in (pool.signals, pool.true_pos.astype(np.int64),
              pool.true_strand.astype(np.int8),
              pool.n_bases.astype(np.int64), pool.kind.astype(np.int8)):
        h.update(np.ascontiguousarray(a).tobytes())
    assert h.hexdigest() == ("6e14c5859248d0d5d95383bad5722b10"
                             "6629d5c7722973034cc2059b6d8f3d3d")
    assert list(pool.kind) == [0, 0, 0, 0, 0, 0, 0, 2, 0, 0, 0, 1, 1, 2, 2, 2]
    due, ch, rows = traffic.arrivals(
        {"rate_per_s": 100, "channels": 8, "pool_reads": 16}, 1.0, 0)
    assert len(due) == 98
    assert hashlib.sha256(due.tobytes() + ch.tobytes()
                          + rows.tobytes()).hexdigest() == (
        "0ea999b95d98d87e3d0b573304eab45350409bf6127b8597aa8781d16b96a044")


def test_pool_mix_is_fixed_per_seed():
    """Every seed gives the same counts of each kind of read."""
    g = traffic.make_genome(5000, traffic.rng_for(1, traffic.GENOME))
    mix = {"pool_reads": 50, "junk_frac": 0.08, "offtarget_frac": 0.5,
           "background_len": 5000}
    for seed in (1, 2 ** 31 + 11):
        pool = traffic.make_pool(g, mix, 256, seed)
        assert np.bincount(pool.kind, minlength=3).tolist() == [21, 4, 25]


def test_run_refuses_without_tpu():
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "HOME": str(ROOT)}
    r = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr
