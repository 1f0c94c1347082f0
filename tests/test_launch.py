"""The launchers end to end at D1, the persistent compile cache's
directory, and the mesh axes the sharded path depends on.

Every launcher calls ``compile_cache.enable()``; the cases point
``JAX_COMPILATION_CACHE_DIR`` at a temporary directory so nothing is
written into the checkout.
"""
import contextlib
import io
import json
import pathlib
import re
import shlex

import jax
import pytest
from jax.sharding import AxisType

from repro.core import driver
from repro.launch import compile_cache, map_reads, serve_rsga
from repro.launch.mesh import make_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]
MAP_ARGS = ["--dataset", "D1", "--reads", "64", "--chunk", "32"]


@pytest.fixture
def cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))


def _map(workdir, *extra):
    """Run map_reads and keep its progress log (the launcher deletes it
    when the run completes).  Returns (accuracy, stdout, PAF lines, log
    records)."""
    paf = workdir / "out.paf"
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp, contextlib.redirect_stdout(out):
        mp.setattr(driver.ProgressLog, "clear", lambda self: None)
        acc = map_reads.main(MAP_ARGS + ["--workdir", str(workdir),
                                         "--out", str(paf), *extra])
    log = workdir / "progress_ms_fixed.jsonl"
    records = [json.loads(line) for line in log.read_text().splitlines()]
    return acc, out.getvalue(), paf.read_text().splitlines(), records


@pytest.fixture(scope="module")
def reference_run(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax")))
        return _map(tmp_path_factory.mktemp("map_ref"))


def _check_run(acc, stdout, paf, records):
    assert {"precision", "recall", "f1"} <= set(acc)
    rows = [row for rec in records for row in rec["rows"]]
    assert [rec["next"] for rec in records] == [1, 2]
    assert len(rows) == 64
    assert len(paf) == sum(mapped for _, _, mapped in rows) > 0
    assert "[map] 64 reads" in stdout


def test_map_reads_reference_plan(reference_run):
    _check_run(*reference_run)
    assert "fused_cheap=False" in reference_run[1]


def test_map_reads_pallas_plan(tmp_path, cache_dir, reference_run):
    """--use-kernels engages the fused kernel and maps every read as the
    reference plan does."""
    run = _map(tmp_path, "--use-kernels")
    _check_run(*run)
    assert "fused_cheap=True" in run[1]
    assert run[2] == reference_run[2]
    assert run[0] == reference_run[0]


def test_map_reads_resumes_after_first_chunk(tmp_path, cache_dir,
                                             reference_run):
    """A job killed after its first chunk leaves the log's first line; the
    rerun continues at chunk 1 and writes the uninterrupted run's PAF."""
    (tmp_path / "progress_ms_fixed.jsonl").write_text(
        json.dumps(reference_run[3][0]) + "\n")
    acc, stdout, paf, records = _map(tmp_path)
    assert "[resume] continuing at chunk 1" in stdout
    assert "[map] 64 reads" in stdout
    assert paf == reference_run[2]
    assert acc == reference_run[0]
    assert records == reference_run[3]


@pytest.mark.parametrize("model", ["analytic", "sim"])
def test_serve_rsga_cost_models(capsys, cache_dir, model):
    reports = serve_rsga.main(["--dataset", "D1", "--streams", "3",
                               "--reads-per-stream", "4", "--chunk", "8",
                               "--model", model])
    assert sorted(reports) == ["s0", "s1", "s2"]
    assert sum(r.n_reads for r in reports.values()) == 12
    assert f"-SSD array [{model}]" in capsys.readouterr().out


def test_readme_serving_command_runs(capsys, cache_dir):
    """The README's serving example, flags as the README gives them."""
    text = (ROOT / "README.md").read_text(encoding="utf-8")
    cmds = [b for b in re.findall(r"```sh\n(.*?)```", text, flags=re.S)
            if "serve_rsga" in b]
    assert len(cmds) == 1
    argv = shlex.split(cmds[0].replace("\\\n", " "))
    argv = argv[argv.index("repro.launch.serve_rsga") + 1:]
    reports = serve_rsga.main(argv)
    assert reports and "[tenant t1]" in capsys.readouterr().out


def test_compile_cache_follows_environment(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        want = str(ROOT / ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_make_mesh_axes_are_auto():
    """JAX 0.9 makes Explicit axes by default; the sharded chunk program
    places its arguments by NamedSharding and needs Auto ones."""
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)
