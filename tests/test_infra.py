"""Infrastructure tests: signal reader, HLO analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import hlo
from repro.signal import reader as reader_lib


def test_signal_reader_roundtrip(tmp_path):
    rng = np.random.default_rng(0)
    sig = rng.normal(100, 15, (10, 64)).astype(np.float32)
    f = tmp_path / "x.mars"
    reader_lib.write_signals(f, sig)
    rd = reader_lib.SignalReader(f, chunk=4)
    chunks = list(rd)
    assert [c[0] for c in chunks] == [0, 1, 2]
    assert chunks[-1][1] == 2                      # valid reads in tail
    got = np.concatenate([c[2][:c[1]] for c in chunks])
    np.testing.assert_allclose(got, sig, atol=0.02)


def test_signal_reader_resume(tmp_path):
    rng = np.random.default_rng(1)
    sig = rng.normal(100, 15, (12, 32)).astype(np.float32)
    f = tmp_path / "y.mars"
    reader_lib.write_signals(f, sig)
    rd = reader_lib.SignalReader(f, chunk=4, start_chunk=2)
    chunks = list(rd)
    assert [c[0] for c in chunks] == [2]


def test_hlo_analyzer_counts_loop_trips():
    """The motivating experiment: a 10-step scanned matmul must report 10x
    the flops of a single matmul (XLA's own cost_analysis reports 1x)."""
    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)

    def single(x, w):
        return x @ w

    def scanned(x, w):
        def body(c, _):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    t1 = jax.jit(single).lower(x, w).compile().as_text()
    t10 = jax.jit(scanned).lower(x, w).compile().as_text()
    f1 = hlo.analyze(t1)["flops"]
    f10 = hlo.analyze(t10)["flops"]
    assert f1 == pytest.approx(2 * 128**3, rel=0.01)
    assert f10 == pytest.approx(10 * f1, rel=0.05)


def test_hlo_analyzer_dot_flops_with_resolved_operands():
    a = jax.ShapeDtypeStruct((64, 256), jnp.float32)
    b = jax.ShapeDtypeStruct((256, 32), jnp.float32)
    text = jax.jit(lambda a, b: a @ b).lower(a, b).compile().as_text()
    res = hlo.analyze(text)
    assert res["flops"] == pytest.approx(2 * 64 * 256 * 32, rel=0.01)
