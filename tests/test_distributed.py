"""Multi-device behaviour (8 virtual CPU devices via subprocess): sharded
training, checkpoint/restore with resharding (elastic), int8 collectives,
pipeline stages."""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]


def _run(script: str, devices: int = 8, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_loss_decreases():
    _run("""
import jax, jax.numpy as jnp
from repro.configs import get_config
from repro.configs.base import ShapeSpec
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.train import optimizer as opt, steps as S
from repro.data.tokens import TokenStream
mesh = make_mesh((2,2,2), ("pod","data","model"))
cfg = get_config("qwen3-4b").reduced()
step, jit_for, sh = S.make_train_step(cfg, mesh, opt.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=20))
fn = jit_for(S.make_batch_abstract(cfg, ShapeSpec("t", 32, 4, "train")))
params = jax.device_put(M.init_params(cfg, jax.random.key(0)), sh["params"])
ostate = jax.jit(opt.init_state, out_shardings=sh["opt"])(params)
ts = TokenStream(cfg.vocab, 4, 32)
losses = []
for _ in range(5):
    b = {k: jnp.asarray(v) for k, v in ts.next_batch().items()}
    params, ostate, m = fn(params, ostate, b)
    losses.append(float(m["loss"]))
assert losses[-1] < losses[0], losses
print("ok", losses)
""")


def test_sharded_prefill_decode():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.distributed import sharding as sh
from repro.train import steps as S
mesh = make_mesh((4,2), ("data","model"))
cfg = get_config("h2o-danube-1.8b").reduced()
params_abs = M.abstract_params(cfg)
p_sh = sh.param_shardings(params_abs, mesh)
params = jax.device_put(M.init_params(cfg, jax.random.key(0)), p_sh)
B, Sq, T = 4, 16, 32
cache = M.init_cache(cfg, B, T)
cache = jax.device_put(cache, sh.cache_shardings(jax.eval_shape(lambda: M.init_cache(cfg, B, T)), mesh))
toks = jax.random.randint(jax.random.key(1), (B, Sq+1), 0, cfg.vocab)
logits_full, _, _ = M.forward(params, toks, cfg)
_, cache = M.prefill(params, toks[:, :Sq], cfg, cache=cache)
got, _ = M.decode_step(params, toks[:, Sq:], cfg, cache=cache, cache_index=Sq)
np.testing.assert_allclose(np.asarray(got), np.asarray(logits_full[:, -1, :]), rtol=5e-2, atol=5e-2)
print("ok")
""")


def test_checkpoint_restore_and_elastic_reshard():
    _run("""
import tempfile, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.distributed import sharding as sh
from repro.train import checkpoint as ckpt

cfg = get_config("qwen3-4b").reduced()
mesh8 = make_mesh((4,2), ("data","model"))
params = jax.device_put(M.init_params(cfg, jax.random.key(0)),
                        sh.param_shardings(M.abstract_params(cfg), mesh8))
with tempfile.TemporaryDirectory() as d:
    ckpt.save(d, 7, params, data_state=dict(seed=1, step=7))
    assert ckpt.latest_step(d) == 7
    # restore onto a DIFFERENT mesh (elastic: 8 -> 4 devices used)
    mesh4 = make_mesh((2,2), ("data","model"))
    restored, step, ds, _ = ckpt.restore(
        d, M.abstract_params(cfg),
        shardings=sh.param_shardings(M.abstract_params(cfg), mesh4))
    assert step == 7 and ds["step"] == 7
    a = jax.tree_util.tree_leaves(params)[3]
    b = jax.tree_util.tree_leaves(restored)[3]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # corruption detection
    import pathlib
    f = sorted(pathlib.Path(d).glob("step_*/arr_00000.npy"))[0]
    f.write_bytes(b"garbage")
    try:
        ckpt.restore(d, M.abstract_params(cfg))
        raise SystemExit("corruption not detected")
    except IOError:
        pass
print("ok")
""")


def test_int8_psum_collective():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.launch.mesh import make_mesh
from repro.distributed.collectives import psum_int8
mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.key(0), (8, 1024), jnp.float32)
def body(xl):
    return psum_int8(xl[0], "data")[None]
got = shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
want = x.sum(axis=0)
err = np.abs(np.asarray(got[0]) - np.asarray(want))
rel = err.max() / (np.abs(np.asarray(want)).max() + 1e-9)
assert rel < 0.02, rel       # int8 block-scaled: ~1% worst-case error
print("ok", rel)
""")


def test_pipeline_stages():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.distributed.pipeline import pipeline_apply
mesh = make_mesh((4,), ("pipe",))
# stage transform: y = x @ W_s (per-stage weight)
W = jax.random.normal(jax.random.key(0), (4, 16, 16)) * 0.3
x = jax.random.normal(jax.random.key(1), (8, 16))
def fn_stage(w, xb):
    return jnp.tanh(xb @ w)
got = pipeline_apply(fn_stage, x, W, mesh, n_micro=4, axis="pipe")
want = x
for s in range(4):
    want = jnp.tanh(want @ W[s])
np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
print("ok")
""")


def test_quantized_collective_unit():
    """Single-device quantizer roundtrip properties."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.distributed.collectives import (dequantize_int8,
                                               quantize_int8,
                                               quantize_kv_int8,
                                               dequantize_kv_int8)
    x = jax.random.normal(jax.random.key(0), (1000,), jnp.float32) * 5
    q, s, n = quantize_int8(x)
    y = dequantize_int8(q, s, n, x.shape)
    err = np.abs(np.asarray(x - y)).max()
    scale_max = float(np.asarray(s).max())
    assert err <= scale_max * 0.51 + 1e-6
    kv = jax.random.normal(jax.random.key(1), (2, 8, 4, 64), jnp.bfloat16)
    qkv, sc = quantize_kv_int8(kv)
    back = dequantize_kv_int8(qkv, sc)
    rel = np.abs(np.asarray(back, np.float32) - np.asarray(kv, np.float32)).max()
    assert rel < 0.1
