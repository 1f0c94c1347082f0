"""GPipe-style pipeline-parallel stage utility (optional mesh axis 'pipe').

The production dry-run mesh does not allocate a 'pipe' axis (scan-over-
layers + FSDP + TP covers the assigned shapes; DESIGN.md Section 6), but the
framework supports PP when the launcher is given a mesh with one:
microbatches flow through `n_stages` shard_map stages connected by
collective_permute, with the classic (n_micro + n_stages - 1) schedule.

Tested on small host meshes (tests/test_pipeline_pp.py).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(fn_stage: Callable, x: jnp.ndarray, stage_params,
                   mesh: Mesh, n_micro: int, axis: str = "pipe"):
    """Run `fn_stage(params_for_stage, micro_batch)` as a GPipe pipeline.

    x: (B, ...) global batch, split into n_micro microbatches along axis 0.
    stage_params: pytree with leading stage axis (n_stages, ...), sharded
    over `axis` so each device row holds its stage's weights.
    Returns fn's output with the same batch layout as x.
    """
    n_stages = mesh.shape[axis]
    assert x.shape[0] % n_micro == 0

    def stage_body(params_local, x_local):
        # params_local: (1, ...) this stage's params; x_local: full batch
        # (replicated over pipe axis — each stage computes every microbatch
        # but only its own stage transform, passing activations around the
        # ring).
        sid = jax.lax.axis_index(axis)
        p_own = jax.tree_util.tree_map(lambda t: t[0], params_local)
        micros = x_local.reshape(n_micro, -1, *x_local.shape[1:])
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(micros[0])
        outs = jnp.zeros_like(micros)

        def tick(carry, t):
            buf, outs = carry
            mb_idx = t - sid
            # stages 0 feeds new microbatches; others consume the permuted
            feed = micros[jnp.clip(mb_idx, 0, n_micro - 1)]
            cur = jnp.where(sid == 0, feed, buf)
            active = (mb_idx >= 0) & (mb_idx < n_micro)
            y = fn_stage(p_own, cur)
            y = jnp.where(active, y, cur)
            # last stage writes its finished microbatch
            outs = jax.lax.cond(
                active & (sid == n_stages - 1),
                lambda o: o.at[jnp.clip(mb_idx, 0, n_micro - 1)].set(y),
                lambda o: o, outs)
            # rotate activations stage i -> i+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs),
                                      jnp.arange(n_ticks))
        # broadcast the last stage's outputs to all rows so the result is
        # replicated over the pipe axis
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs.reshape(x_local.shape)

    spec_p = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(stage_body, mesh=mesh, in_specs=(spec_p, P()),
                       out_specs=P(), check_vma=False)
    return fn(stage_params, x)
