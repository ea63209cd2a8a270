"""GPipe-style pipeline parallelism over ``shard_map`` + ``ppermute``.

The production 40-cell grid uses DP x TP (x EP) -- at 4k sequence on a v5e
pod that layout dominates.  PP is provided for deeper-than-HBM models and
exercised by tests on an 8-device host mesh: layers are stacked per stage,
microbatches stream through the stage axis with collective_permute hops,
and the schedule is the standard (S + M - 1)-slot GPipe loop.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def pipeline_forward(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,          # leaves with leading [n_stages] dim
    x: jnp.ndarray,             # (n_micro, micro_batch, ...)
    *,
    mesh: Mesh,
    stage_axis: str = "stage",
) -> jnp.ndarray:
    """Runs x through n_stages sequential stages, microbatch-pipelined.

    stage_fn(params_for_stage, micro) -> micro  (same shape)
    Returns outputs in microbatch order, shape == x.shape.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    assert n_micro % n_stages == 0, "microbatches must divide stages for this schedule"

    def per_stage(params, xs):
        # params: this stage's slice (leading dim 1); xs: all microbatches
        # (already replicated along the stage axis -- simple reference
        # schedule; a production variant would scatter microbatches)
        idx = jax.lax.axis_index(stage_axis)
        p = jax.tree.map(lambda a: a[0], params)
        total_slots = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def slot(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (if any)
            mb = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(t < n_micro, xs[mb], jnp.zeros_like(xs[0]))
            cur = jnp.where(idx == 0, inject, buf)
            # every stage processes its current slot
            y = stage_fn(p, cur)
            # last stage emits microbatch (t - (n_stages-1))
            out_i = t - (n_stages - 1)
            valid = (out_i >= 0) & (idx == n_stages - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_i, 0, n_micro - 1), 0),
                lambda o: o, outs)
            # shift activations down the pipe
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return buf, outs

        buf, outs = jax.lax.fori_loop(0, total_slots, slot, (buf, outs))
        # only the last stage holds real outputs; broadcast them back
        outs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis)
        return outs

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(stage_axis), P()),      # params split by stage; x replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)


def make_mlp_stage(d: int):
    """Toy stage for tests/examples: y = gelu(x @ w1) @ w2."""

    def stage_fn(p, x):
        return jax.nn.gelu(x @ p["w1"]) @ p["w2"]

    def init(key, n_stages):
        k1, k2 = jax.random.split(key)
        s = 1.0 / np.sqrt(d)
        return {
            "w1": jax.random.normal(k1, (n_stages, d, d)) * s,
            "w2": jax.random.normal(k2, (n_stages, d, d)) * s,
        }

    return stage_fn, init
