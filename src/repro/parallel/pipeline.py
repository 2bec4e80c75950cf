"""GPipe-style pipeline parallelism over a mesh axis (optional feature).

``pipeline_apply`` runs ``n_stages`` stage functions over microbatches with
the classic fill/drain schedule, expressed as a shard_map over the ``pipe``
axis: every device holds one stage's params; microbatch activations move
stage→stage with ``ppermute`` (the same neighbour-only pattern as SPLIM's
ring broadcast — DESIGN.md §2). Bubble fraction = (S-1)/(M+S-1).

The production dry-runs use DP×TP (PP off by default); this module is the
composable PP building block, exercised by tests/test_pipeline.py on 8 fake
devices.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_stacked, x_microbatches,
                   mesh: Mesh, axis: str = "pipe"):
    """Run a homogeneous-stage pipeline.

    stage_fn(params_slice, x) -> x      one stage's computation
    params_stacked: leaves (n_stages, ...) sharded over ``axis``
    x_microbatches: (n_micro, mb, ...) replicated input microbatches
    Returns (n_micro, mb, ...) outputs after all stages.
    """
    n_stages = mesh.shape[axis]

    def shard_fn(params_local, xs):
        # params_local: (1, ...) this stage's params; xs: (n_micro, mb, ...)
        p = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        total = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any); others use the buffer
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, xs[inject], buf)
            y = stage_fn(p, x_in)
            # valid iff this stage is processing microbatch m = t - stage
            m = t - stage
            valid = jnp.logical_and(m >= 0, m < n_micro)
            y = jnp.where(valid, y, buf)
            # last stage records its finished microbatch
            outs = jax.lax.cond(
                jnp.logical_and(valid, stage == n_stages - 1),
                lambda o: o.at[jnp.clip(m, 0, n_micro - 1)].set(y),
                lambda o: o, outs)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), ()

        (buf, outs), _ = jax.lax.scan(
            tick, (jax.lax.pcast(buf, axis, to="varying"),
                   jax.lax.pcast(outs, axis, to="varying")),
            jnp.arange(total))
        # outs live on the last stage; broadcast to all for a replicated out
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    fn = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P()))
    return fn(params_stacked, x_microbatches)
