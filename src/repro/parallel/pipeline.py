"""GPipe-style pipeline parallelism over a 'stage' mesh axis.

For meshes with a ``stage`` axis, a scanned layer stack is split into S
contiguous stages; microbatches stream through with ``collective_permute``
hops between neighbours. This is the PP leg of the parallelism suite —
optional (the production dry-run mesh uses DP x TP; PP is exercised by
tests/test_pipeline.py on a small mesh) but required posture at 1000+ nodes
where a single TP domain cannot span the cluster.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

__all__ = ["pipeline_apply"]


def pipeline_apply(
    fn_stage: Callable,           # (stage_params, x) -> x
    stage_params,                 # leaves stacked along leading `stage` axis
    x: jax.Array,                 # (num_micro, micro_batch, ...) inputs
    mesh: Mesh,
    axis: str = "stage",
):
    """Run ``fn_stage`` as an S-stage GPipe pipeline over microbatches.

    x[m] is microbatch m; returns the stacked outputs. The schedule runs
    S + M - 1 ticks; each tick every stage processes one slot then passes it
    right (collective_permute), overlapping compute and communication.
    """
    s = mesh.shape[axis]
    m = x.shape[0]

    def per_stage(params, xs):
        stage = jax.lax.axis_index(axis)
        # strip the sharded leading stage axis from the params shard
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        xs = xs[0]  # the replicated microbatch stack
        ticks = s + m - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain); others use buf
            take = jnp.clip(t, 0, m - 1)
            inject = jax.lax.dynamic_index_in_dim(xs, take, keepdims=False)
            cur = jnp.where(stage == 0, jnp.where(t < m, inject, buf * 0),
                            buf)
            y = fn_stage(params, cur)
            # last stage emits microbatch (t - s + 1)
            emit_idx = jnp.clip(t - s + 1, 0, m - 1)
            emit = (stage == s - 1) & (t >= s - 1)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, emit_idx, axis=0),
                lambda o: o, outs)
            # pass activations rightward
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return nxt, outs

        buf, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        return outs[None]

    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(axis),
        check_vma=False)
    # output of every stage slot; the real result lives on the last stage —
    # slice it out (stage-major leading axis of size s)
    out_all = fn(stage_params, x[None])
    return out_all[-1]
