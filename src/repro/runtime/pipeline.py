"""GPipe-style pipeline parallelism over a mesh axis (shard_map+ppermute).

Stages hold consecutive layer groups (params stacked on a leading stage
dim, sharded over the pipeline axis). Microbatches stream through with the
classic (M + S - 1)-tick schedule; inter-stage hops are collective-permute
(neighbour traffic only — the pattern that maps to ICI rings, and the hop
whose payload the CEAZ fixed-ratio path can compress when stages span the
pod boundary).

This is an optional execution mode (the production mesh uses pod/data/
model); it is exercised by tests/test_pipeline.py on a (stage, data) mesh
and available to the trainer via stage_axis='pod'.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(stage_fn: Callable, params_stacked, microbatches,
                   mesh: Mesh, stage_axis: str = "stage"):
    """Run `stage_fn(stage_params, x) -> y` as a pipeline.

    params_stacked: pytree with leading dim = n_stages (sharded over
        stage_axis).
    microbatches: (M, mb, ...) array, replicated over stage_axis.
    Returns (M, mb, ...) outputs (replicated).
    """
    n_stages = mesh.shape[stage_axis]
    M = microbatches.shape[0]
    ticks = M + n_stages - 1

    def per_stage(params_local, mb_local):
        # params_local: (1, ...) slice for this stage; mb_local: (M, ...)
        params_local = jax.tree.map(lambda a: a[0], params_local)
        sid = jax.lax.axis_index(stage_axis)
        buf_shape = mb_local.shape[1:]
        # pvary: the loop state is stage-VARYING from tick 1 on; the zeros
        # init must carry the same varying-manual-axes type
        outputs = jax.lax.pvary(jnp.zeros_like(mb_local), stage_axis)
        carry_in = jax.lax.pvary(jnp.zeros(buf_shape, mb_local.dtype),
                                 stage_axis)
        mb_local = jax.lax.pvary(mb_local, stage_axis)

        def tick(t, state):
            carry, outputs = state
            # stage 0 ingests microbatch t (if any); others take the wire
            mb_idx = jnp.clip(t, 0, M - 1)
            x = jnp.where(sid == 0, mb_local[mb_idx], carry)
            y = stage_fn(params_local, x)
            # last stage emits output for microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            emit = (sid == n_stages - 1) & (t >= n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(emit, y, outputs[out_idx]), out_idx, 0)
            # shift activations one stage forward
            carry = jax.lax.ppermute(
                y, stage_axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return carry, outputs

        carry, outputs = jax.lax.fori_loop(0, ticks, tick,
                                           (carry_in, outputs))
        # outputs live on the last stage; broadcast to all stages
        outputs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outputs, 0.0), stage_axis)
        return outputs

    return jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        axis_names={stage_axis},
    )(params_stacked, microbatches)


def sequential_reference(stage_fn: Callable, params_stacked, microbatches):
    """Oracle: apply all stages in order to each microbatch."""
    n_stages = jax.tree.leaves(params_stacked)[0].shape[0]

    def one(mb):
        h = mb
        for s in range(n_stages):
            ps = jax.tree.map(lambda a: a[s], params_stacked)
            h = stage_fn(ps, h)
        return h

    return jax.vmap(one)(microbatches)
