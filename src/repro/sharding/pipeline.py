"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Completes the at-scale parallelism set (DP/FSDP/TP/EP/SP + **PP**): layer
stacks are split into S stages laid out along a mesh axis; microbatches
circulate stage-to-stage with ``jax.lax.ppermute`` in the classic GPipe
schedule (S + M − 1 ticks, bubble fraction (S−1)/(S+M−1)).  Differentiable —
``jax.grad`` through ``ppermute`` yields the reverse permute, so the same
function serves training.

Use when layer count divides the stage count (e.g. yi-9b / internlm2: 48
layers over 16 stages).  The dry-run lowers this on the production mesh via
``launch/perf.py --variant pp`` (EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(
    stage_fn: Callable,            # (stage_params, x: (mb, S, d)) -> same
    mesh: Mesh,
    stage_axis: str = "model",
    data_axes: tuple[str, ...] = ("data",),
):
    """Returns pipelined(params_stacked, x_microbatches) running under
    shard_map.

    params_stacked : pytree with leading dim L = n_stages * layers_per_stage
                     (sharded over ``stage_axis`` on that dim)
    x_microbatches : (n_micro, micro_batch, seq, d) (microbatch dim sharded
                     over ``data_axes``)

    Output: (n_micro, micro_batch, seq, d) — activations after all stages
    (each microbatch has passed through every layer, in order).
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[stage_axis]

    def run(params_local, x_local):
        # params_local: leading dim L/S (this stage's layers)
        # x_local: (n_micro, mb_local, seq, d)
        stage = jax.lax.axis_index(stage_axis)
        n_micro = x_local.shape[0]
        ticks = n_micro + n_stages - 1
        mb_shape = x_local.shape[1:]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when valid); others take the
            # circulated activation from the previous stage.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = x_local[mb_idx]
            inp = jnp.where(stage == 0, inject, state)
            out = stage_fn(params_local, inp)
            # last stage emits microbatch (t - (S-1)) at tick t
            emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (t >= n_stages - 1)
            outputs = jax.lax.cond(
                valid,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, emit_idx, 0),
                lambda o: o,
                outputs)
            # circulate: stage i -> stage i+1 (last wraps, value unused)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(out, stage_axis, perm)
            return (state, outputs), None

        state0 = jnp.zeros(mb_shape, x_local.dtype)
        outs0 = jnp.zeros_like(x_local)
        (_, outputs), _ = jax.lax.scan(
            tick, (state0, outs0), jnp.arange(ticks))
        # only the LAST stage holds real outputs; broadcast them so the
        # result is replicated along the stage axis (psum of masked values).
        is_last = (stage == n_stages - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * is_last, stage_axis)
        return outputs

    in_specs = (P(stage_axis), P(None, data_axes, None, None))
    out_specs = P(None, data_axes, None, None)
    return shard_map(run, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def reference_forward(stage_fn, params_stacked, x_micro, n_stages: int):
    """Oracle: apply all stages sequentially (no pipelining)."""
    L = jax.tree.leaves(params_stacked)[0].shape[0]
    per = L // n_stages

    def apply_all(x):
        for s in range(n_stages):
            p = jax.tree.map(lambda a: a[s * per:(s + 1) * per],
                             params_stacked)
            x = stage_fn(p, x)
        return x

    return jax.vmap(apply_all)(x_micro)
