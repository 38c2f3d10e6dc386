"""Wire-level int8 gradient synchronization (shard_map).

``compress_int8`` in adamw.py models error-feedback quantization numerically,
but under pjit the gradient all-reduce is inserted by autodiff in fp32 — the
wire still carries 4 bytes/element.  This module provides the real thing for
data-parallel training: a shard_map train step whose gradient reduction is

    1. error-feedback int8 quantization (per-tensor scale, pmax'd),
    2. reduce-scatter via all_to_all of the int8 payload,
    3. local fp32 summation of the received shards,
    4. re-quantized int8 all_gather of the reduced shard.

Wire bytes per chip ≈ 2·S/4 vs fp32 ring all-reduce's 2·S — a 4× cut, at the
cost of one extra quantization of the *reduced* gradient (also carried in the
error-feedback state, so the bias is corrected over steps).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.optim.adamw import AdamWConfig, apply_updates


def _flatten_grads(grads):
    leaves, treedef = jax.tree.flatten(grads)
    sizes = [int(np.prod(l.shape)) for l in leaves]
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    return flat, (treedef, [l.shape for l in leaves], sizes)


def _unflatten_grads(flat, meta):
    treedef, shapes, sizes = meta
    out, off = [], 0
    for shp, sz in zip(shapes, sizes):
        out.append(flat[off:off + sz].reshape(shp))
        off += sz
    return jax.tree.unflatten(treedef, out)


def int8_wire_allreduce(flat: jnp.ndarray, err: jnp.ndarray,
                        axis_names) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Mean-reduce ``flat`` (1-D, f32, same length on every shard) across
    ``axis_names`` with int8 wire payload.  Returns (mean_grad, new_err)."""
    n = jax.lax.psum(1, axis_names)
    gf = flat + err
    scale1 = jax.lax.pmax(jnp.max(jnp.abs(gf)), axis_names) / 127.0
    scale1 = jnp.maximum(scale1, 1e-12)
    q1 = jnp.clip(jnp.round(gf / scale1), -127, 127).astype(jnp.int8)
    new_err = gf - q1.astype(jnp.float32) * scale1

    pad = (-q1.shape[0]) % n
    q1p = jnp.pad(q1, (0, pad))
    chunk = q1p.shape[0] // n
    # reduce-scatter: all_to_all int8 chunks, sum locally in f32
    parts = q1p.reshape(n, chunk)
    recv = jax.lax.all_to_all(parts, axis_names, 0, 0, tiled=True)
    local_sum = jnp.sum(recv.reshape(n, chunk).astype(jnp.float32), axis=0)
    local_mean = local_sum * (scale1 / n)
    # re-quantize the reduced shard and all_gather it (int8 wire again)
    scale2 = jax.lax.pmax(jnp.max(jnp.abs(local_mean)), axis_names) / 127.0
    scale2 = jnp.maximum(scale2, 1e-12)
    q2 = jnp.clip(jnp.round(local_mean / scale2), -127, 127).astype(jnp.int8)
    gathered = jax.lax.all_gather(q2, axis_names, tiled=True)
    mean = gathered.astype(jnp.float32) * scale2
    return mean[:flat.shape[0]], new_err


def make_int8_wire_train_step(model, opt_cfg: AdamWConfig, mesh,
                              dp_axes: tuple[str, ...]):
    """Data-parallel (replicated-params) train step with int8 gradient wire.

    in/out specs: params/opt replicated, batch sharded over ``dp_axes`` —
    build with batch leading dim divisible by the DP size.
    """
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    def step(params, opt_state, err_flat, batch):
        loss, grads = jax.value_and_grad(model.loss)(params, batch)
        loss = jax.lax.pmean(loss, dp_axes)
        flat, meta = _flatten_grads(grads)
        mean_flat, new_err = int8_wire_allreduce(flat, err_flat, dp_axes)
        grads = _unflatten_grads(mean_flat, meta)
        new_params, new_opt, metrics = apply_updates(
            params, grads, opt_state, opt_cfg)
        metrics["loss"] = loss
        return new_params, new_opt, new_err, metrics

    pspec = P()
    bspec = P(dp_axes)
    return shard_map(
        step, mesh=mesh,
        in_specs=(pspec, pspec, pspec, bspec),
        out_specs=(pspec, pspec, pspec, pspec),
        check_vma=False)


def init_err_state(params) -> jnp.ndarray:
    n = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    return jnp.zeros((n,), jnp.float32)
