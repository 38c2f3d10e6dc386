"""Jit-friendly public wrappers around the point-cloud Pallas kernels.

Mirrors ``kernels/ops.py`` for the LLM ops: tile shapes and burst-pipeline
depth come from the interface-aware synthesis flow (``core.kernel_synth``),
shapes the kernels can't tile fall back to the jnp references in interpret
mode and raise ``ValueError`` when compiled, and each wrapper exposes
``interpret=`` so the CPU container executes the real kernel bodies.  ``pipelined=`` overrides the synthesized go/no-go decision
(None = trust the cost model).

Also registers e-graph intrinsics for the ``fps`` / ``ball_query`` /
``group_agg`` ISAXes so offloaded programs execute through the same
datapaths the "hardware" provides.
"""

from __future__ import annotations

import functools
import os as _os

import jax.numpy as jnp
import numpy as np

from repro.core.interface_model import TPU_VMEM_BUDGET
from repro.core.kernel_synth import (
    choose_ball_blocks,
    choose_fps_blocks,
    choose_group_blocks,
    fps_vmem_bytes,
)
from repro.core.tiling import down_pow2
from repro.kernels.pipeline import use_pipeline
from repro.pointcloud import kernels as pck
from repro.pointcloud import ref as pcref


@functools.lru_cache(maxsize=None)
def _fps_schedule(N: int, S: int, dtype_bytes: int):
    return choose_fps_blocks(N, S, dtype_bytes)


@functools.lru_cache(maxsize=None)
def _ball_schedule(M: int, N: int, k: int, dtype_bytes: int):
    return choose_ball_blocks(M, N, k, dtype_bytes)


@functools.lru_cache(maxsize=None)
def _group_schedule(M: int, N: int, k: int, C: int, dtype_bytes: int):
    return choose_group_blocks(M, N, k, C, dtype_bytes)


def pc_tiles(M: int, N: int, sched, stream_key: str):
    """Derive (bm, bn) power-of-two tiles from a synthesized schedule, or
    None when the shape is untileable.

    ``down_pow2`` always divides, so divisibility can't fail — instead a
    shape with a large odd factor *degrades*: its biggest power-of-two
    divisor collapses toward 1-wide tiles.  Those degenerate launches are
    worse than the XLA reference, so "untileable" means the derived tile
    fell below the meaningful minimum (8 sublanes of centers, 128 lanes of
    streamed rows — or the whole axis when it is smaller than that).
    """
    bm = down_pow2(M, sched.block("centers")[0])
    bn = down_pow2(N, sched.block(stream_key)[0])
    if bm < min(M, 8) or bn < min(N, 128):
        return None
    return bm, bn


def _no_kernel(op: str, shapes) -> ValueError:
    return ValueError(f"{op}: shapes {shapes} have no kernel launch "
                      f"(the dispatcher's scheduler declines them)")


def farthest_point_sample(xyz, n_samples: int, *, interpret: bool = False):
    """FPS: xyz (B, N, d) → indices (B, n_samples) i32.  Asked for more
    samples than points, or for a cloud that exceeds VMEM (FPS has no
    tiling to shrink), it falls back to the reference in interpret mode and
    raises ``ValueError`` when compiled."""
    B, N, d = xyz.shape
    if (n_samples > N
            or fps_vmem_bytes(N, n_samples,
                              xyz.dtype.itemsize) > TPU_VMEM_BUDGET):
        if interpret:
            return pcref.fps_ref(xyz, n_samples)
        raise _no_kernel("fps", (xyz.shape, n_samples))
    _fps_schedule(N, n_samples, xyz.dtype.itemsize)  # recorded by dispatch
    return pck.fps(xyz, n_samples, interpret=interpret)


def ball_query(xyz, centers, radius: float, k: int, *,
               interpret: bool = False, pipelined: bool | None = None,
               radius_sq: float | None = None):
    """Ball query with synthesis-chosen tiles; ``pipelined`` streams the X
    tiles through the burst-DMA pipeline (None = the cost-model decision).
    ``radius_sq`` supplies the squared radius exactly (the e-graph
    intrinsic's contract is in r² — squaring a rounded sqrt would move the
    boundary by ULPs)."""
    B, N, d = xyz.shape
    M = centers.shape[1]
    sched = _ball_schedule(M, N, k, xyz.dtype.itemsize)
    tiles = pc_tiles(M, N, sched, "x")
    if tiles is None:
        if interpret:
            return pcref.ball_query_ref(xyz, centers, radius, k,
                                        radius_sq=radius_sq)
        raise _no_kernel("ball_query", (xyz.shape, centers.shape))
    bm, bn = tiles
    if use_pipeline(sched, pipelined, N // bn):
        return pck.ball_query_pipelined(
            xyz, centers, radius, k, block_m=bm, block_n=bn,
            depth=max(2, sched.buffering), interpret=interpret,
            radius_sq=radius_sq)
    return pck.ball_query(xyz, centers, radius, k, block_m=bm, block_n=bn,
                          interpret=interpret, radius_sq=radius_sq)


def group_aggregate(features, idx, *, interpret: bool = False,
                    pipelined: bool | None = None):
    """Grouped max-pool aggregation with synthesis-chosen tiles;
    ``pipelined`` streams the feature tiles through the burst-DMA pipeline
    (None = the cost-model decision)."""
    B, N, C = features.shape
    M, k = idx.shape[1], idx.shape[2]
    sched = _group_schedule(M, N, k, C, features.dtype.itemsize)
    tiles = pc_tiles(M, N, sched, "f")
    if tiles is None:
        if interpret:
            return pcref.group_aggregate_ref(features, idx)
        raise _no_kernel("group_aggregate", (features.shape, idx.shape))
    bm, bn = tiles
    if use_pipeline(sched, pipelined, N // bn):
        return pck.group_aggregate_pipelined(
            features, idx, block_m=bm, block_n=bn,
            depth=max(2, sched.buffering), interpret=interpret)
    return pck.group_aggregate(features, idx, block_m=bm, block_n=bn,
                               interpret=interpret)


# ---------------------------------------------------------------------------
# E-graph intrinsic registration (same pattern as kernels/ops.py: on this
# CPU host the fused path is the jnp oracle — what the hardware datapath
# provides — and REPRO_INTRINSIC_INTERPRET=1 forces the Pallas kernel
# bodies through the interpreter instead).
# ---------------------------------------------------------------------------

_INTERPRET = _os.environ.get("REPRO_INTRINSIC_INTERPRET", "0") == "1"


def _intr_fps(Xp, n_s, Dp, Sp):
    """fps ISAX: valid for the canonical init (Dp uniform → start at 0)."""
    xyz = jnp.asarray(np.asarray(Xp, np.float32))[None]
    if _INTERPRET:
        sel = farthest_point_sample(xyz, int(n_s), interpret=True)
    else:
        sel = pcref.fps_ref(xyz, int(n_s))
    Sp[:] = np.asarray(sel[0], dtype=Sp.dtype)
    # D (the running min-distance) is ISAX-internal state; materialize it
    # for evaluator parity with the reference program.
    d = np.asarray(Dp[0], np.float64)
    X = np.asarray(Xp, np.float64)
    for s in np.asarray(Sp, np.int64):
        diff = X - X[s]
        d = np.minimum(d, (diff * diff).sum(-1))
    Dp[0] = d.astype(Dp.dtype)


def _intr_ball_query(Xp, Cn, r2, kk, n_c, Gq):
    xyz = jnp.asarray(np.asarray(Xp, np.float32))[None]
    cen = jnp.asarray(np.asarray(Cn, np.float32))[None]
    # the ISAX contract is in r²: pass it through exactly (radius_sq) so
    # the in-radius boundary never moves by a sqrt→square round trip
    radius = float(np.sqrt(r2))
    if _INTERPRET:
        sel = ball_query(xyz, cen, radius, int(kk), interpret=True,
                         radius_sq=float(r2))
    else:
        sel = pcref.ball_query_ref(xyz, cen, radius, int(kk),
                                   radius_sq=float(r2))
    Gq[:] = np.asarray(sel[0], dtype=Gq.dtype)


def _intr_group_agg(Fg, Gq, n_c, Ag):
    f = jnp.asarray(np.asarray(Fg, np.float32))[None]
    idx = jnp.asarray(np.asarray(Gq, np.int32))[None]
    if _INTERPRET:
        out = group_aggregate(f, idx, interpret=True)
    else:
        out = pcref.group_aggregate_ref(f, idx)
    Ag[:] = np.asarray(out[0], dtype=Ag.dtype)


def register_pointcloud_intrinsics() -> None:
    """Register the e-graph intrinsics backed by the point-cloud kernels."""
    from repro.core import offload
    offload.register_intrinsic("fps", _intr_fps)
    offload.register_intrinsic("ball_query", _intr_ball_query)
    offload.register_intrinsic("group_agg", _intr_group_agg)
