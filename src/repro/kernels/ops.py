"""Jit'd public wrappers around the Pallas kernels.

Each wrapper: (1) derives tile shapes from the interface-aware synthesis flow
(``core.kernel_synth``) instead of hand-tuned constants — the paper's C1
applied to kernel configuration; (2) checks those tiles against the TPU
compiler's block rule (``core.tiling.tpu_block_ok``); (3) exposes an
``interpret=`` flag so the CPU container can execute the kernel bodies for
correctness.

A shape whose tiles break the block rule falls back to the jnp reference
only in interpret mode, where the tiny CPU test shapes need it.  Compiled
for the chip (``interpret=False``) such a shape raises ``ValueError``
naming the op and the shape, so a kernel that never ran cannot hide behind
the reference.  The ``*_tiles`` functions are the one rounding and
tileability rule: the dispatcher's schedulers (``targets/llm.py``) call
them too, so the shapes they accept are exactly the ones the wrappers can
launch.

Also registers e-graph intrinsics (``core.offload``) backed by the
interpret-mode kernels, so offloaded programs execute through the same
datapaths the "hardware" provides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kernel_synth import (
    choose_flash_blocks,
    choose_matmul_blocks,
    choose_ssd_blocks,
)
from repro.core.tiling import down_pow2, tpu_block_ok
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.int8_matmul import int8_matmul as _int8mm
from repro.kernels.pipeline import (
    flash_attention_pipelined as _flash_pipe,
    int8_matmul_pipelined as _int8mm_pipe,
    ssd_scan_pipelined as _ssd_pipe,
    use_pipeline,
)
from repro.kernels.rmsnorm import rmsnorm as _rmsnorm
from repro.kernels.ssd_scan import ssd_scan as _ssd

#: Row tile of the RMSNorm kernel (rounded down to a divisor of the rows).
RMSNORM_BLOCK_ROWS = 256

# Back-compat aliases (one release): the tile/routing helpers are public
# now — ``repro.core.tiling.down_pow2`` and ``kernels.pipeline.use_pipeline``.
_use_pipeline = use_pipeline
_down_pow2 = down_pow2


@functools.lru_cache(maxsize=None)
def flash_tiles(S: int, T: int, H: int, K: int, hd: int, dtype_bytes: int,
                causal: bool = False):
    """``(schedule, (bq, bk))`` for one attention shape; the tiles are None
    when GQA heads do not fold or the blocks break the TPU block rule.  A
    causal call is costed over the tiles at or below the diagonal only."""
    sched = choose_flash_blocks(S, T, hd, dtype_bytes, causal=causal)
    bq = down_pow2(S, sched.block("q")[0])
    bk = down_pow2(T, sched.block("kv")[0])
    ok = (H % K == 0 and tpu_block_ok((bq, bk), (S, T))
          and (T >= S or not causal))
    return sched, ((bq, bk) if ok else None)


@functools.lru_cache(maxsize=None)
def matmul_tiles(M: int, N: int, K: int, dtype_bytes: int):
    """``(schedule, (bm, bn, bk))`` for the int8 GEMM; None tiles when a
    block breaks the TPU block rule."""
    sched = choose_matmul_blocks(M, N, K, dtype_bytes=dtype_bytes)
    bm = down_pow2(M, sched.block("a")[0])
    bn = down_pow2(N, sched.block("b")[1])
    bk = down_pow2(K, sched.block("a")[1])
    ok = (tpu_block_ok((bm, bk), (M, K)) and tpu_block_ok((bn, bk), (N, K))
          and tpu_block_ok((bm, bn), (M, N)))
    return sched, ((bm, bn, bk) if ok else None)


@functools.lru_cache(maxsize=None)
def ssd_tiles(S: int, H: int, P: int, N: int):
    """``(schedule, chunk)`` for the SSD scan; None chunk when the (chunk,
    P) block breaks the TPU block rule."""
    sched = choose_ssd_blocks(S, H, P, N)
    chunk = down_pow2(S, sched.block("chunk")[0])
    return sched, (chunk if tpu_block_ok((chunk, P), (S, P)) else None)


def rmsnorm_tiles(R: int, d: int):
    """Row tile of the RMSNorm kernel, or None when it breaks the TPU block
    rule (a row count with a large odd factor)."""
    br = down_pow2(R, RMSNORM_BLOCK_ROWS)
    return br if tpu_block_ok((br, d), (R, d)) else None


def _untileable(op: str, shapes) -> ValueError:
    return ValueError(f"{op}: shapes {shapes} do not tile for the TPU "
                      f"compiler (see core.tiling.tpu_block_ok)")


def flash_attention_gqa(q, k, v, mask, *, sm_scale: float,
                        causal: bool = False, interpret: bool = False,
                        pipelined: bool | None = None):
    """Drop-in for ``layers.sdpa``: synthesis-chosen tiles.  ``causal``
    says ``mask`` is ``make_mask("causal")``: the kernel then builds it
    from tile indices and skips the tiles above the diagonal.
    ``pipelined`` routes K/V streaming through the burst-DMA pipeline (None
    = the synthesized cost-model decision)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    sched, tiles = flash_tiles(S, T, H, k.shape[2], hd, q.dtype.itemsize,
                               causal)
    if tiles is None:
        if interpret:
            return ref.flash_attention_ref(q, k, v, mask, sm_scale=sm_scale)
        raise _untileable("flash_attention", (q.shape, k.shape))
    bq, bk = tiles
    mask = jnp.broadcast_to(mask, (mask.shape[0], S, T))
    kw = dict(sm_scale=sm_scale, block_q=bq, block_k=bk, causal=causal,
              interpret=interpret)
    if use_pipeline(sched, pipelined, T // bk):
        return _flash_pipe(q, k, v, mask, depth=max(2, sched.buffering),
                           **kw)
    return _flash(q, k, v, mask, **kw)


def int8_matmul(x, wq, scale, *, interpret: bool = False,
                pipelined: bool | None = None):
    """Quantized GEMM with synthesis-chosen tiles; ``pipelined`` routes the
    int8 weight (and activation) tiles through the burst-DMA pipeline
    (None = the synthesized cost-model decision)."""
    M, K = x.shape
    N = wq.shape[0]
    sched, tiles = matmul_tiles(M, N, K, 1)
    if tiles is None:
        if interpret:
            return ref.int8_matmul_ref(x, wq, scale)
        raise _untileable("int8_matmul", (x.shape, wq.shape))
    bm, bn, bk = tiles
    if use_pipeline(sched, pipelined, K // bk):
        return _int8mm_pipe(x, wq, scale, block_m=bm, block_n=bn,
                            block_k=bk, depth=max(2, sched.buffering),
                            interpret=interpret)
    return _int8mm(x, wq, scale, block_m=bm, block_n=bn, block_k=bk,
                   interpret=interpret)


def ssd_scan(x, dt, A, B, C, *, interpret: bool = False,
             pipelined: bool | None = None):
    """SSD chunked scan with synthesis-chosen chunk length; ``pipelined``
    streams the x/B/C chunks through the burst-DMA pipeline (None = the
    synthesized cost-model decision)."""
    BT, H, S, P = x.shape
    N = B.shape[-1]
    sched, chunk = ssd_tiles(S, H, P, N)
    if chunk is None:
        if interpret:
            return ref.ssd_scan_ref(x, dt, A, B, C)
        raise _untileable("ssd_scan", (x.shape, B.shape))
    if use_pipeline(sched, pipelined, S // chunk):
        return _ssd_pipe(x, dt, A, B, C, chunk=chunk,
                         depth=max(2, sched.buffering), interpret=interpret)
    return _ssd(x, dt, A, B, C, chunk=chunk, interpret=interpret)


def rmsnorm(x, g, *, eps: float = 1e-6, interpret: bool = False):
    """Row-blocked RMSNorm: x (R,d), g (d) → (R,d)."""
    R, d = x.shape
    br = rmsnorm_tiles(R, d)
    if br is None:
        if interpret:
            return ref.rmsnorm_ref(x, g, eps=eps)
        raise _untileable("rmsnorm", (x.shape,))
    return _rmsnorm(x, g, eps=eps, block_rows=br, interpret=interpret)


# ---------------------------------------------------------------------------
# E-graph intrinsic registration: the offloaded "custom instructions" execute
# the fused datapath.  On this CPU host the fused path is the jit'd oracle
# (one fused XLA computation — what the hardware datapath provides); the
# Pallas kernel bodies themselves are validated separately in interpret mode
# (tests/test_kernels.py, REPRO_INTRINSIC_INTERPRET=1 forces them here too).
# ---------------------------------------------------------------------------

import os as _os

_INTERPRET = _os.environ.get("REPRO_INTRINSIC_INTERPRET", "0") == "1"


def _as_f32(a):
    return jnp.asarray(np.asarray(a), jnp.float32)


@functools.lru_cache(maxsize=None)
def _jit_flash():
    def _f(q, k, v, mask, scale):
        return ref.flash_attention_ref(q, k, v, mask, sm_scale=scale)
    return jax.jit(_f, static_argnums=(4,))


@functools.lru_cache(maxsize=None)
def _jit_int8():
    return jax.jit(ref.int8_matmul_ref)


@functools.lru_cache(maxsize=None)
def _jit_rms():
    return jax.jit(lambda x, g, eps: ref.rmsnorm_ref(x, g, eps=eps),
                   static_argnums=(2,))


@functools.lru_cache(maxsize=None)
def _jit_ssd_seq():
    def _f(a, B, C, X, h0):
        # per-step decay recurrence (evaluator layout: a_t scalar per step)
        def _step(h, inp):
            a_t, b_t, c_t, x_t = inp
            h = a_t * h + jnp.outer(b_t, x_t)
            return h, h.T @ c_t
        return jax.lax.scan(_step, h0, (a, B, C, X))
    return jax.jit(_f)


def _intr_flash(Q, K, V, scale, n_q, P, O):
    q = _as_f32(Q)[None, :, None, :]
    k = _as_f32(K)[None, :, None, :]
    v = _as_f32(V)[None, :, None, :]
    mask = jnp.ones((1, q.shape[1], k.shape[1]), bool)
    if _INTERPRET:
        out = flash_attention_gqa(q, k, v, mask, sm_scale=float(scale),
                                  interpret=True)
    else:
        out = _jit_flash()(q, k, v, mask, float(scale))
    O[:] = np.asarray(out[0, :, 0, :], dtype=O.dtype)
    # P (the normalized probability matrix) is an ISAX-internal intermediate;
    # materialize it for evaluator parity with the reference program.
    s = (np.asarray(Q, np.float64) @ np.asarray(K, np.float64).T) * float(scale)
    e = np.exp(s - s.max(-1, keepdims=True))
    P[:] = e / e.sum(-1, keepdims=True)


def _intr_int8_matvec(Wq, X, s_w, n, C):
    x = _as_f32(X)
    w = jnp.asarray(np.asarray(Wq), jnp.int8)
    scale = jnp.full((w.shape[0],), float(s_w), jnp.float32)
    if _INTERPRET:
        out = int8_matmul(x, w, scale, interpret=True)
    else:
        out = _jit_int8()(x, w, scale)
    C[:] = np.asarray(out, dtype=C.dtype)


def _intr_ssd(A, B, C, X, T, H, Y):
    a = _as_f32(A)
    h, ys = _jit_ssd_seq()(a, _as_f32(B), _as_f32(C), _as_f32(X),
                           _as_f32(H[0]))
    Y[:] = np.asarray(ys, dtype=Y.dtype)
    H[0] = np.asarray(h, dtype=H.dtype)


def _intr_rmsnorm(Xn, G, eps, n, On):
    if _INTERPRET:
        out = rmsnorm(_as_f32(Xn), _as_f32(G), eps=float(eps),
                      interpret=True)
    else:
        out = _jit_rms()(_as_f32(Xn), _as_f32(G), float(eps))
    On[:] = np.asarray(out, dtype=On.dtype)


def register_kernel_intrinsics() -> None:
    """Register the e-graph intrinsics backed by these kernel datapaths."""
    from repro.core import offload
    offload.register_intrinsic("flash_attention", _intr_flash)
    offload.register_intrinsic("int8_matvec", _intr_int8_matvec)
    offload.register_intrinsic("ssd_step", _intr_ssd)
    offload.register_intrinsic("rmsnorm", _intr_rmsnorm)
