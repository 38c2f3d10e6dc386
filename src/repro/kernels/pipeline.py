"""Burst-DMA memory pipeline: multi-buffered async HBM→VMEM tile streaming.

The paper's headline hardware contribution is a burst DMA engine that keeps
the compute datapath fed; the TPU-native equivalent is explicit
``pltpu.make_async_copy`` multi-buffering.  The kernels in this module are
the pipelined variants of the baseline Pallas kernels: the "cold" operands
(K/V tiles for flash attention, quantized weight/activation tiles for the
int8 GEMM, x/B/C chunks for the SSD scan) stay in HBM (``memory_space=ANY``)
and are streamed into a ``depth``-deep rotating VMEM buffer by an explicit
DMA pipeline, overlapping the copy-in of tile ``i+1 .. i+depth-1`` with
compute on tile ``i``.

``BurstPipeline`` is the reusable streamer: the point-cloud kernels
(``pointcloud/kernels.py``) drive their X/feature tile streaming through
the same class, so the DMA schedule logic lives in exactly one place.

Buffer depth and tile shapes come from ``core.kernel_synth`` (which models
the transfer cost through the §4.1 interface-model recurrences and only
turns the pipeline on when both the interface model and the roofline
overlap bound predict a win); the dispatcher records the decision in its
compile cache, and ``benchmarks/bench_membw.py`` measures pipelined vs
unpipelined across memory-bound shapes.

Everything here runs under ``interpret=True`` on CPU — the Pallas
interpreter emulates DMA semaphores — so CI exercises the exact kernel
bodies that run on TPU.

Pipeline schedule (per sweep of the sequential grid dim, ``n_steps`` tiles):

    step 0      : start tiles 0..depth-2          (pipeline fill)
    step i      : start tile  i+depth-1  (if any) ─┐ overlapped with
                  wait  tile  i                    ─┘ compute on tile i
    step n-1    : nothing left to start; drain

Starts and waits balance exactly within one sweep, so the pipeline is clean
at every outer-grid-dim boundary (e.g. each new flash-attention q tile).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import last_causal_tile
from repro.kernels.flash_attention import (
    _finalize_flash_output,
    _init_flash_scratch,
    _online_softmax_update,
    _tile_specs,
    _tile_update,
    flash_operands,
    flash_scratch,
    heads_major,
)

#: Default burst depth when a caller forces the pipeline on without a
#: synthesized schedule (two buffers = classic double buffering).
DEFAULT_DEPTH = 2


def use_pipeline(sched, override: bool | None, n_steps: int) -> bool:
    """Burst-pipeline routing rule shared by every op wrapper.

    The synthesized go/no-go decision (``sched.pipelined``) unless the
    caller forces it (``override``); a single streamed tile can never
    overlap, so it always takes the plain path.  Public home of the old
    ``kernels/ops._use_pipeline`` (it crossed module boundaries privately).
    """
    if n_steps < 2:
        return False
    return sched.pipelined if override is None else bool(override)


class BurstPipeline:
    """Multi-buffered HBM→VMEM tile streamer for use inside kernel bodies.

    Parameters
    ----------
    streams : sequence of ``(slice_fn, buf_ref)``
        One entry per cold operand.  ``slice_fn(t)`` must return the HBM
        source slice of tile ``t`` (``t`` may be a Python int during the
        pipeline fill or a traced scalar), shaped like one slot of
        ``buf_ref`` — a VMEM scratch of shape ``(depth, *tile_shape)``.
    sem : DMA semaphore array of shape ``(len(streams), depth)``.
    n_steps : trip count of the streamed (sequential) grid dim: static, or
        a traced scalar when a sweep stops early (the causal flash kernel's
        last tile under the diagonal); steps from ``n_steps`` on must not
        call ``stream_step``.
    depth : static buffer depth ≥ 2.
    """

    def __init__(self, *, streams, sem, n_steps: int, depth: int):
        assert depth >= 2, "a burst pipeline needs at least two buffers"
        self.streams = tuple(streams)
        self.sem = sem
        self.n_steps = n_steps
        self.depth = depth

    def _copy(self, j: int, t):
        slice_fn, buf = self.streams[j]
        slot = t % self.depth
        return pltpu.make_async_copy(slice_fn(t), buf.at[slot],
                                     self.sem.at[j, slot])

    def _start_all(self, t) -> None:
        for j in range(len(self.streams)):
            self._copy(j, t).start()

    def stream_step(self, step):
        """Advance the pipeline by one grid step.

        Fills the pipeline at ``step == 0``, starts the copy of tile
        ``step + depth - 1`` (overwriting the slot the *previous* step
        finished computing on), then blocks until tile ``step`` has landed.
        Returns the buffer slot holding tile ``step``; the caller reads
        ``buf[slot]`` and computes while the started copies fly.
        """
        @pl.when(step == 0)
        def _fill():
            for d in range(self.depth - 1):
                pl.when(d < self.n_steps)(functools.partial(self._start_all, d))

        nxt = step + self.depth - 1
        @pl.when(nxt < self.n_steps)
        def _prefetch():
            self._start_all(nxt)

        for j in range(len(self.streams)):
            self._copy(j, step).wait()
        return step % self.depth


# ---------------------------------------------------------------------------
# Flash attention (K/V tiles streamed)
# ---------------------------------------------------------------------------

def _flash_pipelined_kernel(q_ref, k_hbm, v_hbm, aux_ref, o_ref,
                            k_buf, v_buf, sem, m_scr, l_scr, acc_scr,
                            *, sm_scale: float, n_kv: int, block_k: int,
                            depth: int, n_groups: int, causal: bool):
    b, h = pl.program_id(0), pl.program_id(1)
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq = q_ref.shape[2]
    kvh = h // n_groups
    # A causal sweep streams only the tiles up to the diagonal.
    n_steps = (jnp.minimum(n_kv, last_causal_tile(qi, bq, block_k,
                                                  aux_ref[0]) + 1)
               if causal else n_kv)
    pipe = BurstPipeline(
        streams=(
            (lambda t: k_hbm.at[b, kvh, pl.ds(t * block_k, block_k), :],
             k_buf),
            (lambda t: v_hbm.at[b, kvh, pl.ds(t * block_k, block_k), :],
             v_buf),
        ),
        sem=sem, n_steps=n_steps, depth=depth)

    @pl.when(ki == 0)
    def _init():
        _init_flash_scratch(m_scr, l_scr, acc_scr)

    @pl.when(ki < n_steps)
    def _step():
        slot = pipe.stream_step(ki)

        def update(mask):
            _online_softmax_update(q_ref[0, 0], k_buf[slot], v_buf[slot],
                                   mask, sm_scale, m_scr, l_scr, acc_scr)

        _tile_update(qi, ki, aux_ref, causal, bq, block_k, update)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        _finalize_flash_output(o_ref, l_scr, acc_scr)


def flash_attention_pipelined(q, k, v, mask, *, sm_scale: float,
                              block_q: int = 128, block_k: int = 128,
                              causal: bool = False,
                              depth: int = DEFAULT_DEPTH,
                              interpret: bool = False):
    """Burst-DMA flash attention: K/V tiles streamed HBM→VMEM explicitly.

    Same contract as ``flash_attention.flash_attention`` — q (B,S,H,hd),
    k/v (B,T,K,hd), mask (1|B,S,T) bool, ``causal`` → (B,S,H,hd), tiled
    over the same heads-major view — but the K/V operands bypass BlockSpec
    staging and flow through a ``depth``-deep rotating buffer driven by
    ``BurstPipeline``.  A causal sweep streams only the tiles at or below
    the diagonal.

    Mosaic only slices an HBM ref whose minor dim is a multiple of 128
    lanes, so a head dim below that is zero-padded up to it (zero lanes add
    nothing to q·k, and the padded output lanes are dropped).  The padding
    matches what the schedule already costs: ``choose_flash_blocks`` models
    K/V tiles at the lane-padded head dim.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    assert T >= S or not causal, (S, T)
    nq, nk = S // bq, T // bk
    lanes = -(-hd // 128) * 128
    if lanes != hd:
        pad = [(0, 0)] * 3 + [(0, lanes - hd)]
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
        out = flash_attention_pipelined(
            q, k, v, mask, sm_scale=sm_scale, block_q=bq, block_k=bk,
            causal=causal, depth=depth, interpret=interpret)
        return out[..., :hd]
    q_spec, _, aux_spec = _tile_specs(bq, bk, hd, G, mask.shape[0],
                                      causal_offset=T - S if causal else None)
    out = pl.pallas_call(
        functools.partial(_flash_pipelined_kernel, sm_scale=sm_scale,
                          n_kv=nk, block_k=bk, depth=depth, n_groups=G,
                          causal=causal),
        grid=(B, H, nq, nk),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pltpu.ANY),   # K stays in HBM
            pl.BlockSpec(memory_space=pltpu.ANY),   # V stays in HBM
            aux_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((depth, bk, hd), k.dtype),
            pltpu.VMEM((depth, bk, hd), v.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            *flash_scratch(bq, hd),
        ],
        interpret=interpret,
    )(*flash_operands(q, k, v, mask, causal))
    return heads_major(out)


# ---------------------------------------------------------------------------
# Int8-weight matmul (weight + activation tiles streamed)
# ---------------------------------------------------------------------------

def _int8_mm_pipelined_kernel(x_hbm, w_hbm, s_ref, o_ref,
                              x_buf, w_buf, sem, acc_scr,
                              *, n_k: int, block_m: int, block_n: int,
                              block_k: int, depth: int):
    mi, ni, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pipe = BurstPipeline(
        streams=(
            (lambda t: x_hbm.at[pl.ds(mi * block_m, block_m),
                                pl.ds(t * block_k, block_k)], x_buf),
            (lambda t: w_hbm.at[pl.ds(ni * block_n, block_n),
                                pl.ds(t * block_k, block_k)], w_buf),
        ),
        sem=sem, n_steps=n_k, depth=depth)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    slot = pipe.stream_step(ki)
    x = x_buf[slot].astype(jnp.float32)             # (bm, bk)
    w = w_buf[slot].astype(jnp.float32)             # (bn, bk) int8 → f32
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        scale = s_ref[...].astype(jnp.float32)       # (bn,)
        o_ref[...] = (acc_scr[...] * scale[None, :]).astype(o_ref.dtype)


def int8_matmul_pipelined(x, wq, scale, *, block_m: int = 128,
                          block_n: int = 128, block_k: int = 512,
                          depth: int = DEFAULT_DEPTH,
                          interpret: bool = False, out_dtype=None):
    """Burst-DMA int8 GEMM: weight and activation tiles streamed HBM→VMEM.

    Same contract as ``int8_matmul.int8_matmul`` — x (M,K) float, wq (N,K)
    int8, scale (N,) → (M,N).  The int8 weight tiles stream at half the DMA
    bytes of bf16, which is exactly what the interface model rewards.
    """
    M, K = x.shape
    N = wq.shape[0]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (x.shape, wq.shape)
    grid = (M // bm, N // bn, K // bk)
    out_dtype = out_dtype or x.dtype
    return pl.pallas_call(
        functools.partial(_int8_mm_pipelined_kernel, n_k=grid[2],
                          block_m=bm, block_n=bn, block_k=bk, depth=depth),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.ANY),   # x stays in HBM
            pl.BlockSpec(memory_space=pltpu.ANY),   # wq stays in HBM
            pl.BlockSpec((bn,), lambda mi, ni, ki: (ni,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((depth, bm, bk), x.dtype),
            pltpu.VMEM((depth, bn, bk), wq.dtype),
            pltpu.SemaphoreType.DMA((2, depth)),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret,
    )(x, wq, scale)


# ---------------------------------------------------------------------------
# SSD chunked scan (x/B/C chunks streamed; running state stays warm in VMEM)
# ---------------------------------------------------------------------------

def _ssd_pipelined_kernel(dt_ref, a_ref, x_hbm, b_hbm, c_hbm, y_ref,
                          x_buf, b_buf, c_buf, sem, state_scr,
                          *, n_chunks: int, chunk: int, depth: int):
    b, h, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    pipe = BurstPipeline(
        streams=(
            (lambda t: x_hbm.at[b, h, pl.ds(t * chunk, chunk), :], x_buf),
            (lambda t: b_hbm.at[b, pl.ds(t * chunk, chunk), :], b_buf),
            (lambda t: c_hbm.at[b, pl.ds(t * chunk, chunk), :], c_buf),
        ),
        sem=sem, n_steps=n_chunks, depth=depth)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    slot = pipe.stream_step(ci)
    x = x_buf[slot].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)      # (Q,)
    A = a_ref[0].astype(jnp.float32)           # () per-head
    B = b_buf[slot].astype(jnp.float32)        # (Q, N)
    C = c_buf[slot].astype(jnp.float32)        # (Q, N)

    a = dt * A
    a_cum = jnp.cumsum(a)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    decay = jnp.exp(a_cum[:, None] - a_cum[None, :])
    Q = x.shape[0]
    tril = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    M = jnp.where(tril, scores * decay, 0.0)
    y_intra = jax.lax.dot_general(M * dt[None, :], x,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    h_prev = state_scr[...]
    y_inter = jax.lax.dot_general(C * jnp.exp(a_cum)[:, None], h_prev,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    decay_last = jnp.exp(a_cum[-1] - a_cum)
    wB = B * (decay_last * dt)[:, None]
    state_scr[...] = (jnp.exp(a_cum[-1]) * h_prev
                      + jax.lax.dot_general(wB, x, (((0,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))


def ssd_scan_pipelined(x, dt, A, B, C, *, chunk: int = 128,
                       depth: int = DEFAULT_DEPTH, interpret: bool = False):
    """Burst-DMA SSD scan: x/B/C chunks streamed HBM→VMEM explicitly.

    Same contract as ``ssd_scan.ssd_scan`` — x (BT,H,S,P), dt (BT,H,S),
    A (H,), B/C (BT,S,N) → y (BT,H,S,P); S must be a multiple of ``chunk``.
    The (N,P) running state stays warm in VMEM scratch across the chunk
    sweep while the streamed chunks rotate through the burst buffers.
    """
    BT, H, S, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    return pl.pallas_call(
        functools.partial(_ssd_pipelined_kernel, n_chunks=nc, chunk=Q,
                          depth=depth),
        grid=(BT, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q), lambda b, h, ci: (b, h, ci)),
            pl.BlockSpec((1,), lambda b, h, ci: (h,)),
            pl.BlockSpec(memory_space=pltpu.ANY),   # x stays in HBM
            pl.BlockSpec(memory_space=pltpu.ANY),   # B stays in HBM
            pl.BlockSpec(memory_space=pltpu.ANY),   # C stays in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda b, h, ci: (b, h, ci, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[
            pltpu.VMEM((depth, Q, P), x.dtype),
            pltpu.VMEM((depth, Q, N), B.dtype),
            pltpu.VMEM((depth, Q, N), C.dtype),
            pltpu.SemaphoreType.DMA((3, depth)),
            pltpu.VMEM((N, P), jnp.float32),
        ],
        interpret=interpret,
    )(dt, A, x, B, C)
