"""Flash-attention Pallas TPU kernel (the LLM-inference ISAX of paper §6.5,
TPU-native: VMEM-staged KV streaming instead of BRAM scratchpads).

Tiling and buffering come from the interface-aware synthesis flow
(``core.kernel_synth.choose_flash_blocks``): Q tiles are "warm" (persistent
across the kv loop), K/V tiles are "cold" (streamed), mirroring the paper's
cache_hint machinery.

Layout: the public contract is q (B, S, H, hd), k/v (B, T, K, hd); the
wrappers hand the kernels a heads-major view (B, H, S, hd) so each block is
(1, 1, tile, hd).  Mosaic requires the last two block dims to be multiples
of (8, 128) or the full array dims; in the (B, S, H, hd) layout a
one-head block leaves a second-minor dim of 1, which the chip's compiler
refuses.  GQA folds h → h // G in the BlockSpec index map.  Grid
(B, H, nq, nk): the last grid dim iterates sequentially on TPU, so the
running max / denominator (kept as (bq, 1) columns) and the output
accumulator live in VMEM scratch and are re-initialized at nk == 0.

Two mask sources, one kernel:

* **causal** — the caller's mask is ``make_mask("causal")``: key ``col`` is
  visible from query ``row`` iff ``col <= row + offset`` with ``offset =
  T − S``.  The kernel gets no mask operand; the offset arrives as an int32
  scalar in SMEM, the last operand.  Tiles wholly above the diagonal are
  skipped (``pl.when``) and the K/V index map clamps to the last tile a q
  tile needs, so a skipped step issues no copy; tiles wholly below it run
  unmasked, and only the tiles the diagonal crosses build a mask from tile
  indices.
* **dense** — any other mask (prefix, full, decode) streams as a (1|B, S,
  T) operand, one (bq, bk) tile per step.

Either way the operands are q, k, v and then one int32 array, and q/k/v
enter both dots in their stored dtype (bf16 on the MXU at bf16 rates) with
float32 accumulation; the running statistics stay float32.

This is the *unpipelined* baseline: K/V stream through BlockSpec copies.
``kernels.pipeline.flash_attention_pipelined`` is the burst-DMA variant
(explicit multi-buffered ``make_async_copy`` K/V streaming); the
``ops.flash_attention_gqa`` wrapper routes between them on the synthesized
cost-model decision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tiling import last_causal_tile

NEG_INF = -1e30


def heads_major(x):
    """(B, S, H, hd) → (B, H, S, hd): the view the flash kernels tile."""
    return jnp.swapaxes(x, 1, 2)


def flash_scratch(bq: int, hd: int) -> list:
    """VMEM scratch of one q tile: running max, running denominator (both
    (bq, 1) columns, lane-broadcast against the (bq, bk) scores) and the
    (bq, hd) output accumulator."""
    return [pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32)]


def _init_flash_scratch(m_scr, l_scr, acc_scr):
    """Reset the online-softmax running stats at the start of a kv sweep."""
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _online_softmax_update(q, k, v, mask, sm_scale,
                           m_scr, l_scr, acc_scr):
    """One flash tile update: masked scores → online softmax → scratch.

    ``q`` (bq, hd), ``k``/``v`` (bk, hd) enter the dots in the dtype they
    come in, with float32 accumulation; ``p`` is cast to v's dtype for the
    PV dot.  ``mask`` is a (bq, bk) bool, or None for a tile every query
    sees whole.  Shared by the BlockSpec baseline, the int8-KV variant, and
    the burst-DMA pipelined kernel (``kernels/pipeline.py``) so the
    numerically delicate masked-row handling lives in exactly one place.
    """
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]                              # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # guard fully-masked rows (m == NEG_INF): exp(NEG_INF - NEG_INF) = 1
    # would pollute l; use alpha = exp(m_prev - m_new) with masked-safe forms.
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _causal_mask(qi, ki, bq: int, bk: int, offset):
    """(bq, bk) mask of one tile the diagonal crosses, from the tile
    indices alone."""
    row = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + qi * bq
    col = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ki * bk
    return col <= row + offset


def _tile_update(qi, ki, mask_or_offset, causal: bool, bq: int, bk: int,
                 update):
    """Run ``update(mask)`` for kv tile ``ki`` of q tile ``qi``.

    Dense: ``mask_or_offset`` is the (1, bq, bk) mask block.  Causal: it is
    the SMEM offset; tiles above the diagonal are skipped, tiles below it
    run unmasked, and tiles on it with the index mask."""
    if not causal:
        update(mask_or_offset[0])
        return
    offset = mask_or_offset[0]
    first = qi * bq + offset               # shifted row of the tile's top
    needed = ki * bk <= first + bq - 1
    whole = (ki + 1) * bk - 1 <= first

    @pl.when(whole)
    def _below():
        update(None)

    @pl.when(needed & jnp.logical_not(whole))
    def _diagonal():
        update(_causal_mask(qi, ki, bq, bk, offset))


def _finalize_flash_output(o_ref, l_scr, acc_scr):
    """Divide the accumulator by the running denominator (masked-row safe)."""
    denom = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, aux_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, sm_scale: float, n_kv: int,
                  causal: bool):
    qi, ki = pl.program_id(2), pl.program_id(3)
    bq, bk = q_ref.shape[2], k_ref.shape[2]

    @pl.when(ki == 0)
    def _init():
        _init_flash_scratch(m_scr, l_scr, acc_scr)

    def update(mask):
        _online_softmax_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
                               mask, sm_scale, m_scr, l_scr, acc_scr)

    _tile_update(qi, ki, aux_ref, causal, bq, bk, update)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        _finalize_flash_output(o_ref, l_scr, acc_scr)


def _flash_kernel_int8kv(q_ref, k_ref, v_ref, ks_ref, vs_ref, mask_ref,
                         o_ref, m_scr, l_scr, acc_scr,
                         *, sm_scale: float, n_kv: int, n_groups: int):
    """int8-KV variant (the paper's §6.5 quantized-attention ISAX): K/V
    stream HBM→VMEM as int8 (half the DMA bytes — what the interface model
    rewards) and dequantize against per-head scales INSIDE the tile, so the
    bf16 cache is never materialized."""
    kvh = pl.program_id(1) // n_groups
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        _init_flash_scratch(m_scr, l_scr, acc_scr)

    _online_softmax_update(
        q_ref[0, 0].astype(jnp.float32),
        k_ref[0, 0].astype(jnp.float32) * ks_ref[kvh],
        v_ref[0, 0].astype(jnp.float32) * vs_ref[kvh],
        mask_ref[0], sm_scale, m_scr, l_scr, acc_scr)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        _finalize_flash_output(o_ref, l_scr, acc_scr)


def _tile_specs(bq: int, bk: int, hd: int, G: int, mask_b: int,
                causal_offset: int | None = None):
    """BlockSpecs over the heads-major view: q/out (1,1,bq,hd), k/v
    (1,1,bk,hd) on kv head h // G, and the third input's spec: the
    (1,bq,bk) mask block, or with ``causal_offset`` the SMEM offset, the kv
    index then clamped to the last tile the q tile needs."""
    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, qi, ki: (b, h, qi, 0))
    if causal_offset is None:
        kv_index = lambda qi, ki: ki
        aux_spec = pl.BlockSpec((1, bq, bk),
                                lambda b, h, qi, ki:
                                (b if mask_b > 1 else 0, qi, ki))
    else:
        kv_index = lambda qi, ki: jnp.minimum(
            ki, last_causal_tile(qi, bq, bk, causal_offset))
        aux_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kv_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda b, h, qi, ki:
                           (b, h // G, kv_index(qi, ki), 0))
    return q_spec, kv_spec, aux_spec


def flash_operands(q, k, v, mask, causal: bool):
    """Heads-major q, k, v and the trailing int32 operand: the (1|B, S, T)
    mask, or for a causal call the diagonal's offset T − S."""
    S, T = q.shape[1], k.shape[1]
    aux = jnp.full((1,), T - S, jnp.int32) if causal else mask
    return heads_major(q), heads_major(k), heads_major(v), aux


def flash_attention_int8kv(q, k8, v8, k_scale, v_scale, mask, *,
                           sm_scale: float, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: (B,S,H,hd) float; k8/v8: (B,T,K,hd) int8; k_scale/v_scale: (K,)
    per-kv-head fp32 scales; mask: (1|B,S,T) bool → (B,S,H,hd)."""
    B, S, H, hd = q.shape
    T, K = k8.shape[1], k8.shape[2]
    G = H // K
    bq, bk = min(block_q, S), min(block_k, T)
    assert S % bq == 0 and T % bk == 0
    nq, nk = S // bq, T // bk
    q_spec, kv_spec, mask_spec = _tile_specs(bq, bk, hd, G, mask.shape[0])
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_flash_kernel_int8kv, sm_scale=sm_scale, n_kv=nk,
                          n_groups=G),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, smem, smem, mask_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=flash_scratch(bq, hd),
        interpret=interpret,
    )(heads_major(q), heads_major(k8), heads_major(v8), k_scale, v_scale,
      mask)
    return heads_major(out)


def flash_attention(q, k, v, mask, *, sm_scale: float,
                    block_q: int = 128, block_k: int = 128,
                    causal: bool = False, interpret: bool = False):
    """q: (B,S,H,hd), k/v: (B,T,K,hd), mask: (1|B,S,T) bool → (B,S,H,hd).

    ``causal`` promises that ``mask`` is the causal mask aligned to the
    last key (``col <= row + T − S``, T ≥ S): the mask then is not read,
    and tiles above the diagonal are skipped."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    bq = min(block_q, S)
    bk = min(block_k, T)
    assert S % bq == 0 and T % bk == 0, (S, bq, T, bk)
    assert T >= S or not causal, (S, T)
    nq, nk = S // bq, T // bk
    q_spec, kv_spec, aux_spec = _tile_specs(
        bq, bk, hd, G, mask.shape[0], causal_offset=T - S if causal else None)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, sm_scale=sm_scale, n_kv=nk,
                          causal=causal),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, aux_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=flash_scratch(bq, hd),
        interpret=interpret,
    )(*flash_operands(q, k, v, mask, causal))
    return heads_major(out)
