"""Shared layer substrate: norms, RoPE, GQA attention, SwiGLU MLP, embeddings.

Parameters are plain nested dicts of jnp arrays.  Every init_* function has a
matching *_axes function returning the same pytree structure with logical-axis
tuples; ``sharding/policies.py`` maps logical axes to mesh axes.

Kernel selection is a *compiler decision*: every hot op (attention, RMSNorm,
matmul) consults ``repro.compile`` at jit-trace time — the dispatcher runs
the e-graph ISAX pipeline once per op kind, caches the lowering per
(op, shape, dtype, backend), and the layer executes whichever implementation
was extracted (Pallas ISAX kernel, chunked-XLA, or the jnp reference).  The
backend preference travels in a ``LoweringConfig`` threaded through the
model families and serve engines; functions fall back to the process-default
lowering when none is passed (trainer, dry-run).  The old module-global
``set_attention_impl`` flag survives only as a deprecation shim.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from repro.compile import config as _lowering_config
from repro.compile.config import LoweringConfig, default_lowering
from repro.configs.base import ModelConfig


def set_attention_impl(impl: str) -> None:
    """Deprecated shim: swaps the process-default ``LoweringConfig`` backend.

    Use ``repro.compile.LoweringConfig(backend=...)`` (threaded through
    ``get_model``/the serve engines) or
    ``repro.compile.set_default_backend`` instead.
    """
    warnings.warn(
        "set_attention_impl is deprecated; construct a "
        "repro.compile.LoweringConfig(backend=...) or call "
        "repro.compile.set_default_backend", DeprecationWarning,
        stacklevel=2)
    _lowering_config.set_default_backend(impl)


def get_attention_impl() -> str:
    """Deprecated shim: reads the process-default backend."""
    return _lowering_config.get_default_backend()


# ---------------------------------------------------------------------------
# Activation sharding constraints (opt-in, set by the launcher)
#
# GSPMD's propagation through scanned layer bodies can drift to replicated
# batch layouts; explicit with_sharding_constraint at block boundaries pins
# the intended DP×TP activation layout (standard MaxText-style practice).
# ``_ACT_SPECS`` maps layout kinds → PartitionSpec; None disables (CPU tests).
# ---------------------------------------------------------------------------

_ACT_SPECS: Optional[dict] = None


def set_activation_shardings(specs: Optional[dict]) -> None:
    """specs: {'btd': PartitionSpec, 'btv': ..., 'btf': ...} or None."""
    global _ACT_SPECS
    _ACT_SPECS = specs


def shard_act(x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if _ACT_SPECS is None or kind not in _ACT_SPECS:
        return x
    spec = _ACT_SPECS[kind]
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm_axes() -> dict:
    return {"scale": ("embed",)}


def rmsnorm(params: dict, x: jnp.ndarray, eps: float = 1e-6, *,
            lowering: Optional[LoweringConfig] = None) -> jnp.ndarray:
    lw = lowering or default_lowering()
    d = x.shape[-1]
    rows = math.prod(x.shape[:-1])
    rec = lw.lower("rmsnorm", (rows, d), x.dtype)
    if rec.impl == "isax":
        out = rec.kernel_fn(x.reshape(rows, d),
                            params["scale"].astype(jnp.float32), eps=eps,
                            interpret=lw.interpret)
        return out.reshape(x.shape).astype(x.dtype)
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta)                     # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def init_attention(cfg: ModelConfig, key) -> dict:
    d, H, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim()
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    scale = d ** -0.5
    p = {
        "wq": (jax.random.normal(k1, (d, H, hd)) * scale).astype(dt),
        "wk": (jax.random.normal(k2, (d, K, hd)) * scale).astype(dt),
        "wv": (jax.random.normal(k3, (d, K, hd)) * scale).astype(dt),
        "wo": (jax.random.normal(k4, (H, hd, d)) * (H * hd) ** -0.5).astype(dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H, hd), dtype=dt)
        p["bk"] = jnp.zeros((K, hd), dtype=dt)
        p["bv"] = jnp.zeros((K, hd), dtype=dt)
    return p


def attention_axes(cfg: ModelConfig) -> dict:
    p = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if cfg.qkv_bias:
        p["bq"] = ("heads", "head_dim")
        p["bk"] = ("kv_heads", "head_dim")
        p["bv"] = ("kv_heads", "head_dim")
    return p


def _qkv(params, x, cfg: ModelConfig, positions):
    cd = dtype_of(cfg.compute_dtype)
    x = x.astype(cd)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(cd))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(cd))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(cd))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cd)
        k = k + params["bk"].astype(cd)
        v = v + params["bv"].astype(cd)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def _sdpa_xla(q, k, v, mask, head_dim: int):
    """Reference scaled-dot-product attention with GQA head grouping.

    q: (B,S,H,hd), k/v: (B,T,K,hd), mask: (1|B, S, T) boolean (True=attend).
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores * (head_dim ** -0.5)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, v)
    return out.reshape(B, S, H, hd)


def _sdpa_chunked(q, k, v, mask, head_dim: int, chunk: int = 1024):
    """Online-softmax (flash) attention in pure JAX: scans KV in chunks with
    running max/denominator, never materializing the (S, T) score matrix —
    the XLA-path equivalent of the Pallas flash kernel, used by the dry-run
    and valid on TPU.  Chunk size mirrors kernel_synth's block choice."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    if T % min(chunk, T):
        return _sdpa_xla(q, k, v, mask, head_dim)
    c = min(chunk, T)
    nk = T // c
    scale = head_dim ** -0.5
    qg = q.reshape(B, S, K, G, hd)
    mask_b = jnp.broadcast_to(mask, (mask.shape[0], S, T))
    k_c = jnp.moveaxis(k.reshape(B, nk, c, K, hd), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nk, c, K, hd), 1, 0)
    m_c = jnp.moveaxis(mask_b.reshape(mask_b.shape[0], S, nk, c), 2, 0)

    def body(carry, inp):
        m, l, acc = carry
        kc, vc, mc = inp
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kc).astype(jnp.float32)
        s = s * scale
        s = jnp.where(mc[:, None, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        p = jnp.where(mc[:, None, None, :, :], p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bskgd", p.astype(q.dtype),
                        vc).astype(jnp.float32)
        acc_new = acc * jnp.moveaxis(alpha, 3, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, S), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, G, S), jnp.float32)
    a0 = jnp.zeros((B, S, K, G, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_c, v_c, m_c))
    denom = jnp.maximum(jnp.moveaxis(l, 3, 1), 1e-30)[..., None]
    return (acc / denom).astype(q.dtype).reshape(B, S, H, hd)


def sdpa(q, k, v, mask, head_dim: int, lowering: LoweringConfig,
         kind: str = "attention", causal: bool = False):
    """Dispatch-routed scaled-dot-product attention (public: the enc-dec
    family calls it for cross attention).

    The compile cache decides the implementation per (kind, shape, dtype,
    backend); the ISAX kernel entry point is pre-resolved in the record (no
    per-forward import).  ``causal`` says that ``mask`` is
    ``make_mask("causal")``: the op is then ``attention_causal``, whose
    kernel builds the mask from tile indices and skips the tiles above the
    diagonal; the XLA lowerings read ``mask`` either way.
    """
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    rec = lowering.lower("attention_causal" if causal else kind,
                         (B, S, H, K, T, hd), q.dtype)
    if rec.impl == "isax":
        return rec.kernel_fn(q, k, v, mask, sm_scale=head_dim ** -0.5,
                             causal=causal, interpret=lowering.interpret)
    if rec.impl == "chunked":
        return _sdpa_chunked(q, k, v, mask, head_dim)
    return _sdpa_xla(q, k, v, mask, head_dim)


_sdpa = sdpa  # back-compat alias (one release): use layers.sdpa


def attention(params, x, cfg: ModelConfig, mask, positions,
              lowering: Optional[LoweringConfig] = None,
              causal: bool = False):
    """Full-sequence attention (train/prefill).  ``causal``: ``mask`` is
    ``make_mask("causal")`` (see ``sdpa``).  Returns (out, (k, v))."""
    lw = lowering or default_lowering()
    hd = cfg.resolved_head_dim()
    q, k, v = _qkv(params, x, cfg, positions)
    out = sdpa(q, k, v, mask, hd, lw, kind="attention", causal=causal)
    cd = dtype_of(cfg.compute_dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cd)), (k, v)


def attention_decode(params, x, cfg: ModelConfig, k_cache, v_cache, pos,
                     lowering: Optional[LoweringConfig] = None):
    """One-token decode against a static-size KV cache.

    x: (B,1,d); k_cache/v_cache: (B,T,K,hd); pos: () int32 current position.
    Returns (out, new_k_cache, new_v_cache).
    """
    lw = lowering or default_lowering()
    hd = cfg.resolved_head_dim()
    positions = jnp.full((x.shape[0], 1), pos, dtype=jnp.int32)
    q, k, v = _qkv(params, x, cfg, positions)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, axis=1)
    T = k_cache.shape[1]
    mask = (jnp.arange(T)[None, None, :] <= pos)  # (1,1,T)
    out = sdpa(q, k_cache.astype(q.dtype), v_cache.astype(q.dtype),
                jnp.broadcast_to(mask, (x.shape[0], 1, T)), hd, lw,
                kind="attention_decode")
    cd = dtype_of(cfg.compute_dtype)
    return (jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cd)),
            k_cache, v_cache)


def attention_decode_paged(params, x, cfg: ModelConfig, k_pages, v_pages,
                           layer, page_table, seq_lens,
                           lowering: Optional[LoweringConfig] = None):
    """One-token decode against a block-paged KV pool (vLLM-style): the
    read side.  The pool is not written here; ``write_decode_kv`` stores
    every layer's new token after the layer scan, in place.

    x: (B,1,d) new-token activations for every batch slot (inactive slots
    carry dummy tokens so the batch shape is jit-stable).
    k_pages/v_pages: (L, N, page, K, hd) page pools of every layer; this
    layer's pages are gathered straight from them at ``layer``.
    page_table: (B, P) int32 — logical page p of slot b lives in physical
    page ``page_table[b, p]``; unused entries may hold any valid index
    (their positions are masked).
    seq_lens: (B,) int32 tokens already stored per slot; the new token sits
    at logical position ``seq_lens[b]``, put into the gathered context.
    Returns (out (B,1,d), k (B,K,hd), v (B,K,hd)) with k/v the new token's
    entries in the pool's dtype.
    """
    lw = lowering or default_lowering()
    hd = cfg.resolved_head_dim()
    B = x.shape[0]
    T = page_table.shape[1] * k_pages.shape[2]
    positions = seq_lens[:, None].astype(jnp.int32)          # (B,1) per-slot
    q, k, v = _qkv(params, x, cfg, positions)
    k = k.astype(k_pages.dtype)
    v = v.astype(v_pages.dtype)
    here = (jnp.arange(T) == positions)[:, :, None, None]    # (B,T,1,1)
    kg = jnp.where(here, k, k_pages[layer, page_table].reshape(
        B, T, *k_pages.shape[3:]))
    vg = jnp.where(here, v, v_pages[layer, page_table].reshape(
        B, T, *v_pages.shape[3:]))
    mask = jnp.arange(T)[None, None, :] <= seq_lens[:, None, None]
    out = sdpa(q, kg.astype(q.dtype), vg.astype(q.dtype), mask, hd, lw,
                kind="attention_paged")
    cd = dtype_of(cfg.compute_dtype)
    return (jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(cd)),
            k[:, 0], v[:, 0])


def write_decode_kv(k_pages, v_pages, k_new, v_new, page_table, seq_lens,
                    active):
    """The write side of paged decode: store each layer's new token at
    logical position ``seq_lens[b]`` of slot b, one scatter per pool.

    k_pages/v_pages: (L, N, page, K, hd); k_new/v_new: (L, B, K, hd).  The
    pools are scattered as (L·N, page, K, hd) rows ``l·N + phys`` (a view of
    the same buffer), so a donated pool is updated in place.  Inactive slots
    write nowhere (out-of-bounds row + drop).
    """
    n_layers, N, page = k_pages.shape[:3]
    B = page_table.shape[0]
    phys = page_table[jnp.arange(B), seq_lens // page]       # (B,)
    rows = jnp.arange(n_layers)[:, None] * N + phys[None, :]  # (L,B)
    rows = jnp.where(active[None, :], rows, n_layers * N)    # OOB → dropped
    slot = jnp.broadcast_to(seq_lens % page, rows.shape)

    def put(pool, new):
        flat = pool.reshape(n_layers * N, *pool.shape[2:])
        flat = flat.at[rows, slot].set(new, mode="drop")
        return flat.reshape(pool.shape)

    return put(k_pages, k_new), put(v_pages, v_new)


def make_mask(kind: str, S: int, T: Optional[int] = None,
              n_prefix: int = 0) -> jnp.ndarray:
    """(1, S, T) boolean attention mask."""
    T = T or S
    rows = jnp.arange(S)[:, None]
    cols = jnp.arange(T)[None, :]
    if kind == "causal":
        m = cols <= rows
    elif kind == "prefix":  # bidirectional over the first n_prefix tokens
        m = (cols <= rows) | (cols < n_prefix)
    elif kind == "full":
        m = jnp.ones((S, T), dtype=bool)
    else:
        raise ValueError(kind)
    return m[None]


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> dict:
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = dtype_of(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi_gate": (jax.random.normal(k1, (d, ff)) * d ** -0.5).astype(dt),
        "wi_up": (jax.random.normal(k2, (d, ff)) * d ** -0.5).astype(dt),
        "wo": (jax.random.normal(k3, (ff, d)) * ff ** -0.5).astype(dt),
    }


def mlp_axes() -> dict:
    return {"wi_gate": ("embed", "ff"), "wi_up": ("embed", "ff"),
            "wo": ("ff", "embed")}


def mlp(params, x, cfg: ModelConfig,
        lowering: Optional[LoweringConfig] = None):
    lw = lowering or default_lowering()
    cd = dtype_of(cfg.compute_dtype)
    x = x.astype(cd)
    d, ff = params["wi_gate"].shape
    # The bf16/fp32 GEMM is captured through the dispatcher like every other
    # hot op; the ISAX library has no plain-matmul datapath, so the compiler
    # always extracts the XLA reference here (a recorded negative control).
    lw.lower("matmul", (math.prod(x.shape[:-1]), d, ff), x.dtype)
    g = jnp.einsum("bsd,df->bsf", x, params["wi_gate"].astype(cd))
    u = jnp.einsum("bsd,df->bsf", x, params["wi_up"].astype(cd))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * u,
                      params["wo"].astype(cd))


# ---------------------------------------------------------------------------
# Embeddings
# ---------------------------------------------------------------------------

def init_embedding(cfg: ModelConfig, key) -> dict:
    dt = dtype_of(cfg.param_dtype)
    p = {"table": (jax.random.normal(key, (cfg.vocab, cfg.d_model))
                   * cfg.d_model ** -0.5).astype(dt)}
    return p


def embedding_axes() -> dict:
    return {"table": ("vocab", "embed")}


def embed(params, tokens, cfg: ModelConfig):
    cd = dtype_of(cfg.compute_dtype)
    return params["table"].astype(cd)[tokens]


def unembed(table_or_w, x, cfg: ModelConfig,
            lowering: Optional[LoweringConfig] = None):
    lw = lowering or default_lowering()
    cd = dtype_of(cfg.compute_dtype)
    lw.lower("matmul", (math.prod(x.shape[:-1]), x.shape[-1],
                        table_or_w.shape[0]), x.dtype)
    logits = jnp.einsum("bsd,vd->bsv", x.astype(cd),
                        table_or_w.astype(cd))
    return logits.astype(dtype_of(cfg.logit_dtype))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------

def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  ignore_id: int = -1) -> jnp.ndarray:
    """Mean token cross-entropy in fp32; labels == ignore_id are masked."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    w = (labels != ignore_id).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def remat_wrap(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)
