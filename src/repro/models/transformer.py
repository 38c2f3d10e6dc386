"""Decoder-only transformer covering the dense, moe, and vlm families.

Layers are scan-stacked (params carry a leading 'layers' axis) so HLO size
and compile time are depth-independent — required for 1000+ chip compiles.

Entry points:
  init_params / param_axes
  loss(params, batch)                    — train_4k
  prefill(params, batch)                 — prefill_32k (returns last logits + caches)
  decode_step(params, token, caches, pos)— decode_32k / serving
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.compile.config import LoweringConfig, default_lowering
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.moe import init_moe, moe_axes, moe_mlp


def _is_moe(cfg: ModelConfig) -> bool:
    return cfg.moe is not None


def init_block(cfg: ModelConfig, key) -> dict:
    k1, k2 = jax.random.split(key)
    p = {
        "attn_norm": L.init_rmsnorm(cfg.d_model, L.dtype_of(cfg.param_dtype)),
        "attn": L.init_attention(cfg, k1),
        "mlp_norm": L.init_rmsnorm(cfg.d_model, L.dtype_of(cfg.param_dtype)),
    }
    p["moe" if _is_moe(cfg) else "mlp"] = (
        init_moe(cfg, k2) if _is_moe(cfg) else L.init_mlp(cfg, k2))
    return p


def block_axes(cfg: ModelConfig) -> dict:
    p = {
        "attn_norm": L.rmsnorm_axes(),
        "attn": L.attention_axes(cfg),
        "mlp_norm": L.rmsnorm_axes(),
    }
    if _is_moe(cfg):
        p["moe"] = moe_axes(cfg)
    else:
        p["mlp"] = L.mlp_axes()
    return p


def init_params(cfg: ModelConfig, key) -> dict:
    ke, kl, ku = jax.random.split(key, 3)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    blocks = jax.vmap(lambda k: init_block(cfg, k))(layer_keys)
    p = {
        "embed": L.init_embedding(cfg, ke),
        "blocks": blocks,
        "final_norm": L.init_rmsnorm(cfg.d_model, L.dtype_of(cfg.param_dtype)),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = {"w": (jax.random.normal(ku, (cfg.vocab, cfg.d_model))
                              * cfg.d_model ** -0.5
                              ).astype(L.dtype_of(cfg.param_dtype))}
    return p


def param_axes(cfg: ModelConfig) -> dict:
    stack = jax.tree.map(lambda ax: ("layers",) + ax, block_axes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple))
    p = {
        "embed": L.embedding_axes(),
        "blocks": stack,
        "final_norm": L.rmsnorm_axes(),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = {"w": ("vocab", "embed")}
    return p


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def block_fwd(cfg: ModelConfig, x, bp, mask, positions, lowering=None,
              causal: bool = False):
    x = L.shard_act(x, "btd")
    a, kv = L.attention(bp["attn"],
                        L.rmsnorm(bp["attn_norm"], x, cfg.norm_eps,
                                  lowering=lowering),
                        cfg, mask, positions, lowering=lowering,
                        causal=causal)
    x = x + a
    if _is_moe(cfg):
        y, aux = moe_mlp(bp["moe"],
                         L.rmsnorm(bp["mlp_norm"], x, cfg.norm_eps,
                                   lowering=lowering),
                         cfg, lowering=lowering)
    else:
        y = L.mlp(bp["mlp"], L.rmsnorm(bp["mlp_norm"], x, cfg.norm_eps,
                                       lowering=lowering), cfg,
                  lowering=lowering)
        aux = jnp.zeros((), jnp.float32)
    return x + y, aux, kv


_block_fwd = block_fwd  # back-compat alias (one release): use block_fwd


def backbone(params, x, cfg: ModelConfig, mask, positions,
             collect_kv: bool = False,
             lowering: Optional[LoweringConfig] = None,
             causal: bool = False):
    """Scan over stacked blocks; ``causal``: ``mask`` is
    ``make_mask("causal")``.  Returns (hidden, aux, kv_stack|None)."""
    lw = lowering or default_lowering()

    def body(carry, bp):
        h, aux = carry
        h2, a, kv = block_fwd(cfg, h, bp, mask, positions, lw, causal)
        ys = kv if collect_kv else None
        return (h2, aux + a), ys

    body = L.remat_wrap(body, cfg.remat)
    (h, aux), ys = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                params["blocks"])
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, lowering=lw)
    return h, aux, ys


def _unembed_table(params, cfg: ModelConfig):
    return (params["embed"]["table"] if cfg.tie_embeddings
            else params["unembed"]["w"])


def _inputs_to_x(params, batch, cfg: ModelConfig):
    """tokens (+ optional prefix_embeds for vlm/stub frontends) → (x, S)."""
    x = L.embed(params["embed"], batch["tokens"], cfg)
    if cfg.n_prefix_tokens and "prefix_embeds" in batch:
        pe = batch["prefix_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    return x


def loss(params, batch, cfg: ModelConfig, aux_weight: float = 0.01,
         lowering: Optional[LoweringConfig] = None):
    """batch: tokens (B, S_text), labels (B, S_text) [, prefix_embeds]."""
    x = _inputs_to_x(params, batch, cfg)
    B, S, _ = x.shape
    mask_kind = "prefix" if cfg.family == "vlm" else "causal"
    mask = L.make_mask(mask_kind, S, n_prefix=cfg.n_prefix_tokens
                       if cfg.family == "vlm" else 0)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, aux, _ = backbone(params, x, cfg, mask, positions, lowering=lowering,
                         causal=mask_kind == "causal")
    logits = L.unembed(_unembed_table(params, cfg), h, cfg,
                       lowering=lowering)
    logits = L.shard_act(logits, "btv")
    n_pref = x.shape[1] - batch["tokens"].shape[1]
    logits = logits[:, n_pref:, :]
    return L.cross_entropy(logits, batch["labels"]) + aux_weight * aux


def prefill(params, batch, cfg: ModelConfig, pad_to: Optional[int] = None,
            lowering: Optional[LoweringConfig] = None):
    """Returns (last-position logits, kv caches stacked over layers, length)."""
    x = _inputs_to_x(params, batch, cfg)
    B, S, _ = x.shape
    mask_kind = "prefix" if cfg.family == "vlm" else "causal"
    mask = L.make_mask(mask_kind, S, n_prefix=cfg.n_prefix_tokens
                       if cfg.family == "vlm" else 0)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, _, kv = backbone(params, x, cfg, mask, positions, collect_kv=True,
                        lowering=lowering, causal=mask_kind == "causal")
    k_stack, v_stack = kv  # (L, B, S, K, hd)
    if pad_to and pad_to > S:
        pad = [(0, 0), (0, 0), (0, pad_to - S), (0, 0), (0, 0)]
        k_stack = jnp.pad(k_stack, pad)
        v_stack = jnp.pad(v_stack, pad)
    logits = L.unembed(_unembed_table(params, cfg), h[:, -1:, :], cfg,
                       lowering=lowering)
    return logits[:, 0], {"k": k_stack, "v": v_stack}


def prefill_at(params, batch, length, cfg: ModelConfig,
               lowering: Optional[LoweringConfig] = None):
    """Prefill a (possibly right-padded) prompt and read logits at position
    ``length - 1`` instead of the last position.  Under a causal mask the
    hidden states and KV at positions < ``length`` are unaffected by padding
    tokens after them, so this is exact for bucketed prompts.

    batch: {'tokens': (B, S_pad)}; length: () int32 true prompt length.
    Returns (logits (B, vocab), {'k','v'} (L, B, S_pad, K, hd)).
    """
    x = _inputs_to_x(params, batch, cfg)
    B, S, _ = x.shape
    mask = L.make_mask("causal", S)
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    h, _, kv = backbone(params, x, cfg, mask, positions, collect_kv=True,
                        lowering=lowering, causal=True)
    k_stack, v_stack = kv
    h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
    logits = L.unembed(_unembed_table(params, cfg), h_last, cfg,
                       lowering=lowering)
    return logits[:, 0], {"k": k_stack, "v": v_stack}


def decode_step_paged(params, tokens, k_pages, v_pages, page_table, seq_lens,
                      active, cfg: ModelConfig,
                      lowering: Optional[LoweringConfig] = None):
    """One-token decode through the paged KV pools.  tokens: (B,) int32;
    pools carry a leading layer axis (L, N, page, K, hd).  The layer scan
    only reads the pools (``layers.attention_decode_paged``) and yields each
    layer's new K/V; one scatter per pool then writes them all in place
    (``layers.write_decode_kv``), so the batch/pool shapes stay constant
    across request admissions and evictions and no layer slices or restacks
    the pool.

    Returns (logits (B, vocab), k_pages, v_pages).
    """
    lw = lowering or default_lowering()
    x = L.embed(params["embed"], tokens[:, None], cfg)  # (B,1,d)

    def body(h, xs):
        bp, layer = xs
        a, k, v = L.attention_decode_paged(
            bp["attn"], L.rmsnorm(bp["attn_norm"], h, cfg.norm_eps,
                                  lowering=lw),
            cfg, k_pages, v_pages, layer, page_table, seq_lens, lowering=lw)
        h = h + a
        if _is_moe(cfg):
            y, _ = moe_mlp(bp["moe"], L.rmsnorm(bp["mlp_norm"], h,
                                                cfg.norm_eps, lowering=lw),
                           cfg, lowering=lw)
        else:
            y = L.mlp(bp["mlp"], L.rmsnorm(bp["mlp_norm"], h, cfg.norm_eps,
                                           lowering=lw), cfg, lowering=lw)
        return h + y, (k, v)

    h, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], jnp.arange(k_pages.shape[0])))
    k_pages, v_pages = L.write_decode_kv(k_pages, v_pages, k_new, v_new,
                                         page_table, seq_lens, active)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, lowering=lw)
    logits = L.unembed(_unembed_table(params, cfg), h, cfg, lowering=lw)
    return logits[:, 0], k_pages, v_pages


def decode_step(params, token, caches, pos, cfg: ModelConfig,
                lowering: Optional[LoweringConfig] = None):
    """One-token decode.  token: (B,) int32; caches: {'k','v'} (L,B,T,K,hd);
    pos: () int32.  Returns (logits (B, vocab), new caches)."""
    lw = lowering or default_lowering()
    x = L.embed(params["embed"], token[:, None], cfg)  # (B,1,d)

    def body(h, xs):
        bp, k_c, v_c = xs
        a, k_c, v_c = L.attention_decode(
            bp["attn"], L.rmsnorm(bp["attn_norm"], h, cfg.norm_eps,
                                  lowering=lw),
            cfg, k_c, v_c, pos, lowering=lw)
        h = h + a
        if _is_moe(cfg):
            y, _ = moe_mlp(bp["moe"], L.rmsnorm(bp["mlp_norm"], h,
                                                cfg.norm_eps, lowering=lw),
                           cfg, lowering=lw)
        else:
            y = L.mlp(bp["mlp"], L.rmsnorm(bp["mlp_norm"], h, cfg.norm_eps,
                                           lowering=lw), cfg, lowering=lw)
        return h + y, (k_c, v_c)

    h, (k_new, v_new) = jax.lax.scan(
        body, x, (params["blocks"], caches["k"], caches["v"]))
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps, lowering=lw)
    logits = L.unembed(_unembed_table(params, cfg), h, cfg, lowering=lw)
    return logits[:, 0], {"k": k_new, "v": v_new}
