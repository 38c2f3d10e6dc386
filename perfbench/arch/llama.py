"""The Llama layout (``model_type`` ``"llama"``): a dense decoder.

Token embedding, then per layer RMSNorm, grouped-query attention with rotary
position embedding (rotate-half form, ``rope_theta``), a causal mask and
scores scaled by 1/sqrt(head_dim), a residual add, RMSNorm and a SwiGLU MLP
with its own residual add; a final RMSNorm and the output head (the
embedding when ``tie_word_embeddings``).  Every layer is alike, and the
program stacks the layers' weights on a leading axis.

The interface is described in ``perfbench/arch/__init__.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.reference import Matmul, attention, rms_norm, rope


def program_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig

    dtype = cfg["torch_dtype"]
    return ModelConfig(
        name=cfg["name"], family=cfg["serve"]["family"],
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["attention_bias"],
        rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        norm_eps=float(cfg["rms_norm_eps"]),
        param_dtype=dtype, compute_dtype=dtype, logit_dtype="float32",
        remat="none")


# -- weights ---------------------------------------------------------------

def weight_shapes(cfg: dict) -> dict:
    """Shape of every leaf, by path (``repro.models.transformer``'s tree)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {
        "embed/table": (V, d),
        "blocks/attn_norm/scale": (L, d),
        "blocks/attn/wq": (L, d, H, hd),
        "blocks/attn/wk": (L, d, K, hd),
        "blocks/attn/wv": (L, d, K, hd),
        "blocks/attn/wo": (L, H, hd, d),
        "blocks/mlp_norm/scale": (L, d),
        "blocks/mlp/wi_gate": (L, d, ff),
        "blocks/mlp/wi_up": (L, d, ff),
        "blocks/mlp/wo": (L, ff, d),
        "final_norm/scale": (d,),
    }
    if not cfg["tie_word_embeddings"]:
        out["unembed/w"] = (V, d)
    return out


def weight_std(path: str, shape, cfg: dict) -> float:
    """One over the square root of each matrix's input width."""
    if path.endswith("attn/wo"):
        return (shape[1] * shape[2]) ** -0.5
    if path.endswith("mlp/wo"):
        return shape[1] ** -0.5
    return cfg["hidden_size"] ** -0.5


# -- reference -------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _layer(h, blocks, i, scales, *, cfg_items, control):
    cfg = dict(cfg_items)
    mm = Matmul(control, scales)
    w = {path: mm.weight(path, leaf[i]) for path, leaf in blocks.items()}
    T = h.shape[0]
    pos = jnp.arange(T)
    x = rms_norm(h, w["attn_norm/scale"], cfg["rms_norm_eps"])
    q = rope(mm("td,dnk->tnk", x, w["attn/wq"]), pos, cfg["rope_theta"])
    k = rope(mm("td,dnk->tnk", x, w["attn/wk"]), pos, cfg["rope_theta"])
    v = mm("td,dnk->tnk", x, w["attn/wv"])
    o = attention(q, k, v, q.shape[-1] ** -0.5, mm)
    h = h + mm("tnk,nkd->td", o, w["attn/wo"])
    x = rms_norm(h, w["mlp_norm/scale"], cfg["rms_norm_eps"])
    g = mm("td,df->tf", x, w["mlp/wi_gate"])
    u = mm("td,df->tf", x, w["mlp/wi_up"])
    y = mm("tf,fd->td", jax.nn.silu(g) * u, w["mlp/wo"])
    return h + y


@functools.partial(jax.jit, static_argnames=("control",))
def _embed(table, tokens, scales, *, control):
    return Matmul(control, scales).weight("embed", table[tokens])


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _head(h, rows, norm, out_w, scales, *, cfg_items, control):
    cfg = dict(cfg_items)
    mm = Matmul(control, scales)
    x = rms_norm(h[rows], norm.astype(jnp.float32), cfg["rms_norm_eps"])
    return mm("td,vd->tv", x, mm.weight("head", out_w))


def reference_logits(cfg: dict, params: dict, tokens, rows, control,
                     scales: dict):
    """Logits (len(rows), vocab) of the padded sequence ``tokens``, one
    jitted call per layer, so that one layer at a time is upcast."""
    kw = {"cfg_items": (("rms_norm_eps", float(cfg["rms_norm_eps"])),
                        ("rope_theta", float(cfg["rope_theta"]))),
          "control": control}
    head = "embed/table" if cfg["tie_word_embeddings"] else "unembed/w"
    pick = lambda name, path: {name: scales[path]} if path in scales else {}
    blocks = {p[len("blocks/"):]: v for p, v in params.items()
              if p.startswith("blocks/")}
    block_scales = {p: scales["blocks/" + p] for p in blocks
                    if "blocks/" + p in scales}
    h = _embed(params["embed/table"], tokens, pick("embed", "embed/table"),
               control=control)
    for i in range(cfg["num_hidden_layers"]):
        h = _layer(h, blocks, i, block_scales, **kw)
    return _head(h, rows, params["final_norm/scale"], params[head],
                 pick("head", head), **kw)


# -- work counts -----------------------------------------------------------
#
# Counts are of the work the computation needs, whatever implements it: a
# multiply-add is 2 operations, a causal attention call counts only the
# (query, key) pairs at or below the diagonal, and bytes are those of the
# call's inputs and outputs read or written once.

def _dims(cfg: dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def layer_matmul_params(cfg: dict) -> int:
    """Weights of one layer's matrix products (q, k, v, o and SwiGLU)."""
    d, H, K, hd, ff, _, _ = _dims(cfg)
    return d * hd * (H + 2 * K) + H * hd * d + 3 * d * ff


def attention_pair_flops(cfg: dict) -> int:
    """Operations per (query, key) pair in one layer: q.k and p.v for every
    query head."""
    _, H, _, hd, _, _, _ = _dims(cfg)
    return 4 * H * hd


def prefill_flops(cfg: dict, n: int) -> float:
    """Model operations of a prefill of ``n`` real prompt tokens, with the
    logits taken at one position (as the serve path does)."""
    d, _, _, _, _, V, L = _dims(cfg)
    pairs = n * (n + 1) / 2
    return (2.0 * n * L * layer_matmul_params(cfg)
            + L * attention_pair_flops(cfg) * pairs + 2.0 * d * V)


def decode_flops(cfg: dict, contexts) -> float:
    """Model operations of one decode step that extends sequences whose
    attention spans are ``contexts`` (keys attended, new token included)."""
    d, _, _, _, _, V, L = _dims(cfg)
    per_token = 2.0 * L * layer_matmul_params(cfg) + 2.0 * d * V
    return (len(contexts) * per_token
            + L * attention_pair_flops(cfg) * float(sum(contexts)))


def flash_call(cfg: dict, s: int, dtype_bytes: int = 2) -> tuple[float, float]:
    """(operations, bytes) of one causal attention call over ``s`` positions
    of one sequence: q, k, v read and o written once."""
    _, H, K, hd, _, _, _ = _dims(cfg)
    flops = attention_pair_flops(cfg) * s * (s + 1) / 2
    nbytes = dtype_bytes * s * hd * (2 * H + 2 * K)
    return float(flops), float(nbytes)


def flash_calls(cfg: dict, s: int) -> list:
    """One prefill at bucket ``s`` makes one alike call in every layer."""
    return [flash_call(cfg, s)] * cfg["num_hidden_layers"]
