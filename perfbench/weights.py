"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout that ``repro.models.transformer`` serves (layers
stacked on a leading axis), in the configuration's dtype.  The benchmark makes
the weights, not the program, so the plain reference may read them.  Norm
scales are drawn around 1, not set to 1, so that a path that dropped a scale
would show in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def key_of(seed: int):
    """A JAX key for any non-negative whole number, also past 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.key(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32((seed >> 32) & 0xFFFFFFFF))


def shapes(cfg: dict) -> dict:
    """Shape of every leaf, by path."""
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


def _std(path: str, shape, cfg: dict) -> float:
    if path.endswith("attn/wo"):
        return (shape[1] * shape[2]) ** -0.5
    if path.endswith("mlp/wo"):
        return shape[1] ** -0.5
    return cfg["hidden_size"] ** -0.5


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(cfg: dict, seed: int) -> dict:
    """The weight tree of ``cfg`` for ``seed``, on the default device."""
    return _make(tuple(sorted(shapes(cfg).items())), cfg["hidden_size"],
                 cfg["torch_dtype"])(key_of(seed))


@functools.lru_cache(maxsize=None)
def _make(leaves: tuple, d: int, dtype: str):
    dt = jnp.dtype(dtype)
    cfg = {"hidden_size": d}

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        flat = {}
        for k, (path, shape) in zip(keys, leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if path.endswith("scale"):
                flat[path] = (1.0 + 0.1 * z).astype(dt)
            else:
                flat[path] = (z * _std(path, shape, cfg)).astype(dt)
        return _nest(flat)

    return init
