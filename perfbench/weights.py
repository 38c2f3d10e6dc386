"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program serves, as the configuration's
architecture module gives it (``weight_shapes`` and ``weight_std`` of
``perfbench/arch/<model_type>.py``), in the configuration's dtype.  The
benchmark makes the weights, not the program, so the plain reference may
read them.  Norm scales are drawn around 1, not set to 1, so that a path
that dropped a scale would show in the comparison.
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


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def make(cfg: dict, seed: int, arch) -> dict:
    """The weight tree of ``cfg`` for ``seed``, on the default device."""
    leaves = tuple(sorted(
        (path, tuple(shape), arch.weight_std(path, shape, cfg))
        for path, shape in arch.weight_shapes(cfg).items()))
    return _make(leaves, cfg["torch_dtype"])(key_of(seed))


@functools.lru_cache(maxsize=None)
def _make(leaves: tuple, dtype: str):
    dt = jnp.dtype(dtype)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(leaves))
        flat = {}
        for k, (path, shape, std) in zip(keys, leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if path.endswith("scale"):
                flat[path] = (1.0 + 0.1 * z).astype(dt)
            else:
                flat[path] = (z * std).astype(dt)
        return _nest(flat)

    return init
