"""JAX's persistent compilation cache, placed from outside.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``
and the examples) call :func:`enable_compile_cache` from ``main()``.
Importing this module changes nothing, and tests never call it.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: Checkout root (this file is ``src/repro/launch/compile_cache.py``).
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is not set.
#: A fixed path: an entry is only found again under the directory it was
#: written to, so the path must not depend on a temp name, a pid or the time.
DEFAULT_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here.  Otherwise the cache goes to ``.jax_cache/`` at
    the repository root, which git ignores.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
