"""Shared tiling arithmetic for kernel wrappers, schedulers, and dispatch.

These helpers used to live as private functions inside ``kernels/ops.py``
and were imported across module boundaries (``compile/dispatch.py``,
``pointcloud/ops.py``) under their ``_``-prefixed names.  They are the
public home now: any code that derives a launchable tile from a synthesized
schedule — domain packages in ``repro/targets``, the op wrappers, the
dispatcher — shares exactly these definitions, so the recorded schedule and
the executed schedule can never disagree on the rounding rule.
"""

from __future__ import annotations

import numpy as np


def down_pow2(n: int, cap: int) -> int:
    """Largest power-of-two divisor of ``n``, at most ``cap``.

    This is the tile-rounding rule every kernel wrapper applies to a
    synthesized block size: it always divides ``n`` (so divisibility can
    never fail), degrading toward 1-wide tiles when ``n`` has a large odd
    factor.
    """
    d = 1
    while n % (d * 2) == 0 and d * 2 <= cap:
        d *= 2
    return d


def tpu_block_ok(block: tuple[int, ...], dims: tuple[int, ...]) -> bool:
    """Mosaic's block-shape rule for the two minor dims of a block.

    ``block`` and ``dims`` give the last two dims of a ``BlockSpec`` block
    and of the array it tiles (one dim for a 1-D array).  The TPU compiler
    accepts a block whose second-minor dim is a multiple of 8 and whose
    minor dim is a multiple of 128, each unless it spans the whole array
    dim.  The interpreter accepts any block, so only this check says
    whether a tile chosen on the CPU would compile for the chip.
    """
    mults = (8, 128)[-len(block):]
    return all(b % m == 0 or b == d for b, d, m in zip(block, dims, mults))


def dtype_itemsize(dtype: str) -> int:
    """Itemsize in bytes for a dtype *name*, matching ``np.dtype`` where
    possible.

    Kernel wrappers derive tiles from ``array.dtype.itemsize``; dispatch-side
    schedulers only see the dtype string in the cache key.  Using the same
    numpy resolution (with a ``bfloat16``-style width fallback for names
    numpy does not know unless ml_dtypes registered them) keeps the recorded
    schedule identical to the one the wrapper re-derives.
    """
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return 2 if dtype.endswith("16") else 4


def last_causal_tile(qi, block_q: int, block_k: int, offset):
    """Index of the last kv tile that q tile ``qi`` sees under the causal
    mask ``col <= row + offset`` (on ints, and on traced scalars inside a
    kernel or an index map)."""
    return ((qi + 1) * block_q - 1 + offset) // block_k


def causal_tiles(S: int, T: int, block_q: int, block_k: int):
    """``(below, crossing, total)`` (q, kv) tile pairs of one head block of
    a causal attention call, offset ``T − S``: tiles wholly at or below the
    diagonal, tiles the diagonal crosses, and all of them.  The causal
    flash kernel computes the first two kinds (masking only the second)
    and skips the rest."""
    nq, nk, offset = S // block_q, T // block_k, T - S
    below = crossing = 0
    for qi in range(nq):
        first = qi * block_q + offset       # shifted row of the tile's top
        n = min(nk, last_causal_tile(qi, block_q, block_k, offset) + 1)
        full = min(n, max(0, (first + 1) // block_k))
        below, crossing = below + full, crossing + n - full
    return below, crossing, nq * nk
