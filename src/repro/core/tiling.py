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
