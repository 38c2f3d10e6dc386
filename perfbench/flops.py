"""The least time the chip needs for a piece of work.

The operations and bytes of each architecture's steps and kernels are counted
by its module under ``perfbench/arch/`` (``prefill_flops``, ``decode_flops``,
``flash_calls``); this is the roofline they are held against.
"""

from __future__ import annotations


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    """Least seconds the chip needs: the larger of the compute bound and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
