"""What a per-layer metric reader is given."""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass
class Observed:
    """One run, as the readers see it.

    ``calls`` are the engine calls the host made between the profiler's start
    and stop; since every step waits for its tokens, the device ran exactly
    their work in the traced window.  ``trace`` is None in a run without a
    trace.  ``arch`` is the configuration's module under ``perfbench/arch/``,
    which counts the model's operations and bytes.
    """

    cfg: dict
    peaks: dict
    names: dict
    served: object
    trace: Optional[object] = None
    calls: Optional[object] = None
    trace_window: Optional[tuple] = None
    arch: Optional[object] = None


def per_call(events) -> Optional[float]:
    """Mean duration in seconds of trace events, None without any."""
    if not events:
        return None
    return sum(e[2] for e in events) / len(events) / 1e9
