"""Spans of the serving engine, and the prefill padding and flash tile share
of its requests.

Spans are ``jax.profiler`` trace annotations: they are written into the
profiler's own trace, on the clock of the device's ops, and only while a
profiler session is active (``with jax.profiler.trace(dir): eng.run(reqs)``).
With no session a span costs about a microsecond of host time, so the engine
keeps them on always; the profiler session is their only store.

What the engine counts lives on what it counts: each admitted request
records the bucket it was prefilled at (``Request.bucket``), and the engine
its decode dispatches (``ContinuousEngine.decode_steps``).
"""

from __future__ import annotations

import gc

import jax
import jax.numpy as jnp

from repro.core.tiling import causal_tiles
from repro.kernels.ops import flash_tiles


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` with arguments ``args`` (such as a request
    id), to be entered with ``with``."""
    return jax.profiler.TraceAnnotation(name, **args)


def pad_share(requests) -> float:
    """Share of the tokens prefilled for ``requests`` that were bucket
    padding; requests not yet admitted are left out."""
    admitted = [r for r in requests if r.bucket is not None]
    padded = sum(r.bucket for r in admitted)
    if not padded:
        return 0.0
    return 1.0 - sum(r.prompt_len for r in admitted) / padded


def flash_tile_share(requests, cfg) -> float:
    """Share of the causal flash kernel's grid tiles that ran compute in the
    prefills of ``requests`` (model ``cfg``), pooled over the admitted
    ones: the rest lie wholly above the diagonal and are skipped.  Read
    from each request's bucket and the tiles ``kernels.ops.flash_tiles``
    chooses there."""
    dims = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim(),
            jnp.dtype(cfg.compute_dtype).itemsize)
    computed = total = 0
    for r in requests:
        if r.bucket is None:
            continue
        _, tiles = flash_tiles(r.bucket, r.bucket, *dims, causal=True)
        if tiles is None:
            continue
        below, crossing, t = causal_tiles(r.bucket, r.bucket, *tiles)
        computed, total = computed + below + crossing, total + t
    return computed / total if total else 0.0


class _GcSpans:
    """A ``gc.callbacks`` hook that puts an ``engine.gc`` span around each
    collection of generation 1 or 2 (the young generation's are frequent and
    short).  Collections do not nest, so one open span at a time."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] < 1:
            return
        if phase == "start":
            self._open = span("engine.gc", generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


_GC_SPANS = _GcSpans()


def install_gc_spans() -> None:
    """Put the ``engine.gc`` hook on Python's collector, once per process."""
    if _GC_SPANS not in gc.callbacks:
        gc.callbacks.append(_GC_SPANS)
