"""Driving ``ContinuousEngine`` through an open-loop schedule, and what the
host sees of it.

The window uses the engine's public API: each request is ``submit``-ted at
its due wall-clock time (``arrival_step=0``), and ``step()`` runs in a loop;
when the engine has nothing to do the harness sleeps until the next due time.
``step()`` blocks on its token fetch, so after each one the harness stamps
every new output token itself: time to first token counts from the due time,
and every gap between tokens is one a streaming client would see.

Host spans (``jax.profiler.TraceAnnotation``, on the profiler's clock) are
put around the engine's ``_admit``, ``_prefill`` and ``_decode`` calls by
wrapping those attributes of the engine instance; the program is not edited.
The same wrappers record, per prefill, its bucket and prompt length and, per
decode, the slots in use and their context lengths, which the per-layer
metrics read.
"""

from __future__ import annotations

import dataclasses
import time
import types
from typing import Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from perfbench import traffic
from repro.serve.scheduler import Request


@dataclasses.dataclass
class Timed:
    """A request with the times the harness saw."""

    req: Request
    due: float                       # perf_counter time it was due
    section: str
    submitted: Optional[float] = None
    t_prefill: Optional[float] = None  # start of its own prefill call
    stamps: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.stamps) >= self.req.max_new_tokens


@dataclasses.dataclass
class Calls:
    """Per-call records of the engine's programs, host side."""

    prefill: list = dataclasses.field(default_factory=list)  # (t, bucket, n)
    decode: list = dataclasses.field(default_factory=list)   # (t, slots, ctx)


class Instrumented:
    """Spans and call records around one engine's internal calls."""

    def __init__(self, eng):
        self.calls = Calls()
        self.current: Optional[Timed] = None
        self.by_rid: dict[int, Timed] = {}
        admit, prefill, decode = eng._admit, eng._prefill, eng._decode

        def _admit(req):
            self.current = self.by_rid.get(req.rid)
            with TraceAnnotation("admit"):
                return admit(req)

        def _prefill(p, batch, length):
            t = time.perf_counter()
            if self.current is not None:
                self.current.t_prefill = t
            n = self.current.req.prompt_len if self.current else -1
            self.calls.prefill.append((t, batch["tokens"].shape[1], n))
            with TraceAnnotation("prefill"):
                return prefill(p, batch, length)

        def _decode(*args):
            slots = eng.scheduler.active_slots
            ctx = [int(eng.cache.seq_lens[s]) + 1 for s in slots]
            self.calls.decode.append((time.perf_counter(), len(slots), ctx))
            with TraceAnnotation("decode"):
                return decode(*args)

        eng._admit, eng._prefill, eng._decode = _admit, _prefill, _decode

    def reset(self) -> None:
        self.calls = Calls()


def warm_up(eng, buckets, vocab: int, seed: int) -> None:
    """Serve one two-token request per bucket: compiles each prefill bucket
    the traffic uses, the decode step and the host-side scatter.  Prompts
    are two short of the bucket, so that the largest bucket fits
    ``max_len`` with its two tokens."""
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=-1 - i,
                    prompt=rng.integers(0, vocab, b - 2, dtype=np.int32),
                    max_new_tokens=2) for i, b in enumerate(buckets)]
    for r in reqs:
        eng.submit(r)
    while eng.queue or eng.scheduler.has_active():
        eng.step()
    jax.block_until_ready(device_arrays(eng))


_OPAQUE = (type, types.ModuleType, types.FunctionType, types.MethodType,
           types.BuiltinFunctionType)


def device_arrays(obj) -> list:
    """The live device arrays reachable from ``obj`` through attributes and
    containers (functions, classes and modules are not followed)."""
    out, seen, stack = [], set(), [obj]
    while stack:
        o = stack.pop()
        if id(o) in seen or isinstance(o, _OPAQUE):
            continue
        seen.add(id(o))
        if isinstance(o, jax.Array):
            if not o.is_deleted():
                out.append(o)
        elif isinstance(o, dict):
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        elif hasattr(o, "__dict__"):
            stack.extend(vars(o).values())
    return out


@dataclasses.dataclass
class Served:
    """What one pass over a schedule produced."""

    timed: list
    window: tuple           # (start, end) perf_counter
    stopped: float
    calls: Calls
    lag: np.ndarray         # submit time - due time, per request, seconds
    compiles: int
    trace_window: Optional[tuple] = None


class CompileCounter:
    """Counts JAX's tracing and compilation events while ``on``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name in self.EVENTS:
            self.count += 1


def run_schedule(eng, inst: Instrumented, items, start: float,
                 ramp_s: float, seconds: float, grace_s: float, *,
                 counter: Optional[CompileCounter] = None,
                 keep_load: bool = True,
                 trace: Optional[tuple] = None) -> Served:
    """Offer ``items`` (from ``traffic.schedule``) from perf_counter time
    ``start``; measure the ``seconds`` after ``ramp_s``.

    After the window the harness keeps stepping until every request due in it
    is done, or ``grace_s`` has passed; with ``keep_load`` the ``tail``
    section's requests keep being offered meanwhile.  ``trace`` is
    ``(start_fn, stop_fn, t_on, t_off)``: the profiler is started at the
    first step boundary after ``t_on`` and stopped at the first after
    ``t_off``.
    """
    w0, w1 = start + ramp_s, start + ramp_s + seconds
    timed = [Timed(Request(rid=i, prompt=it.prompt,
                           max_new_tokens=it.max_new_tokens),
                   start + it.due_s, it.section)
             for i, it in enumerate(items)
             if keep_load or it.section != "tail"]
    inst.by_rid = {t.req.rid: t for t in timed}
    inflight: list[Timed] = []
    nxt, n = 0, len(timed)
    measured = [t for t in timed if t.section == "window"]
    tr_on = tr_off = None
    while True:
        now = time.perf_counter()
        if trace is not None:
            if tr_on is None and now >= trace[2]:
                trace[0]()
                tr_on = time.perf_counter()
            elif tr_on is not None and tr_off is None and now >= trace[3]:
                trace[1]()
                tr_off = time.perf_counter()
        if counter is not None:
            counter.on = w0 <= now < w1
        while nxt < n and timed[nxt].due <= now:
            t = timed[nxt]
            eng.submit(t.req)
            t.submitted = now
            inflight.append(t)
            nxt += 1
        if now >= w1 and (now >= w1 + grace_s
                          or all(t.done for t in measured)):
            break
        if eng.queue or eng.scheduler.has_active():
            with TraceAnnotation("step"):
                eng.step()
            now = time.perf_counter()
            with TraceAnnotation("stamp"):
                still = []
                for t in inflight:
                    new = len(t.req.out_tokens) - len(t.stamps)
                    t.stamps.extend([now] * new)
                    if not t.done:
                        still.append(t)
                inflight = still
        else:
            wake = timed[nxt].due if nxt < n else w1 + grace_s
            with TraceAnnotation("idle_wait"):
                time.sleep(max(0.0, min(wake, w1 + grace_s) - now))
    if counter is not None:
        counter.on = False
    if tr_on is not None and tr_off is None:
        trace[1]()
        tr_off = time.perf_counter()
    lag = np.array([t.submitted - t.due for t in timed
                    if t.submitted is not None])
    return Served(timed=timed, window=(w0, w1), stopped=time.perf_counter(),
                  calls=inst.calls, lag=lag,
                  compiles=counter.count if counter else 0,
                  trace_window=(tr_on, tr_off) if tr_on else None)


def _pct(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(s: Served) -> dict:
    """The end-to-end metrics of one pass, by name, in their units."""
    w0, w1 = s.window
    measured = [t for t in s.timed if t.section == "window"]
    # A request that never produced its first token counts as missing: its
    # time to first token is at least the time the harness waited for it.
    ttft = [t.stamps[0] - t.due if t.stamps else s.stopped - t.due
            for t in measured]
    gaps = np.concatenate([np.diff(t.stamps) for t in measured
                           if len(t.stamps) > 1] or [np.zeros(0)])
    out_tokens = sum(int(np.sum((np.asarray(t.stamps) >= w0)
                                & (np.asarray(t.stamps) < w1)))
                     for t in s.timed)
    return {
        "ttft_p90_s": _pct(ttft, 90),
        "itl_p50_s": _pct(gaps, 50),
        "itl_p95_s": _pct(gaps, 95),
        "output_tokens_per_s": out_tokens / (w1 - w0),
    }


def attempted_failed(s: Served) -> tuple[int, int]:
    """Requests due in the window, and those of them not done at the end."""
    measured = [t for t in s.timed if t.section == "window"]
    return len(measured), sum(not t.done for t in measured)


def items_for(cell, seconds: float, seed: int) -> list:
    """The seeded schedule of a cell."""
    return traffic.schedule(cell.traffic, seconds, seed,
                            cell.config["vocab_size"])

