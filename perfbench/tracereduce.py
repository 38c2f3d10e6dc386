"""Reduction of a profiler trace to device busy time, program and kernel
times, and idle gaps attributed to what the host was doing.

``Trace.from_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes,
with JAX alone.  Device planes are ``/device:<KIND>:<n>``; on each, the line
``XLA Ops`` holds one event per operation the device ran, named by its HLO
text, and ``XLA Modules`` one per program execution (``jit_<fn>(<id>)``).
A scan's ``while`` op spans the ops of its body.  A Pallas kernel is a
``custom-call`` with ``custom_call_target="tpu_custom_call"`` and no name of
its own, so op names are kept in a compact form that still tells kernels
apart: the instruction's name, and for a custom call its target and operand
dtypes (``%closed_call.23 tpu_custom_call (bf16,bf16,bf16,s32)``).  Host
spans are the benchmark's own ``TraceAnnotation`` events on the host plane;
engine spans are those the program writes there, named ``engine.*``
(``repro.serve.telemetry``).  All times are nanoseconds on the profiler's
one clock.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re

#: Host spans the harness writes (see ``perfbench/serve.py``), innermost first
#: when several cover the same instant.
HOST_SPANS = ("prefill", "decode", "admit", "stamp", "step", "idle_wait")
#: Prefix of the engine's own spans, and the name of idle time under none.
ENGINE_PREFIX = "engine."
NO_ENGINE_SPAN = "host:harness"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def compact(hlo: str) -> str:
    """Short name of an ``XLA Ops`` event (see the module docstring)."""
    head, _, rest = hlo.partition(" = ")
    if "custom-call(" not in rest:
        return head
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    args = rest.split("custom-call(", 1)[1].split("), custom_call_target")[0]
    dtypes = ",".join(re.findall(r"\b([a-z]+[0-9]*)\[", args))
    return f"{head} {target.group(1) if target else ''} ({dtypes})"


@dataclasses.dataclass
class Trace:
    """Events of one traced window: ``ops`` and ``modules`` per device, as
    ``[name, start_ns, duration_ns]``; the harness's ``spans`` and the
    program's ``engine_spans`` on the host likewise."""

    ops: dict
    modules: dict
    spans: list
    engine_spans: list = dataclasses.field(default_factory=list)

    @classmethod
    def from_xplane(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData

        ops, modules, spans, engine = {}, {}, [], []
        for plane in ProfileData.from_file(path).planes:
            if _DEVICE_PLANE.match(plane.name):
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        ops.setdefault(plane.name, []).extend(
                            [compact(e.name), e.start_ns, e.duration_ns]
                            for e in line.events)
                    elif line.name == MODULES_LINE:
                        modules.setdefault(plane.name, []).extend(
                            [e.name, e.start_ns, e.duration_ns]
                            for e in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name in HOST_SPANS:
                            spans.append([e.name, e.start_ns, e.duration_ns])
                        elif e.name.startswith(ENGINE_PREFIX):
                            engine.append([e.name, e.start_ns, e.duration_ns])
        return cls(ops, modules, sorted(spans, key=lambda e: e[1]),
                   sorted(engine, key=lambda e: (e[1], -e[2])))

    @classmethod
    def from_dir(cls, trace_dir: str) -> "Trace":
        """The one ``.xplane.pb`` under a ``jax.profiler.trace`` directory."""
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(found) != 1:
            raise FileNotFoundError(f"expected one xplane.pb under "
                                    f"{trace_dir}, found {found}")
        return cls.from_xplane(found[0])

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(d["ops"], d["modules"], d["spans"],
                   d.get("engine_spans", []))

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls.from_json(json.load(f))

    # -- reductions --------------------------------------------------------

    def window(self) -> tuple[float, float]:
        """The traced window: from the first host span to the end of the
        last (the harness is inside one span or another throughout)."""
        if not self.spans:
            raise ValueError("the trace holds no host span of the harness")
        return (min(s[1] for s in self.spans),
                max(s[1] + s[2] for s in self.spans))

    def devices(self) -> list:
        return sorted(self.ops)

    def busy(self, device: str) -> tuple[float, list]:
        """Busy nanoseconds (union of op intervals) inside the window, and
        the idle gaps as ``(start, end)``."""
        lo, hi = self.window()
        iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in
                    self.ops.get(device, []) if s < hi and s + d > lo)
        busy, gaps, cur = 0.0, [], lo
        for s, e in iv:
            if s > cur:
                gaps.append((cur, s))
            if e > cur:
                busy += e - max(s, cur)
                cur = e
        if hi > cur:
            gaps.append((cur, hi))
        return busy, gaps

    def busy_s(self) -> float:
        """Busy seconds, averaged over the traced devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(self.busy(d)[0] for d in devs) / len(devs) / 1e9

    def window_s(self) -> float:
        lo, hi = self.window()
        return (hi - lo) / 1e9

    def idle_share(self) -> float:
        """1 - busy / window, averaged over devices."""
        return 1.0 - self.busy_s() / self.window_s()

    def self_time_by_name(self) -> dict:
        """Device seconds by op name, each op less the ops nested in it (a
        ``while`` less its body), summed over devices."""
        out: dict = {}
        for events in self.ops.values():
            stack: list = []   # [end, name, self_ns] of the enclosing ops
            for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
                while stack and stack[-1][0] <= s:
                    _, n, t = stack.pop()
                    out[n] = out.get(n, 0.0) + t / 1e9
                if stack and s + d <= stack[-1][0]:
                    stack[-1][2] -= d
                stack.append([s + d, name, d])
            for _, n, t in stack:
                out[n] = out.get(n, 0.0) + t / 1e9
        return out

    def matching(self, patterns, kind: str = "ops") -> list:
        """Events whose name contains every substring of any one pattern
        (a pattern is a list of substrings), as ``[name, start_ns,
        duration_ns]``, over all devices."""
        return [e for events in getattr(self, kind).values() for e in events
                if any(all(p in e[0] for p in pat) for pat in patterns)]

    def host_segments(self) -> list:
        """The window cut into ``(start, end, span)`` pieces, each labelled
        with the innermost harness span covering it (``host:other`` where
        none does)."""
        marks = sorted([(st, 1, n) for n, st, _ in self.spans]
                       + [(st + d, -1, n) for n, st, d in self.spans])
        active = {n: 0 for n in HOST_SPANS}
        out, prev = [], None
        for t, step, name in marks:
            if prev is not None and t > prev:
                inner = next((n for n in HOST_SPANS if active[n] > 0),
                             "host:other")
                out.append((prev, t, inner))
            active[name] += step
            prev = t
        return out

    def engine_segments(self) -> list:
        """The window cut into ``(start, end, span)`` pieces, each labelled
        with the innermost engine span (the one that opened last) covering
        it, ``NO_ENGINE_SPAN`` where none does.  The engine's spans nest,
        ``engine.gc`` included, since all run on one thread."""
        lo, hi = self.window()
        spans = self.engine_spans
        # at one instant: closes before opens, and the longer span opens first
        marks = sorted([(s, 1, -d, n) for n, s, d in spans]
                       + [(s + d, 0, d, n) for n, s, d in spans])
        segs, open_, prev = [], [], lo
        for t, opens, _, name in marks + [(hi, 0, 0, None)]:
            t = min(max(t, lo), hi)
            if t > prev:
                segs.append((prev, t, open_[-1] if open_ else NO_ENGINE_SPAN))
                prev = t
            if name is None:
                break
            if opens:
                open_.append(name)
            else:
                open_.remove(name)
        return segs

    def idle_by_host(self) -> dict:
        """Idle device seconds by what the harness was doing during each
        part of each gap, averaged over devices."""
        return self._idle_by(self.host_segments())

    def idle_by_engine_span(self) -> dict:
        """Idle device seconds by the innermost engine span, averaged over
        devices."""
        return self._idle_by(self.engine_segments())

    def _idle_by(self, segs: list) -> dict:
        out: dict = {}
        devs = self.devices()
        for d in devs:
            k = 0
            for s, e in self.busy(d)[1]:
                while k < len(segs) and segs[k][1] <= s:
                    k += 1
                j = k
                while j < len(segs) and segs[j][0] < e:
                    a, b, name = segs[j]
                    part = min(b, e) - max(a, s)
                    out[name] = out.get(name, 0.0) + part / 1e9 / len(devs)
                    j += 1
        return out


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of ``{name: seconds}`` as ``[name, s]``."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
