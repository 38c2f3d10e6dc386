"""The engine's spans in a trace (``Trace.engine_spans``, read by
``perfbench/engine_split.py`` and ``run.py``'s ``breakdown.engine_idle``),
and the readers of ``metrics/step_host_ms.py`` and ``metrics/prefill_pad_share.py``:
known answers on a hand-made trace, the recorded trace, which holds no
engine span, and one traced pass of the tiny cell on the CPU."""

import dataclasses
import json
import pathlib
from types import SimpleNamespace

import pytest

from perfbench import bench, engine_split
from perfbench.metrics._common import Observed
from perfbench.tests import tiny
from perfbench.tracereduce import NO_ENGINE_SPAN as HARNESS
from perfbench.tracereduce import Trace

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"
NAMES = {"decode_program": [["jit__decode_fn("]]}
STEP_HOST_MS = bench.metric_reader("step_host_ms.chat")


def handmade(spans=None, engine_spans=()):
    """Device busy 0-10, 30-40, 60-70 (two decode programs); the harness
    in ``step`` 0-50 (``decode`` 2-5) and ``stamp`` 50-70."""
    return Trace(
        ops={DEV: [["fusion.1", 0, 10], ["fusion.2", 30, 10],
                   ["fusion.3", 60, 10]]},
        modules={DEV: [["jit__decode_fn(2)", 0, 10],
                       ["jit__decode_fn(2)", 30, 10]]},
        spans=spans if spans is not None else [
            ["step", 0, 50], ["decode", 2, 3], ["stamp", 50, 20]],
        engine_spans=list(engine_spans))


# The engine's step 1-48 holding decode 2-6, fetch 8-35 and bookkeep 40-45,
# and a collection 44-55 that outlasts the step.
ENGINE_SPANS = [["engine.step", 1, 47], ["engine.decode", 2, 4],
                ["engine.fetch", 8, 27], ["engine.bookkeep", 40, 5],
                ["engine.gc", 44, 11]]


def test_idle_by_innermost_engine_span():
    tr = handmade(engine_spans=ENGINE_SPANS)
    assert tr.idle_by_engine_span() == pytest.approx(
        {"engine.fetch": 20e-9, "engine.bookkeep": 4e-9, "engine.gc": 11e-9,
         HARNESS: 5e-9})


def test_span_opened_at_the_same_instant_as_its_parent_is_innermost():
    tr = handmade(engine_spans=[["engine.step", 10, 40],
                                ["engine.fetch", 10, 25]])
    assert tr.idle_by_engine_span() == pytest.approx(
        {"engine.fetch": 20e-9, "engine.step": 10e-9, HARNESS: 10e-9})


def _obs(tr, names=NAMES):
    return Observed(cfg={}, peaks={}, names=names, served=None, trace=tr)


def test_step_host_ms():
    # idle under the harness's step 0-50: 10-30 and 40-50, over two runs
    assert STEP_HOST_MS(_obs(handmade())) == pytest.approx(15e-6)


@pytest.mark.parametrize("obs", [
    _obs(None),
    _obs(handmade(spans=[])),
    _obs(handmade(), names={"decode_program": [["jit__other("]]}),
], ids=["untraced", "no_harness_span", "no_decode_run"])
def test_step_host_ms_none(obs):
    assert STEP_HOST_MS(obs) is None


def _recorded():
    d = json.loads((DATA / "small_trace.json").read_text())
    calls = json.loads((DATA / "small_calls.json").read_text())
    from perfbench.serve import Calls
    return Trace.from_json(d), Calls(
        prefill=[tuple(c) for c in calls["prefill"]],
        decode=[tuple(c) for c in calls["decode"]])


def test_recorded_trace_without_engine_spans():
    """The recorded trace (a program with no engine span): all its idle
    falls under ``host:harness``, and ``step_host_ms`` is the idle under the
    harness's step and the spans nested in it, per decode run."""
    tr, _ = _recorded()
    idle = tr.idle_by_host()
    assert tr.engine_spans == []
    assert tr.idle_by_engine_span() == pytest.approx(
        {HARNESS: sum(idle.values())})
    names = bench.trace_names()
    runs = len(tr.matching(names["decode_program"], "modules"))
    want = sum(idle.get(s, 0.0) for s in ("step", "admit", "prefill",
                                          "decode"))
    assert runs > 0 and want > 0
    assert STEP_HOST_MS(_obs(tr, names)) == pytest.approx(1e3 * want / runs)


@dataclasses.dataclass
class _OldRequest:
    """A request of a program that records no bucket."""

    prompt_len: int


def _pad_obs(reqs, prefills, trace_window=(10.0, 20.0)):
    timed = [SimpleNamespace(req=r, t_prefill=t)
             for r, t in zip(reqs, prefills)]
    return Observed(cfg={}, peaks={}, names={},
                    served=SimpleNamespace(timed=timed),
                    trace_window=trace_window)


def test_prefill_pad_share_over_the_traced_window():
    import numpy as np

    from repro.serve.scheduler import Request

    reqs = []
    for n, b in ((700, 1024), (1500, 2048), (3000, 4096), (100, 1024)):
        reqs.append(Request(rid=len(reqs), prompt=np.zeros(n, np.int32),
                            max_new_tokens=1, bucket=b))
    # the last request was prefilled before the profiler started
    got = bench.metric_reader("prefill_pad_share.longprompt")(
        _pad_obs(reqs, (11.0, 12.0, 19.0, 9.0)))
    assert got == pytest.approx(100 * (7168 - 5200) / 7168)


@pytest.mark.parametrize("obs", [
    _pad_obs([_OldRequest(700)], (11.0,)),
    _pad_obs([SimpleNamespace(prompt_len=700, bucket=1024)], (9.0,)),
    _pad_obs([SimpleNamespace(prompt_len=700, bucket=1024)], (11.0,),
             trace_window=None),
], ids=["no_bucket_recorded", "no_prefill_in_window", "untraced"])
def test_prefill_pad_share_none(obs):
    assert bench.metric_reader("prefill_pad_share.longprompt")(obs) is None


def test_traced_pass_splits_idle_by_engine_span(tmp_path):
    root = tiny.make_root(tmp_path)
    out = engine_split.traced_pass("tiny.test", 2 ** 31 + 11, 2.0,
                                   require_device=False, root=root)
    assert set(out) == {"engine_idle", "idle_gaps", "step_host_ms"}
    # the CPU trace has no device plane: nothing is idle, nothing is read
    assert out["engine_idle"] == [] and out["step_host_ms"] is None


def test_traced_run_breakdown_has_engine_idle(tmp_path):
    from perfbench import run

    root = tiny.make_root(tmp_path)
    res = run.run(run.parse(["--workload", "tiny.test", "--seed",
                             str(2 ** 31 + 17), "--seconds", "2",
                             "--trace", "1"]),
                  require_device=False, root=root)
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps",
                                     "engine_idle"}
    # the CPU trace has no device plane: nothing is idle
    assert res["breakdown"]["engine_idle"] == []
    assert res["correct"] is True


def test_engine_spans_read_back(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("engine.step"):
            with jax.profiler.TraceAnnotation("engine.fetch"):
                pass
        with jax.profiler.TraceAnnotation("step"):
            pass
    tr = Trace.from_dir(str(tmp_path))
    spans = tr.engine_spans
    assert [s[0] for s in tr.spans] == ["step"]
    assert [s[0] for s in spans] == ["engine.step", "engine.fetch"]
    assert spans[0][1] <= spans[1][1]
    assert spans[1][1] + spans[1][2] <= spans[0][1] + spans[0][2]
