"""The trace reduction on a hand-made trace with known answers, and on a
small trace recorded on a TPU v5e (``data/``)."""

import json
import pathlib

import pytest

from perfbench import bench
from perfbench.arch import llama
from perfbench.metrics._common import Observed
from perfbench.tracereduce import Trace, top

DATA = pathlib.Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def handmade():
    return Trace(
        ops={DEV: [["fusion.1", 0, 10], ["_flash_kernel", 5, 10],
                   ["fusion.2", 30, 10]]},
        modules={DEV: [["jit__lambda(1)", 0, 15], ["jit__decode_fn(2)", 30,
                                                   10]]},
        spans=[["step", 0, 45], ["decode", 2, 3], ["idle_wait", 45, 5]])


def test_busy_union_and_idle_share():
    tr = handmade()
    busy, gaps = tr.busy(DEV)
    assert busy == 25 and gaps == [(15, 30), (40, 50)]
    assert tr.window_s() == pytest.approx(50e-9)
    assert tr.busy_s() == pytest.approx(25e-9)
    assert tr.idle_share() == pytest.approx(0.5)


def test_idle_gaps_by_host_span():
    got = handmade().idle_by_host()
    assert got == pytest.approx({"step": 20e-9, "idle_wait": 5e-9})


def test_self_time_and_matching():
    tr = handmade()
    assert tr.self_time_by_name()["_flash_kernel"] == pytest.approx(10e-9)
    assert len(tr.matching([["jit__decode_fn("]], "modules")) == 1
    assert len(tr.matching([["fusion", "2"], ["_flash"]])) == 2


def test_self_time_leaves_out_nested_ops():
    tr = Trace(ops={DEV: [["%while.1", 0, 100], ["%fusion.1", 10, 30],
                          ["%fusion.2", 50, 20], ["%copy", 120, 5]]},
               modules={}, spans=[["step", 0, 130]])
    got = tr.self_time_by_name()
    assert got == pytest.approx({"%while.1": 50e-9, "%fusion.1": 30e-9,
                                 "%fusion.2": 20e-9, "%copy": 5e-9})
    assert top(got, 2) == [["%while.1", pytest.approx(5e-8)],
                           ["%fusion.1", pytest.approx(3e-8)]]


def test_recorded_trace_round_trips(tmp_path):
    tr = Trace.load(str(DATA / "small_trace.json"))
    tr.save(str(tmp_path / "t.json"))
    assert Trace.load(str(tmp_path / "t.json")) == tr


def test_recorded_trace_reduction():
    """A TPU v5e trace of a small engine serving a few requests: the decode
    and prefill programs and the flash kernel are found under the names of
    ``trace_names.json``, once per host call, and the reduction is
    consistent."""
    tr = Trace.load(str(DATA / "small_trace.json"))
    calls = json.loads((DATA / "small_calls.json").read_text())
    names = bench.trace_names()
    assert tr.devices() == [DEV]
    assert 0 < tr.busy_s() < tr.window_s()
    assert 0 < tr.idle_share() < 1
    idle = sum(tr.idle_by_host().values())
    assert idle == pytest.approx(tr.window_s() - tr.busy_s(), rel=1e-6)
    decode = tr.matching(names["decode_program"], "modules")
    prefill = tr.matching(names["prefill_program"], "modules")
    assert len(decode) == len(calls["decode"])
    assert len(prefill) == len(calls["prefill"])
    assert tr.matching(names["flash_kernel"])
    obs = Observed(cfg=json.loads((DATA / "small_config.json").read_text()),
                   peaks=bench.load_peaks("TPU v5 lite"), names=names,
                   served=None, trace=tr, calls=_calls(calls),
                   trace_window=(0.0, 1.0), arch=llama)
    for metric in ("decode_step_ms", "decode_mfu", "prefill_mfu",
                   "flash_roofline", "device_idle_share"):
        v = bench.metric_reader(metric)(obs)
        assert v is not None and 0 < v, metric
        if metric != "decode_step_ms":
            assert v <= 100, metric


def _calls(d):
    from perfbench.serve import Calls
    return Calls(prefill=[tuple(c) for c in d["prefill"]],
                 decode=[tuple(c) for c in d["decode"]])
