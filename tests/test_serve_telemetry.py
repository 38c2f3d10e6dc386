"""The serving engine's own spans, decode step count and prefill padding: a small ``ContinuousEngine``
served inside a ``jax.profiler`` session on the CPU, its trace read back
with ``jax.profiler.ProfileData``."""

import gc
import glob
import os
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.models.registry import get_model
from repro.serve.engine import ContinuousEngine
from repro.serve.scheduler import Request
from repro.serve.telemetry import pad_share

BUCKETS = (16, 32, 64)
# (prompt length, new tokens, arrival step, bucket it pads to)
REQUESTS = [(5, 3, 0, 16), (16, 1, 0, 16), (20, 4, 1, 32), (31, 2, 2, 32),
            (40, 5, 3, 64)]
IN_ADMIT = ("engine.prefill", "engine.kv_write", "engine.first_token")


def _requests(vocab):
    rng = np.random.default_rng(0)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                    max_new_tokens=m, arrival_step=a)
            for i, (n, m, a, _) in enumerate(REQUESTS)]


def _engine(cfg, params):
    return ContinuousEngine(cfg, params, max_batch=2, page_size=16,
                            max_len=128, prompt_buckets=BUCKETS)


def _engine_events(trace_dir):
    """Host events named ``engine.*`` as ``(name, start, end, args)``."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("engine."))
    return sorted(out, key=lambda e: e[1])


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """One workload served twice over the same weights: inside a profiler
    session (with a full collection at its end) and without one."""
    cfg = reduced(get_config("llama110m"))
    params = get_model(cfg).init(jax.random.key(0))
    plain = _requests(cfg.vocab)
    _engine(cfg, params).run(plain)
    traced, eng = _requests(cfg.vocab), _engine(cfg, params)
    trace_dir = str(tmp_path_factory.mktemp("engine-trace"))
    with jax.profiler.trace(trace_dir):
        stats = eng.run(traced)
        gc.collect()
    return {"plain": plain, "traced": traced, "eng": eng, "stats": stats,
            "events": _engine_events(trace_dir)}


def _named(events, name):
    return [e for e in events if e[0] == name]


def test_spans_nest_inside_steps(served):
    events = served["events"]
    steps = _named(events, "engine.step")
    assert len(steps) == served["eng"].step_count
    assert [e[3]["step_num"] for e in steps] == list(range(len(steps)))
    others = [e for e in events if e[0] not in ("engine.step", "engine.gc")]
    assert {e[0] for e in others} >= {"engine.retire", "engine.admit",
                                      "engine.upload", "engine.decode",
                                      "engine.fetch", "engine.bookkeep",
                                      *IN_ADMIT}
    for name, s, e, _ in others:
        assert any(a <= s and e <= b for _, a, b, _ in steps), name


def test_gc_span_marks_a_collection(served):
    gcs = _named(served["events"], "engine.gc")
    assert gcs and all(e[3]["generation"] >= 1 for e in gcs)


@pytest.mark.parametrize("name", ("engine.admit",) + IN_ADMIT)
def test_one_span_per_request_carries_its_rid(served, name):
    spans = _named(served["events"], name)
    assert sorted(e[3]["rid"] for e in spans) == list(range(len(REQUESTS)))
    if name != "engine.admit":
        admits = {e[3]["rid"]: e for e in _named(served["events"],
                                                 "engine.admit")}
        for _, s, e, args in spans:
            assert admits[args["rid"]][1] <= s and e <= admits[args["rid"]][2]


@pytest.mark.parametrize("name", ("engine.decode", "engine.fetch",
                                  "engine.bookkeep"))
def test_one_span_per_decode_step(served, name):
    n = served["eng"].decode_steps
    assert n == served["stats"].decode_steps > 0
    assert len(_named(served["events"], name)) == n


def test_run_counts_its_own_decode_steps(served):
    """``run`` reports the decode steps of its workload alone, also on an
    engine that served one before."""
    cfg = reduced(get_config("llama110m"))
    eng = _engine(cfg, served["eng"].params)
    first = eng.run(_requests(cfg.vocab)).decode_steps
    again = eng.run(_requests(cfg.vocab)).decode_steps
    assert first == again == served["stats"].decode_steps
    assert eng.decode_steps == first + again


def test_each_request_records_its_bucket(served):
    assert [r.bucket for r in served["traced"]] == [r[3] for r in REQUESTS]


def _req(prompt_len, bucket):
    return SimpleNamespace(prompt_len=prompt_len, bucket=bucket)


@pytest.mark.parametrize("reqs, want", [
    ("served", 1 - sum(r[0] for r in REQUESTS) / sum(r[3] for r in REQUESTS)),
    ([_req(30, 40)], 0.25),
    ([_req(30, 40), _req(30, None)], 0.25),
    ([], 0.0),
], ids=["hand_count", "one_request", "one_not_admitted", "none"])
def test_pad_share(served, reqs, want):
    reqs = served["traced"] if reqs == "served" else reqs
    assert pad_share(reqs) == pytest.approx(want)


def test_tokens_same_with_and_without_a_session(served):
    got = [r.out_tokens for r in served["traced"]]
    assert got == [r.out_tokens for r in served["plain"]]
    assert [len(t) for t in got] == [r[1] for r in REQUESTS]
