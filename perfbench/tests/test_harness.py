"""The harness on the CPU: what it refuses, what it finds by name, and a
run whose timed path is broken underneath reading ``correct: false``."""

import json
import os
import re
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

from perfbench import bench, traffic
from perfbench import run as harness
from perfbench.tests import tiny

REPO = tiny.REPO


def _run_cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "perfbench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def _no_result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode != 0 and not (lines and lines[-1].startswith("{"))


def test_cpu_run_exits_nonzero_without_result():
    proc = _run_cli(REPO, "--workload", "yi9b.chat", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert _no_result(proc), proc.stdout
    assert "no accelerator" in proc.stderr


def test_benchmark_alone_exits_nonzero_without_result(tmp_path):
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path, "--workload", "yi9b.chat", "--seed", "1",
                    "--seconds", "1", "--trace", "0")
    assert _no_result(proc), proc.stdout


def _args(seed=2 ** 31 + 11, trace=0):
    return harness.parse(["--workload", "tiny.test", "--seed", str(seed),
                          "--seconds", "2", "--trace", str(trace)])


def test_added_cell_is_found_and_correct(tmp_path):
    root = tiny.make_root(tmp_path)
    res = harness.run(_args(), require_device=False, root=root)
    assert res["correct"] is True
    assert res["attempted"] == round(tiny.TRAFFIC["rate_per_s"] * 2)
    assert res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in json.loads(
        (REPO / "BENCHMARK.json").read_text())["end_to_end"]}
    assert list(res)[-1] == "checks"
    assert res["checks"]["gap_max"]["value"] <= tiny.LIMITS["limits"][
        "gap_max"]["limit"]


def _alter_decode(eng):
    orig = eng._decode

    def wrong(*args):
        nxt, kp, vp, sl = orig(*args)
        return (nxt + 1) % eng.cfg.vocab, kp, vp, sl

    eng._decode = wrong


def _alter_prefill(eng):
    orig = eng._prefill

    def wrong(p, batch, length):
        logits, kv = orig(p, batch, length)
        return jnp.roll(logits, 1, axis=-1), kv

    eng._prefill = wrong


@pytest.mark.parametrize("fault", [_alter_decode, _alter_prefill],
                         ids=["token_altered_in_decode",
                              "token_altered_in_prefill"])
def test_altered_token_reads_incorrect(tmp_path, fault):
    root = tiny.make_root(tmp_path)
    res = harness.run(_args(), require_device=False, root=root,
                      engine_hook=fault)
    assert res["correct"] is False
    assert res["checks"]["gap_max"]["value"] > 10 * tiny.LIMITS["limits"][
        "gap_max"]["limit"]


def test_every_seed_offers_the_same_work():
    """Lengths and arrival times are the same for every seed, drawn as
    stratified quantiles of the mix; only the token ids differ."""
    mix = json.loads((REPO / "perfbench/traffic/chat.json").read_text())
    a = traffic.schedule(mix, 30, 1, 1000)
    b = traffic.schedule(mix, 30, 2 ** 31 + 99, 1000)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens, x.section)
            for x in a] == [(x.due_s, len(x.prompt), x.max_new_tokens,
                             x.section) for x in b]
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    t0 = 0.0
    for sec, (n, span) in traffic.section_sizes(mix, 30).items():
        part = [x for x in a if x.section == sec]
        assert len(part) == n
        assert sorted(len(x.prompt) for x in part) == sorted(
            traffic.lengths(mix["prompt_len"], n))
        assert part[-1].due_s == pytest.approx(t0 + span)
        t0 += span
    assert sum(x.section == "window" for x in a) == round(
        mix["rate_per_s"] * 30)


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_names_its_files():
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    assert b["command"] == ["python3", "perfbench/run.py"]
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    names += [w["name"] for w in b["workloads"]]
    names += [c["name"] for c in b["configs"]]
    assert len(names) == len(set(names)) and all(map(_NAME.match, names))
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        cell = bench.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert (REPO / "perfbench/checks" / f"{w['name']}.json").exists()
        mix = cell.traffic
        longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
        assert longest <= cell.config["serve"]["max_len"]
    for m in b["per_layer"]:
        assert callable(bench.metric_reader(m["name"]))
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
