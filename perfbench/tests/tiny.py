"""A tiny cell for tests on the CPU: a copy of the benchmark with one more
configuration, traffic mix and cell, added as files and entries only."""

from __future__ import annotations

import json
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

CONFIG = {
    "name": "tiny", "source": "test", "model_type": "llama",
    "hidden_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 256,
    "vocab_size": 512, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": False, "attention_bias": False,
    "torch_dtype": "bfloat16",
    "serve": {"family": "dense", "max_batch": 4, "page_size": 16,
              "max_len": 128, "prompt_buckets": [32, 64]},
}
TRAFFIC = {
    "rate_per_s": 4.0,
    "prompt_len": {"dist": "uniform", "min": 8, "max": 60},
    "output_len": {"dist": "uniform", "min": 4, "max": 12},
    "ramp_s": 0.5, "grace_s": 20, "check_tokens": 30, "trace_s": 1,
}
LIMITS = {"limits": {"gap_max": {"limit": 0.1}}}


def make_root(tmp: pathlib.Path, config=CONFIG, traffic=TRAFFIC,
              limits=LIMITS) -> pathlib.Path:
    """A checkout under ``tmp`` holding BENCHMARK.json, perfbench/ and a
    link to the program, with the cell ``tiny.test`` added."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "perfbench", root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.test", "config": "tiny",
                               "traffic": "test", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "perfbench/configs/tiny.json").write_text(json.dumps(config))
    (root / "perfbench/traffic/test.json").write_text(json.dumps(traffic))
    (root / "perfbench/checks/tiny.test.json").write_text(json.dumps(limits))
    return root
