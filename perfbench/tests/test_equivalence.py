"""What moving the Llama layout into ``perfbench/arch/llama.py`` may not
change: each value below was computed with the harness as it stood before
the move, when the layout was written into ``weights.py``, ``reference.py``
and ``flops.py``.

Weights and logits are compared bit for bit, by the SHA-256 of their bytes
(first 16 hex digits), on the CPU; counts and readings exactly.
"""

import hashlib
import json
import pathlib
from collections import Counter

import numpy as np
import pytest

from perfbench import bench, flops, weights
from perfbench.arch import llama
from perfbench.metrics._common import Observed
from perfbench.reference import Reference, _flat
from perfbench.serve import Calls
from perfbench.tests import tiny
from perfbench.tracereduce import Trace

HERE = pathlib.Path(__file__).resolve().parent
SEED = 2 ** 31 + 7
CONFIGS = {
    "bf16": tiny.CONFIG,
    "f32_tied": dict(tiny.CONFIG, torch_dtype="float32",
                     tie_word_embeddings=True),
}
WEIGHTS = {
    "bf16": {
        "blocks/attn/wk": "12abcfce5aadb67a",
        "blocks/attn/wo": "68383d4ab188bb87",
        "blocks/attn/wq": "3748210b1bb0ec52",
        "blocks/attn/wv": "6ba14083b4cfd9f6",
        "blocks/attn_norm/scale": "dddef9e6397028f4",
        "blocks/mlp/wi_gate": "9bbd348a104dd493",
        "blocks/mlp/wi_up": "dce541b6c5e859bb",
        "blocks/mlp/wo": "c5b0470dcb3a7121",
        "blocks/mlp_norm/scale": "533984eb38a4cedb",
        "embed/table": "f4af71cd3b1b98f5",
        "final_norm/scale": "09fb9d22bc36e3d4",
        "unembed/w": "30c318ec6d2ab1d1",
    },
    "f32_tied": {
        "blocks/attn/wk": "6cf95e860a939360",
        "blocks/attn/wo": "5f78765c8961ada9",
        "blocks/attn/wq": "6b632f1adaeda707",
        "blocks/attn/wv": "8ae005ad9b567b8d",
        "blocks/attn_norm/scale": "b8de26012782f423",
        "blocks/mlp/wi_gate": "12f5356486aa5f42",
        "blocks/mlp/wi_up": "5345b3b5120f4c66",
        "blocks/mlp/wo": "5d4a2fca9593a90c",
        "blocks/mlp_norm/scale": "39b0add799a93a18",
        "embed/table": "0cfaa8a77800da41",
        "final_norm/scale": "d8d3d7c4b4204284",
    },
}
#: Logits at rows 0, 17 and 39 of 40 tokens drawn from ``default_rng(0)``.
LOGITS = {
    ("bf16", None): "3411d3e7fa42a232",
    ("bf16", "int8"): "cede43aede8f8cff",
    ("bf16", "fp8"): "dc1f14f87764b7e3",
    ("f32_tied", None): "632e4070a7d4547b",
    ("f32_tied", "int8"): "effbee8223c7aff0",
    ("f32_tied", "fp8"): "845f86931b60c280",
}
PREFILL_N = (1, 20, 1000, 3968)
DECODE_CONTEXTS = ([100, 4096], [1], list(range(1, 17)))
BUCKETS = (32, 64, 1024, 2048, 4096)
COUNTS = {
    "tiny": {
        "prefill": [721920.0, 12142592.0, 1102467072.0, 10404036608.0],
        "decode": [5738496.0, 721920.0, 11673600.0],
        "flash": [(270336.0, 24576.0), (1064960.0, 49152.0),
                  (268697600.0, 786432.0), (1074266112.0, 1572864.0),
                  (4296015872.0, 3145728.0)],
        "flash_least": [6.001465201465201e-08, 1.2002930402930402e-07,
                        2.7278944162436548e-06, 1.0906254944162437e-05,
                        4.361437433502538e-05],
    },
    "yi9b": {
        "prefill": [6061031424.0, 111308963840.0, 5668208640000.0,
                    24033533886464.0],
        "decode": [13221494784.0, 6061031424.0, 97007960064.0],
        "flash": [(8650752.0, 589824.0), (34078720.0, 1179648.0),
                  (8598323200.0, 18874368.0), (34376515584.0, 37748736.0),
                  (137472507904.0, 75497472.0)],
        "flash_least": [1.1522813186813187e-05, 2.3045626373626375e-05,
                        0.0006983409705583756, 0.002792001265705584,
                        0.011165279829766497],
    },
}
#: Per-layer readings of the recorded v5e trace (``data/``).
READINGS = {
    "decode_step_ms": 0.020904392857142857,
    "decode_mfu": 0.090341476693102,
    "prefill_mfu": 6.245573683441866,
    "flash_roofline": 19.550338631697045,
    "device_idle_share": 99.70386832682149,
    "step_host_ms": 4.357008428571411,
}


def digest(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_weights_are_bit_identical(name):
    got = _flat(weights.make(CONFIGS[name], SEED, llama))
    assert {k: digest(v) for k, v in got.items()} == WEIGHTS[name]


@pytest.mark.parametrize("name,control", sorted(LOGITS, key=str))
def test_reference_logits_are_bit_identical(name, control):
    cfg = CONFIGS[name]
    ref = Reference(cfg, weights.make(cfg, SEED, llama), llama)
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40,
                                               dtype=np.int32)
    got = ref.logits(tokens, np.array([0, 17, 39]), control=control)
    assert digest(np.asarray(got, np.float32)) == LOGITS[(name, control)]


def _config(name):
    if name == "tiny":
        return tiny.CONFIG
    return json.loads((HERE.parent / "configs" / "yi9b.json").read_text())


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_work_counts_are_equal(name):
    cfg, want = _config(name), COUNTS[name]
    peaks = bench.load_peaks("TPU v5 lite")
    assert [llama.prefill_flops(cfg, n) for n in PREFILL_N] == want["prefill"]
    assert [llama.decode_flops(cfg, c)
            for c in DECODE_CONTEXTS] == want["decode"]
    calls = [llama.flash_calls(cfg, s) for s in BUCKETS]
    assert [c[0] for c in calls] == want["flash"]
    assert all(len(c) == cfg["num_hidden_layers"] and len(set(c)) == 1
               for c in calls)
    # as flash_roofline sums them: alike calls counted together
    assert [sum(k * flops.least_time(*call, peaks)
                for call, k in Counter(c).items())
            for c in calls] == want["flash_least"]


@pytest.mark.parametrize("metric", sorted(READINGS))
def test_readings_of_the_recorded_trace_are_equal(metric):
    calls = json.loads((HERE / "data" / "small_calls.json").read_text())
    obs = Observed(
        cfg=json.loads((HERE / "data" / "small_config.json").read_text()),
        peaks=bench.load_peaks("TPU v5 lite"), names=bench.trace_names(),
        served=None, trace=Trace.load(str(HERE / "data" / "small_trace.json")),
        calls=Calls(prefill=[tuple(c) for c in calls["prefill"]],
                    decode=[tuple(c) for c in calls["decode"]]),
        trace_window=(0.0, 1.0), arch=llama)
    assert bench.metric_reader(metric)(obs) == READINGS[metric]
