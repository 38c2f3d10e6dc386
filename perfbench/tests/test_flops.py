"""Operation and byte counts of the Llama layout (``perfbench/arch/llama.py``)
against numbers worked by hand."""

import json
import pathlib

import pytest

from perfbench import bench, flops
from perfbench.arch import llama

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def yi():
    return json.loads((CONFIGS / "yi9b.json").read_text())


def test_layer_params_yi():
    # q 4096x32x128, k and v 4096x4x128, o 32x128x4096, SwiGLU 3x4096x11008
    assert llama.layer_matmul_params(yi()) == (
        16_777_216 + 2 * 2_097_152 + 16_777_216 + 135_266_304)


def test_prefill_worked():
    cfg = yi()
    n = 1000
    layer = 173_015_040
    attn = 4 * 32 * 128 * (1000 * 1001 // 2)          # per layer
    head = 2 * 4096 * 64000                              # one position
    want = 2 * n * 16 * layer + 16 * attn + head
    assert llama.prefill_flops(cfg, n) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(5.6682e12, rel=1e-4)


def test_decode_step_worked():
    cfg = yi()
    ctx = [100, 4096]
    per_token = 2 * 16 * 173_015_040 + 2 * 4096 * 64000
    want = 2 * per_token + 16 * 4 * 32 * 128 * (100 + 4096)
    assert llama.decode_flops(cfg, ctx) == pytest.approx(want, rel=1e-12)


def test_flash_call_worked():
    f, b = llama.flash_call(yi(), 4096)
    assert f == pytest.approx(4 * 32 * 128 * 4096 * 4097 / 2, rel=1e-12)
    # q and o: 4096x32x128, k and v: 4096x4x128, two bytes each
    assert b == 2 * 4096 * 128 * (2 * 32 + 2 * 4)
    peaks = bench.load_peaks("TPU v5 lite")
    # compute-bound: 137.5 GFLOP over 197 TFLOP/s is 0.698 ms
    assert flops.least_time(f, b, peaks) == pytest.approx(f / 197e12)
    assert flops.least_time(f, b, peaks) == pytest.approx(6.978e-4, rel=1e-3)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(bench.NoDevice):
        bench.load_peaks("TPU v9 imaginary")
