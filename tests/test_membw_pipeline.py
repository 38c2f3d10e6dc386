"""Burst-DMA pipeline tests: interpret-mode numerical parity of the
pipelined kernels vs the unpipelined baselines (fp32/bf16/int8), the
synthesis buffer-depth decision under a constrained VMEM budget, and the
never-pipelined-on-a-predicted-loss guarantee."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.kernel_synth import (
    PIPELINE_GAIN_MIN,
    choose_flash_blocks,
    choose_matmul_blocks,
    choose_ssd_blocks,
)
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.pipeline import (
    flash_attention_pipelined,
    int8_matmul_pipelined,
    ssd_scan_pipelined,
    use_pipeline,
)
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(7)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-4)


# ---------------------------------------------------------------------------
# Numerical parity: pipelined vs unpipelined kernel bodies (interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("depth", [2, 3])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_pipelined_parity(dtype, depth, causal):
    """Pipelined == BlockSpec kernel on the same path: the dense mask, or
    the causal index mask with the sweep stopped at the diagonal."""
    B, S, H, K, T, hd = 2, 128, 4, 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    mask = jnp.broadcast_to(jnp.tril(jnp.ones((S, T), bool), k=T - S)[None],
                            (1, S, T))
    got = flash_attention_pipelined(q, k, v, mask, sm_scale=hd ** -0.5,
                                    block_q=64, block_k=64, causal=causal,
                                    depth=depth, interpret=True)
    want = flash_attention(q, k, v, mask, sm_scale=hd ** -0.5,
                           block_q=64, block_k=64, causal=causal,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("depth", [2, 4])
def test_int8_pipelined_parity(dtype, depth):
    """int8 weight tiles through the burst pipeline == BlockSpec staging."""
    M, N, K = 64, 128, 256
    x = jnp.asarray(RNG.normal(size=(M, K)), dtype)
    wq = jnp.asarray(RNG.integers(-127, 127, size=(N, K)), jnp.int8)
    sc = jnp.asarray(RNG.uniform(0.01, 0.02, size=(N,)), jnp.float32)
    got = int8_matmul_pipelined(x, wq, sc, block_m=32, block_n=64,
                                block_k=64, depth=depth, interpret=True)
    want = int8_matmul(x, wq, sc, block_m=32, block_n=64, block_k=64,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("depth", [2, 3])
def test_ssd_pipelined_parity(dtype, depth):
    BT, H, S, P, N = 2, 3, 128, 16, 8
    x = jnp.asarray(RNG.normal(size=(BT, H, S, P)), dtype)
    dt = jnp.asarray(RNG.uniform(0.01, 0.1, size=(BT, H, S)), dtype)
    A = jnp.asarray(-RNG.uniform(0.5, 1.5, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(BT, S, N)), dtype)
    C = jnp.asarray(RNG.normal(size=(BT, S, N)), dtype)
    got = ssd_scan_pipelined(x, dt, A, B, C, chunk=32, depth=depth,
                             interpret=True)
    want = ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_ops_wrapper_pipeline_override_parity():
    """ops.* route both paths to the same numbers under explicit override."""
    B, S, H, K, T, hd = 1, 64, 2, 2, 512, 64
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, K, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, K, hd)), jnp.float32)
    mask = jnp.ones((1, S, T), bool)
    a = ops.flash_attention_gqa(q, k, v, mask, sm_scale=hd ** -0.5,
                                interpret=True, pipelined=True)
    b = ops.flash_attention_gqa(q, k, v, mask, sm_scale=hd ** -0.5,
                                interpret=True, pipelined=False)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    want = ref.flash_attention_ref(q, k, v, mask, sm_scale=hd ** -0.5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(want), atol=2e-5,
                               rtol=2e-4)


# ---------------------------------------------------------------------------
# Synthesis decision: buffer depth under a VMEM budget, loss veto
# ---------------------------------------------------------------------------

def test_buffer_depth_shrinks_under_vmem_pressure():
    """The synthesized depth must respect the VMEM budget: a tight budget
    prices deep staging out (collapsing to the BlockSpec baseline, since a
    depth-2 explicit pipeline never beats Mosaic's implicit double
    buffering), an impossible one raises."""
    full = choose_flash_blocks(64, 4096, 64, dtype_bytes=2)
    assert full.buffering > 2 and full.pipelined
    # ~300 KiB: deep staging shaved but still worth pipelining
    mid = choose_flash_blocks(64, 4096, 64, dtype_bytes=2,
                              vmem_budget=300 * 1024)
    assert mid.vmem_bytes <= 300 * 1024
    assert mid.buffering < full.buffering and mid.pipelined
    # ~250 KiB: only the (implicitly double-buffered) baseline fits
    tight = choose_flash_blocks(64, 4096, 64, dtype_bytes=2,
                                vmem_budget=250 * 1024)
    assert tight.vmem_bytes <= 250 * 1024
    assert tight.buffering == 1 and not tight.pipelined
    with pytest.raises(AssertionError):
        choose_flash_blocks(64, 4096, 64, dtype_bytes=2,
                            vmem_budget=32 * 1024)


def test_matmul_depth_under_vmem_pressure():
    """Memory-bound skinny GEMM: the budget constrains the working set, and
    the synthesizer pays for it in predicted cycles (smaller tiles / fewer
    buffers), down to infeasibility."""
    full = choose_matmul_blocks(8, 4096, 8192, dtype_bytes=1)
    assert full.buffering > 2 and full.pipelined
    tight_budget = 512 * 1024
    tight = choose_matmul_blocks(8, 4096, 8192, dtype_bytes=1,
                                 vmem_budget=tight_budget)
    assert tight.vmem_bytes <= tight_budget < full.vmem_bytes
    assert tight.est_total_cycles >= full.est_total_cycles
    with pytest.raises(AssertionError):
        choose_matmul_blocks(8, 4096, 8192, dtype_bytes=1,
                             vmem_budget=8 * 1024)


def test_pipeline_never_selected_on_predicted_loss():
    """A single streamed tile can't overlap; a compute-bound GEMM gains
    nothing over BlockSpec's implicit double buffering — neither may select
    the burst pipeline, and every pipelined schedule must carry a predicted
    gain above the threshold."""
    degenerate = choose_flash_blocks(64, 64, 64)
    assert not degenerate.pipelined
    assert degenerate.buffering == 1
    assert degenerate.decisions["pipeline"] == "off"
    fat_gemm = choose_matmul_blocks(4096, 4096, 4096)
    assert not fat_gemm.pipelined  # compute-bound: implicit overlap suffices
    for sched in (choose_flash_blocks(64, 4096, 64),
                  choose_matmul_blocks(8, 4096, 8192, dtype_bytes=1),
                  choose_ssd_blocks(4096, 80, 64, 128)):
        assert sched.pipelined  # memory-bound: deep staging predicted to win
        assert sched.pipeline_gain >= PIPELINE_GAIN_MIN
        assert sched.est_total_cycles <= sched.est_serial_cycles


def test_ops_wrappers_honor_synthesis_decision():
    """With one streamed tile the wrapper must not pipeline even when the
    caller forces it (nothing to overlap)."""
    sched = choose_flash_blocks(64, 64, 64)
    assert use_pipeline(sched, None, 1) is False
    assert use_pipeline(sched, True, 1) is False
    assert use_pipeline(sched, True, 4) is True
    assert use_pipeline(sched, False, 4) is False
    rich = choose_flash_blocks(1024, 4096, 128)
    assert use_pipeline(rich, None, 32) == rich.pipelined


def test_dispatch_records_pipeline_decision():
    """The compile-cache entry exposes the burst-DMA decision (surfaced by
    bench_compile_stats into BENCH_compile.json)."""
    from repro.compile import Dispatcher, OpKey
    disp = Dispatcher()
    rec = disp.lower(OpKey("attention", (1, 128, 4, 4, 2048, 64),
                           "float32", "pallas_interpret"))
    assert rec.impl == "isax"
    for field in ("pipelined", "buffering", "pipeline_gain",
                  "est_serial_cycles"):
        assert field in rec.schedule
    st = disp.stats()
    assert st["pipelined_keys"] == int(bool(rec.schedule["pipelined"]))
