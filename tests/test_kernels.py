"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tiling import causal_tiles
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.kernels.rmsnorm import rmsnorm
from repro.kernels.ssd_scan import ssd_scan

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else \
        dict(atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("B,S,H,K,T,hd", [
    (1, 128, 1, 1, 128, 64),
    (2, 256, 4, 2, 256, 64),     # GQA 2:1
    (1, 256, 8, 1, 512, 128),    # MQA, cross lengths
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("mask_kind", ["causal", "full"])
def test_flash_attention_sweep(B, S, H, K, T, hd, dtype, mask_kind):
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    if mask_kind == "causal":
        mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)[None]
    else:
        mask = jnp.ones((1, S, T), bool)
    out = flash_attention(q, k, v, jnp.broadcast_to(mask, (1, S, T)),
                          sm_scale=hd ** -0.5, block_q=128, block_k=128,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, mask, sm_scale=hd ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,K,T,hd,bq,bk", [
    (1, 256, 4, 4, 256, 64, 64, 64),      # S = T, square tiles
    (1, 256, 4, 2, 256, 64, 128, 32),     # tall tiles: the diagonal spans 4
    (1, 256, 2, 2, 256, 128, 32, 128),    # wide tiles
    (1, 128, 2, 2, 256, 128, 32, 64),     # T > S: offset T − S = 128
    (2, 128, 4, 2, 384, 64, 64, 64),      # T > S: offset T − S = 256
    (1, 256, 8, 1, 256, 128, 64, 64),     # GQA 8:1
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, S, H, K, T, hd, bq, bk, dtype):
    """The causal path (index mask, tiles above the diagonal skipped) equals
    the oracle under the causal mask aligned to the last key; the dense
    mask operand is not read, so an all-True one changes nothing."""
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, K, hd)), dtype)
    mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)[None]
    below, crossing, total = causal_tiles(S, T, bq, bk)
    assert below + crossing < total            # some tiles are skipped
    out = flash_attention(q, k, v, jnp.ones((1, S, T), bool),
                          sm_scale=hd ** -0.5, block_q=bq, block_k=bk,
                          causal=True, interpret=True)
    want = ref.flash_attention_ref(q, k, v, mask, sm_scale=hd ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("S,T,bq,bk", [
    (256, 256, 64, 64), (256, 256, 128, 32), (128, 384, 64, 128),
    (1024, 1024, 1024, 128), (4096, 4096, 512, 1024),
])
def test_causal_tiles_match_the_diagonal(S, T, bq, bk):
    """``causal_tiles`` counts, tile by tile, those wholly at or below the
    diagonal ``col <= row + T − S`` and those it crosses."""
    mask = np.tril(np.ones((S, T), bool), k=T - S)
    tiles = mask.reshape(S // bq, bq, T // bk, bk).transpose(0, 2, 1, 3)
    below = int(tiles.all(axis=(2, 3)).sum())
    ran = int(tiles.any(axis=(2, 3)).sum())
    assert causal_tiles(S, T, bq, bk) == (below, ran - below,
                                          (S // bq) * (T // bk))


@pytest.mark.parametrize("buckets", [(1024,), (1024, 2048, 4096, 4096),
                                     (16, 32)])
def test_flash_tile_share_counts_tiles_at_or_below_the_diagonal(buckets):
    """``flash_tile_share`` pools, over the admitted requests, the tiles of
    the kernel's grid at each request's bucket that hold any visible key."""
    from repro.configs.base import ModelConfig
    from repro.serve.telemetry import flash_tile_share
    cfg = ModelConfig(name="t", family="dense", vocab=64, d_model=4096,
                      n_layers=1, n_heads=32, n_kv_heads=4, d_ff=128,
                      head_dim=128)
    reqs = [types.SimpleNamespace(bucket=b) for b in buckets]
    reqs.append(types.SimpleNamespace(bucket=None))   # not yet admitted
    ran = total = 0
    for b in buckets:
        _, (bq, bk) = ops.flash_tiles(b, b, 32, 4, 128, 2, causal=True)
        grid = np.tril(np.ones((b, b), bool)).reshape(
            b // bq, bq, b // bk, bk).any(axis=(1, 3))
        ran, total = ran + int(grid.sum()), total + grid.size
    assert flash_tile_share(reqs, cfg) == pytest.approx(ran / total)
    assert flash_tile_share(reqs[-1:], cfg) == 0.0


def test_flash_attention_fully_masked_rows():
    """Rows with no valid keys must produce zeros, not NaNs."""
    B, S, H, hd = 1, 128, 1, 64
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, S, 1, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, S, 1, hd)), jnp.float32)
    mask = jnp.zeros((1, S, S), bool).at[:, :, :8].set(True).at[:, :8, :].set(False)
    out = flash_attention(q, k, v, mask, sm_scale=1.0, interpret=True)
    assert not bool(jnp.any(jnp.isnan(out)))
    np.testing.assert_allclose(np.asarray(out[0, :8]), 0.0, atol=1e-6)


@pytest.mark.parametrize("M,N,K", [(128, 128, 128), (256, 384, 512),
                                   (128, 256, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_matmul_sweep(M, N, K, dtype):
    x = jnp.asarray(RNG.normal(size=(M, K)), dtype)
    wq = jnp.asarray(RNG.integers(-127, 127, size=(N, K)), jnp.int8)
    scale = jnp.asarray(RNG.uniform(0.001, 0.02, size=(N,)), jnp.float32)
    out = int8_matmul(x, wq, scale, block_m=128, block_n=128, block_k=128,
                      interpret=True)
    want = ref.int8_matmul_ref(x, wq, scale)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.5 if dtype == jnp.bfloat16 else 1e-2,
                               rtol=2e-2)


@pytest.mark.parametrize("BT,H,S,P,N,chunk", [
    (1, 1, 128, 8, 16, 64),
    (2, 3, 256, 16, 32, 128),
    (1, 2, 512, 64, 128, 256),   # production-ish state size
])
def test_ssd_scan_sweep(BT, H, S, P, N, chunk):
    x = jnp.asarray(RNG.normal(size=(BT, H, S, P)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.1, 0.9, size=(BT, H, S)), jnp.float32)
    A = jnp.asarray(-RNG.uniform(0.5, 1.5, size=(H,)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(BT, S, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(BT, S, N)), jnp.float32)
    out = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    want = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=1e-3)


@pytest.mark.parametrize("R,d", [(256, 128), (512, 1024), (128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_sweep(R, d, dtype):
    x = jnp.asarray(RNG.normal(size=(R, d)), dtype)
    g = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    out = rmsnorm(x, g, interpret=True)
    want = ref.rmsnorm_ref(x, g)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,H,K,T,hd", [
    (1, 128, 4, 2, 256, 64),
    (2, 128, 8, 1, 128, 128),
])
def test_flash_attention_int8kv(B, S, H, K, T, hd):
    """int8-KV flash kernel (the §6.5 quantized-attention ISAX): exact vs the
    dequantized oracle; bounded quantization error vs the fp oracle."""
    from repro.kernels.flash_attention import flash_attention_int8kv
    q = jnp.asarray(RNG.normal(size=(B, S, H, hd)), jnp.float32)
    kf = RNG.normal(size=(B, T, K, hd)).astype(np.float32)
    vf = RNG.normal(size=(B, T, K, hd)).astype(np.float32)
    ks = np.abs(kf).max(axis=(0, 1, 3)) / 127.0
    vs = np.abs(vf).max(axis=(0, 1, 3)) / 127.0
    k8 = jnp.asarray(np.clip(np.round(kf / ks[None, None, :, None]),
                             -127, 127), jnp.int8)
    v8 = jnp.asarray(np.clip(np.round(vf / vs[None, None, :, None]),
                             -127, 127), jnp.int8)
    mask = jnp.tril(jnp.ones((S, T), bool), k=T - S)[None]
    out = flash_attention_int8kv(q, k8, v8, jnp.asarray(ks), jnp.asarray(vs),
                                 mask, sm_scale=hd ** -0.5, interpret=True)
    kd = jnp.asarray(k8, jnp.float32) * ks[None, None, :, None]
    vd = jnp.asarray(v8, jnp.float32) * vs[None, None, :, None]
    want = ref.flash_attention_ref(q, kd, vd, mask, sm_scale=hd ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5,
                               rtol=1e-4)
    want_fp = ref.flash_attention_ref(q, jnp.asarray(kf), jnp.asarray(vf),
                                      mask, sm_scale=hd ** -0.5)
    assert float(jnp.abs(out - want_fp).max()) < 0.1  # int8 quant noise


def test_ops_wrappers_choose_synthesized_blocks():
    """ops.* derive tile sizes from the interface-aware synthesis flow and
    fall back to the oracle for untileable shapes."""
    q = jnp.asarray(RNG.normal(size=(1, 96, 2, 64)), jnp.float32)  # 96: odd
    k = jnp.asarray(RNG.normal(size=(1, 96, 2, 64)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(1, 96, 2, 64)), jnp.float32)
    mask = jnp.ones((1, 96, 96), bool)
    out = ops.flash_attention_gqa(q, k, v, mask, sm_scale=0.125,
                                  interpret=True)
    want = ref.flash_attention_ref(q, k, v, mask, sm_scale=0.125)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_layers_pallas_interpret_path_matches_xla(causal):
    """models.layers attention with a pallas_interpret LoweringConfig == the
    xla-reference lowering (kernel choice through the compile dispatcher),
    on the dense-mask and on the causal path."""
    from repro.compile import Dispatcher, LoweringConfig
    from repro.models import layers as L
    from repro.configs.registry import get_config
    from repro.configs.base import reduced
    cfg = reduced(get_config("granite-3-8b"))
    key = jax.random.key(0)
    p = L.init_attention(cfg, key)
    x = jnp.asarray(RNG.normal(size=(2, 128, cfg.d_model)), jnp.float32)
    mask = L.make_mask("causal", 128)
    pos = jnp.broadcast_to(jnp.arange(128)[None], (2, 128))
    disp = Dispatcher()
    want, _ = L.attention(p, x, cfg, mask, pos,
                          lowering=LoweringConfig("xla", disp),
                          causal=causal)
    got, _ = L.attention(p, x, cfg, mask, pos,
                         lowering=LoweringConfig("pallas_interpret", disp),
                         causal=causal)
    op = "attention_causal" if causal else "attention"
    assert [k.op for k, r in disp.records.items() if r.impl == "isax"] == [op]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)
