"""The plain reference against the serve path at a reduced width (CPU).

In float32 the engine's prefill logits, and the logits of a paged decode over
the pages that prefill wrote, agree with the reference to rounding.  A model
whose attention ignores the causal mask does not.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import weights
from perfbench.arch import llama
from perfbench.reference import Reference
from perfbench.tests import tiny
from repro.compile import LoweringConfig
from repro.serve.engine import ContinuousEngine

CFG = dict(tiny.CONFIG, torch_dtype="float32", tie_word_embeddings=True)
#: float32 on both sides: differences are summation order only.
TOL = 1e-4


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def engine(cfg, params):
    srv = cfg["serve"]
    return ContinuousEngine(
        llama.program_config(cfg), params, max_batch=srv["max_batch"],
        page_size=srv["page_size"], max_len=srv["max_len"],
        prompt_buckets=tuple(srv["prompt_buckets"]),
        lowering=LoweringConfig.from_registry("xla"))


def prefill_and_decode(eng, params, prompt, bucket, token):
    """Prefill logits at the prompt's last position, and the logits of one
    paged decode of ``token`` over the pages that prefill wrote."""
    n = len(prompt)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n] = prompt
    logits, kv = jax.jit(eng.model.prefill_at)(
        params, {"tokens": jnp.asarray(padded)}, jnp.int32(n))
    cache = eng.cache
    cache.bind_slot(0, n + 1)
    cache.write_prefill(0, kv, n)
    pt, sl, act = cache.device_views({0})
    toks = jnp.zeros((cache.max_batch,), jnp.int32).at[0].set(token)
    dec, _, _ = jax.jit(eng.model.decode_paged)(
        params, toks, cache.k_pages, cache.v_pages, pt, sl, act)
    cache.release_slot(0)
    return logits[0], dec[0]


@pytest.mark.parametrize("n,bucket", [(20, 32), (57, 64)])
def test_engine_agrees_with_reference(n, bucket):
    params = weights.make(CFG, 5, llama)
    ref = Reference(CFG, params, llama)
    prompt = np.random.default_rng(n).integers(0, CFG["vocab_size"], n,
                                               dtype=np.int32)
    token = 7
    pre, dec = prefill_and_decode(engine(CFG, params), params, prompt,
                                  bucket, token)
    want = np.asarray(ref.logits(np.append(prompt, token), [n - 1, n]))
    assert rel(pre, want[0]) < TOL
    assert rel(dec, want[1]) < TOL


def test_model_without_causal_mask_fails(monkeypatch):
    from repro.models import layers

    def no_mask(kind, S, T=None, n_prefix=0):
        return jnp.ones((1, S, T or S), bool)

    monkeypatch.setattr(layers, "make_mask", no_mask)
    params = weights.make(CFG, 5, llama)
    ref = Reference(CFG, params, llama)
    n, bucket = 20, 32
    prompt = np.random.default_rng(0).integers(0, CFG["vocab_size"], n,
                                               dtype=np.int32)
    pre, dec = prefill_and_decode(engine(CFG, params), params, prompt,
                                  bucket, 7)
    want = np.asarray(ref.logits(np.append(prompt, 7), [n - 1, n]))
    assert rel(pre, want[0]) > 100 * TOL
    assert rel(dec, want[1]) > 100 * TOL


def test_weights_repeat_for_a_seed_past_32_bits():
    seed = 2 ** 33 + 5
    a = weights.make(CFG, seed, llama)["final_norm"]["scale"]
    b = weights.make(CFG, seed, llama)["final_norm"]["scale"]
    c = weights.make(CFG, seed + 2 ** 32, llama)["final_norm"]["scale"]
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_config_maps_to_program():
    mc = llama.program_config(CFG)
    assert dataclasses.asdict(mc)["tie_embeddings"] is True
    assert mc.resolved_head_dim() == CFG["head_dim"]
