"""Continuous-batching serve path: paged-KV numerics vs the static cache,
scheduler admission behavior, and page-allocator lifetime invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.models import layers as L
from repro.models import transformer
from repro.models.registry import get_model
from repro.serve.engine import ContinuousEngine, StaticBatchEngine
from repro.serve.kv_cache import (PageAllocationError, PageAllocator,
                                  PagedKVCache)
from repro.serve.scheduler import (Request, RequestQueue, Scheduler,
                                   make_poisson_workload, pick_bucket)


@pytest.fixture(scope="module")
def cfg():
    return reduced(get_config("llama110m"))


@pytest.fixture(scope="module")
def model_and_params(cfg):
    model = get_model(cfg)
    return model, model.init(jax.random.key(0))


# ---------------------------------------------------------------------------
# (a) paged-cache decode ≡ static-cache reference
# ---------------------------------------------------------------------------

class TestPagedNumerics:
    def test_paged_matches_static_decode(self, cfg, model_and_params):
        model, params = model_and_params
        B, PL, GEN, MAXLEN, PS = 4, 16, 6, 64, 16
        prompts = np.asarray(jax.random.randint(
            jax.random.key(1), (B, PL), 0, cfg.vocab), np.int32)

        logits, caches = model.prefill(
            params, {"tokens": jnp.asarray(prompts)}, MAXLEN)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        static_logits = [np.asarray(logits)]
        for i in range(GEN - 1):
            logits, caches = model.decode_step(params, tok, caches,
                                               jnp.int32(PL + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            static_logits.append(np.asarray(logits))

        cache = PagedKVCache(cfg, max_batch=B, page_size=PS,
                             n_pages=B * MAXLEN // PS, max_len=MAXLEN)
        toks = np.zeros((B,), np.int32)
        first = []
        for b in range(B):
            cache.bind_slot(b, PL + GEN)
            lg, kv = model.prefill_at(
                params, {"tokens": jnp.asarray(prompts[b:b + 1])},
                jnp.int32(PL))
            cache.write_prefill(b, kv, PL)
            first.append(np.asarray(lg[0]))
            toks[b] = int(jnp.argmax(lg[0]))
        paged_logits = [np.stack(first)]
        for _ in range(GEN - 1):
            pt, sl, act = cache.device_views(set(range(B)))
            lg, cache.k_pages, cache.v_pages = model.decode_paged(
                params, jnp.asarray(toks), cache.k_pages, cache.v_pages,
                pt, sl, act)
            cache.seq_lens[:] += 1
            toks = np.asarray(jnp.argmax(lg, -1), np.int32)
            paged_logits.append(np.asarray(lg))

        for step, (a, b) in enumerate(zip(static_logits, paged_logits)):
            np.testing.assert_allclose(
                a, b, atol=1e-5, rtol=0,
                err_msg=f"paged/static divergence at decode step {step}")

    def test_prefill_at_padded_prompt_exact(self, cfg, model_and_params):
        """Right-padding a prompt to a bucket must not change the logits at
        the true last position (causality)."""
        model, params = model_and_params
        PL, BUCKET = 11, 16
        prompt = np.asarray(jax.random.randint(
            jax.random.key(2), (1, PL), 0, cfg.vocab), np.int32)
        ref, _ = model.prefill(params, {"tokens": jnp.asarray(prompt)}, None)
        padded = np.zeros((1, BUCKET), np.int32)
        padded[0, :PL] = prompt
        got, _ = model.prefill_at(params, {"tokens": jnp.asarray(padded)},
                                  jnp.int32(PL))
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   atol=1e-5, rtol=0)


def _scatter_then_gather_decode(params, tokens, k_pages, v_pages,
                                page_table, seq_lens, active, cfg):
    """Paged decode written plainly, layer by layer: scatter the new token
    into the layer's pool, then gather the slot's pages and attend."""
    B = tokens.shape[0]
    N, page = k_pages.shape[1], k_pages.shape[2]
    P = page_table.shape[1]
    hd = cfg.resolved_head_dim()
    phys = jnp.where(active, page_table[jnp.arange(B), seq_lens // page], N)
    slot = seq_lens % page
    mask = jnp.arange(P * page)[None, None, :] <= seq_lens[:, None, None]
    h = L.embed(params["embed"], tokens[:, None], cfg)
    ks, vs = [], []
    for layer in range(cfg.n_layers):
        bp = jax.tree.map(lambda a: a[layer], params["blocks"])
        q, k, v = L._qkv(bp["attn"], L.rmsnorm(bp["attn_norm"], h,
                                               cfg.norm_eps),
                         cfg, seq_lens[:, None])
        kp = k_pages[layer].at[phys, slot].set(k[:, 0], mode="drop")
        vp = v_pages[layer].at[phys, slot].set(v[:, 0], mode="drop")
        kg = kp[page_table].reshape(B, P * page, *kp.shape[2:])
        vg = vp[page_table].reshape(B, P * page, *vp.shape[2:])
        out = L._sdpa_xla(q, kg, vg, mask, hd)
        h = h + jnp.einsum("bshk,hkd->bsd", out, bp["attn"]["wo"])
        h = h + L.mlp(bp["mlp"], L.rmsnorm(bp["mlp_norm"], h, cfg.norm_eps),
                      cfg)
        ks.append(kp)
        vs.append(vp)
    h = L.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = L.unembed(transformer._unembed_table(params, cfg), h, cfg)
    return logits[:, 0], jnp.stack(ks), jnp.stack(vs)


def test_decode_paged_writes_only_the_new_tokens(cfg, model_and_params):
    """One decode step stores each active slot's new K/V, in every layer,
    at its page and offset; every other pool entry keeps its bits, and
    inactive slots write nothing.  Active slots' logits match the plain
    scatter-then-gather step (inactive slots' logits are discarded)."""
    model, params = model_and_params
    B, PS, MAXLEN = 5, 16, 64
    P = MAXLEN // PS
    N = B * P + 3
    shape = (cfg.n_layers, N, PS, cfg.n_kv_heads, cfg.resolved_head_dim())
    kk, kv, kt = jax.random.split(jax.random.key(7), 3)
    k_pages = jax.random.normal(kk, shape, jnp.float32)
    v_pages = jax.random.normal(kv, shape, jnp.float32)
    page_table = np.random.default_rng(0).permutation(N)[:B * P]
    page_table = page_table.reshape(B, P).astype(np.int32)
    seq_lens = np.array([5, 16, 40, 63, 31], np.int32)
    active = np.array([True, False, True, True, False])
    tokens = jax.random.randint(kt, (B,), 0, cfg.vocab, jnp.int32)
    k_before, v_before = np.asarray(k_pages), np.asarray(v_pages)

    want_logits, want_k, want_v = _scatter_then_gather_decode(
        params, tokens, k_pages, v_pages, jnp.asarray(page_table),
        jnp.asarray(seq_lens), jnp.asarray(active), cfg)
    logits, k_after, v_after = model.decode_paged(
        params, tokens, k_pages, v_pages, jnp.asarray(page_table),
        jnp.asarray(seq_lens), jnp.asarray(active))
    k_after, v_after = np.asarray(k_after), np.asarray(v_after)

    written = np.zeros(shape[:3], bool)
    for b in np.flatnonzero(active):
        written[:, page_table[b, seq_lens[b] // PS], seq_lens[b] % PS] = True
    for b in np.flatnonzero(~active):
        assert not written[:, page_table[b, seq_lens[b] // PS],
                           seq_lens[b] % PS].any()
    for got, before, want in ((k_after, k_before, want_k),
                              (v_after, v_before, want_v)):
        np.testing.assert_array_equal(got[~written], before[~written])
        np.testing.assert_allclose(got[written], np.asarray(want)[written],
                                   atol=1e-5, rtol=0)
        assert not np.allclose(got[written], before[written])
    np.testing.assert_allclose(np.asarray(logits)[active],
                               np.asarray(want_logits)[active],
                               atol=1e-5, rtol=0)


# ---------------------------------------------------------------------------
# (b) scheduler admits late arrivals into in-flight batches
# ---------------------------------------------------------------------------

class TestScheduler:
    def test_late_request_admitted_and_completes(self, cfg):
        eng = ContinuousEngine(cfg, max_batch=2, page_size=16, max_len=64,
                               prompt_buckets=(16,), seed=0)
        rng = np.random.default_rng(0)
        early = [Request(rid=i,
                         prompt=rng.integers(0, cfg.vocab, 8, dtype=np.int32),
                         max_new_tokens=12, arrival_step=0)
                 for i in range(2)]
        late = Request(rid=2,
                       prompt=rng.integers(0, cfg.vocab, 8, dtype=np.int32),
                       max_new_tokens=3, arrival_step=4)
        stats = eng.run(early + [late])
        for r in early + [late]:
            assert len(r.out_tokens) == r.max_new_tokens, r.rid
            assert r.t_first_token is not None and r.t_done is not None
        # the late request rode along with the in-flight batch: total decode
        # steps stay well below a drain-then-restart schedule
        assert stats.decode_steps < 12 + 3
        eng.cache.allocator.check_leaks()

    def test_queue_fifo_and_arrival_gating(self):
        q = RequestQueue()
        a = Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=1,
                    arrival_step=5)
        q.push(a)
        assert q.pop_eligible(step=4) is None
        assert q.pop_eligible(step=5) is a

    def test_slot_reuse(self):
        s = Scheduler(max_batch=2)
        r = Request(rid=0, prompt=np.zeros(4, np.int32), max_new_tokens=1)
        slot = s.bind(r)
        r.out_tokens.append(1)
        assert s.finished_slots() == [slot]
        assert s.retire(slot) is r
        assert s.has_capacity()

    def test_pick_bucket(self):
        assert pick_bucket(8, (16, 32)) == 16
        assert pick_bucket(17, (16, 32)) == 32
        with pytest.raises(ValueError):
            pick_bucket(64, (16, 32))


# ---------------------------------------------------------------------------
# (c) page allocator: no double-free, no leaks, full bench-style run
# ---------------------------------------------------------------------------

class TestPageAllocator:
    def test_alloc_free_roundtrip(self):
        a = PageAllocator(8)
        pages = a.alloc(5)
        assert len(set(pages)) == 5 and a.n_free == 3
        a.free(pages)
        a.check_leaks()

    def test_exhaustion_raises(self):
        a = PageAllocator(4)
        a.alloc(4)
        assert not a.can_alloc(1)
        with pytest.raises(PageAllocationError):
            a.alloc(1)

    def test_double_free_raises(self):
        a = PageAllocator(4)
        pages = a.alloc(2)
        a.free(pages)
        with pytest.raises(PageAllocationError):
            a.free(pages)
        with pytest.raises(PageAllocationError):
            a.free([99])

    def test_no_leak_across_bench_run(self, cfg):
        """A full mixed-length Poisson run (the bench scenario, smaller)
        returns every page to the pool and never trips the allocator's
        invariants mid-flight."""
        eng = ContinuousEngine(cfg, max_batch=4, page_size=16, max_len=128,
                               prompt_buckets=(16, 32), seed=0)
        reqs = make_poisson_workload(10, rate=2.0, vocab=cfg.vocab, seed=3)
        for r in reqs:
            eng.submit(r)
        while eng.queue or eng.scheduler.has_active():
            eng.step()
            eng.cache.allocator.check_invariants()
        eng.cache.allocator.check_leaks()
        assert eng.cache.allocator.n_free == eng.cache.allocator.n_pages
        for r in reqs:
            assert len(r.out_tokens) == r.max_new_tokens

    def test_oversized_request_rejected(self, cfg):
        eng = ContinuousEngine(cfg, max_batch=2, page_size=16, max_len=64,
                               prompt_buckets=(16,), seed=0)
        big = Request(rid=0, prompt=np.zeros(16, np.int32),
                      max_new_tokens=64)
        with pytest.raises(ValueError):
            eng.submit(big)

    def test_single_token_request_never_decodes(self, cfg):
        """max_new_tokens == 1 is satisfied by prefill alone; it must retire
        before the decode dispatch, not ride through one."""
        eng = ContinuousEngine(cfg, max_batch=2, page_size=16, max_len=64,
                               prompt_buckets=(16,), seed=0)
        r = Request(rid=0, prompt=np.zeros(8, np.int32), max_new_tokens=1)
        stats = eng.run([r])
        assert r.out_tokens and len(r.out_tokens) == 1
        assert stats.decode_steps == 0
        eng.cache.allocator.check_leaks()

    def test_pool_smaller_than_request_rejected(self, cfg):
        """A request that could never be admitted must be rejected at
        submit time, not spin run() forever waiting for pages."""
        eng = ContinuousEngine(cfg, max_batch=2, page_size=16, max_len=128,
                               n_pages=4, prompt_buckets=(16,), seed=0)
        big = Request(rid=0, prompt=np.zeros(16, np.int32),
                      max_new_tokens=80)  # 6 pages > 4-page pool
        with pytest.raises(ValueError):
            eng.submit(big)


# ---------------------------------------------------------------------------
# engines end-to-end on the same workload
# ---------------------------------------------------------------------------

class TestWorkloadEngines:
    def test_static_and_continuous_complete_same_workload(self, cfg):
        mk = lambda: make_poisson_workload(6, rate=2.0, vocab=cfg.vocab,
                                           prompt_lens=(8, 16),
                                           out_lens=(2, 4, 6), seed=1)
        for eng in (StaticBatchEngine(cfg, batch=2, max_len=64,
                                      prompt_buckets=(16,), seed=0),
                    ContinuousEngine(cfg, max_batch=2, page_size=16,
                                     max_len=64, prompt_buckets=(16,),
                                     seed=0)):
            reqs = mk()
            stats = eng.run(reqs)
            assert stats.total_tokens == sum(r.max_new_tokens for r in reqs)
            assert stats.tokens_per_s > 0
            assert all(r.t_done is not None for r in reqs)


def test_serve_cfg_smoke_matches_family_guard():
    ssm = reduced(get_config("mamba2-2.7b"))
    with pytest.raises(ValueError):
        ContinuousEngine(ssm, max_batch=2, page_size=16, max_len=64)
