"""Serving engines: the original static-batch ``ServeEngine`` (one prefill +
jit'd decode loop over a monolithic KV cache, TTFT/ITL measurement — the
paper's §6.5 LLM-inference metrics, optional int8 weights) and the
continuous-batching ``ContinuousEngine``:

    RequestQueue → Scheduler (slot admission/retirement)
                 → PagedKVCache (fixed-size pages, free-list allocator)
                 → jit-stable decode step (gathers pages via the page table)

New requests are admitted into in-flight decode batches the moment a slot
and enough pages free up; prompts are prefilled one at a time into bucketed
shapes (bounded recompiles) and their KV scattered into pages, so mixed
prompt/output lengths no longer waste decode steps on padding.
``StaticBatchEngine`` runs the same workload API with classic static
batching — the baseline the serve benchmark compares against.

The static decode step is the same function the dry-run lowers as
``serve_step``.

All engines obtain their attention/rmsnorm/matmul kernels through the
``repro.compile`` dispatcher: a ``LoweringConfig`` (constructor reads the
``REPRO_ATTENTION_IMPL`` env override; pass ``lowering=`` to pin a backend)
is threaded into the model, and the e-graph ISAX pipeline decides per
(op, shape, dtype, backend) whether prefill/decode run an extracted Pallas
kernel or the XLA reference.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile import LoweringConfig
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.registry import Model, get_model
from repro.serve.kv_cache import PagedKVCache
from repro.serve.scheduler import (Request, RequestQueue, Scheduler,
                                   pick_bucket)
from repro.serve.telemetry import install_gc_spans, span


@dataclasses.dataclass
class ServeStats:
    """Latency/throughput stats for one static-batch generation."""

    ttft_s: float
    itl_s: float
    tokens: int
    tokens_per_s: float


def quantize_params_int8(params):
    """Per-tensor symmetric int8 quantization of every ≥2-D weight; returns
    (quantized tree with {'q','scale'} leaves, dequant function)."""

    def _quant(p):
        if p.ndim >= 2:
            scale = jnp.maximum(jnp.max(jnp.abs(p.astype(jnp.float32))),
                                1e-12) / 127.0
            q = jnp.clip(jnp.round(p.astype(jnp.float32) / scale),
                         -127, 127).astype(jnp.int8)
            return {"q": q, "scale": scale, "dtype": str(p.dtype)}
        return p

    def _is_weight(x):
        return isinstance(x, jax.Array)

    qtree = jax.tree.map(_quant, params, is_leaf=_is_weight)

    def _dequant(tree):
        def _deq(x):
            if isinstance(x, dict) and "q" in x:
                return (x["q"].astype(jnp.float32) * x["scale"]).astype(
                    L.dtype_of(x["dtype"]) if isinstance(x["dtype"], str)
                    else jnp.float32)
            return x
        return jax.tree.map(_deq, tree,
                            is_leaf=lambda x: isinstance(x, dict)
                            and "q" in x)

    return qtree, _dequant


def quantization_error(params, qtree, dequant) -> float:
    """Mean relative L1 error of the int8 round-trip over all weights."""
    deq = dequant(qtree)
    num = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32)
                                    - b.astype(jnp.float32))))
              for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(deq)))
    den = sum(float(jnp.sum(jnp.abs(a))) for a in jax.tree.leaves(params))
    return num / max(den, 1e-12)


class ServeEngine:
    """Single-batch prefill + decode engine over a monolithic KV cache —
    the TTFT/ITL measurement harness and the numerics reference the other
    engines are checked against."""

    def __init__(self, model_cfg: ModelConfig, params=None, *,
                 max_len: int = 512, quantize: bool = False, seed: int = 0,
                 lowering: Optional[LoweringConfig] = None):
        self.cfg = model_cfg
        # Kernel choice is a compile decision: the engine's prefill/decode
        # obtain attention/rmsnorm/matmul implementations from the
        # repro.compile dispatcher through this LoweringConfig (env override
        # REPRO_ATTENTION_IMPL is read by its constructor).
        self.lowering = (lowering if lowering is not None
                         else LoweringConfig.from_registry())
        self.model = get_model(model_cfg, lowering=self.lowering)
        self.max_len = max_len
        # (memory model: int8 at rest, dequantized once on load — wire/HBM
        # bytes halved)
        self.params = _init_params(self.model, params, quantize, seed)
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, self.max_len),
            static_argnums=())
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(2,))

    def generate(self, batch: dict, n_tokens: int,
                 greedy: bool = True) -> tuple[np.ndarray, ServeStats]:
        """Prefill ``batch`` and greedily decode ``n_tokens`` tokens;
        returns ``(tokens (B, n_tokens), ServeStats)``."""
        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params, batch)
        logits.block_until_ready()
        ttft = time.perf_counter() - t0

        n_prefix = (self.cfg.n_prefix_tokens
                    if self.cfg.family == "vlm" else 0)
        pos = batch["tokens"].shape[1] + n_prefix
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [np.asarray(token)]
        t1 = time.perf_counter()
        for i in range(n_tokens - 1):
            logits, caches = self._decode(self.params, token, caches,
                                          jnp.int32(pos + i))
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(np.asarray(token))
        token.block_until_ready()
        t2 = time.perf_counter()
        itl = (t2 - t1) / max(n_tokens - 1, 1)
        stats = ServeStats(ttft_s=ttft, itl_s=itl, tokens=n_tokens,
                           tokens_per_s=n_tokens / (t2 - t0))
        return np.stack(out, axis=1), stats


# ---------------------------------------------------------------------------
# Workload-level serving (lists of Requests with arrival times)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WorkloadStats:
    """Aggregate latency/throughput over one served request workload."""

    n_requests: int
    total_tokens: int
    wall_s: float
    tokens_per_s: float
    mean_ttft_s: float
    mean_itl_s: float
    decode_steps: int


def _aggregate(requests: list[Request], wall_s: float,
               decode_steps: int) -> WorkloadStats:
    total = sum(len(r.out_tokens) for r in requests)
    ttfts = [r.ttft_s for r in requests if r.t_first_token is not None]
    itls = [r.itl_s for r in requests if len(r.out_tokens) > 1]
    return WorkloadStats(
        n_requests=len(requests), total_tokens=total, wall_s=wall_s,
        tokens_per_s=total / max(wall_s, 1e-9),
        mean_ttft_s=float(np.mean(ttfts)) if ttfts else 0.0,
        mean_itl_s=float(np.mean(itls)) if itls else 0.0,
        decode_steps=decode_steps)


DEFAULT_BUCKETS = (16, 32, 64)


def _init_params(model: Model, params, quantize: bool, seed: int):
    if params is None:
        params = model.init(jax.random.key(seed))
    if quantize:
        qtree, dequant = quantize_params_int8(params)
        params = dequant(qtree)
    return params


def _filter_buckets(buckets: tuple[int, ...], max_len: int) -> tuple[int, ...]:
    out = tuple(b for b in sorted(buckets) if b <= max_len)
    assert out, f"no prompt bucket in {buckets} fits max_len {max_len}"
    return out


class ContinuousEngine:
    """Continuous-batching server over a paged KV cache.

    ``max_batch`` decode slots share a pool of ``n_pages`` KV pages; the
    decode step's shapes are fixed at construction, so admissions and
    retirements never trigger recompilation.  Prefill compiles once per
    prompt bucket.  Arrival times are in decode steps (virtual time, see
    ``scheduler``); latencies are wall-clock.
    """

    def __init__(self, model_cfg: ModelConfig, params=None, *,
                 max_batch: int = 8, page_size: int = 16,
                 max_len: int = 128, n_pages: Optional[int] = None,
                 prompt_buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 quantize: bool = False, seed: int = 0,
                 lowering: Optional[LoweringConfig] = None):
        self.cfg = model_cfg
        self.lowering = (lowering if lowering is not None
                         else LoweringConfig.from_registry())
        self.model = get_model(model_cfg, lowering=self.lowering)
        if self.model.decode_paged is None:
            raise ValueError(
                f"family {model_cfg.family!r} has no paged decode path")
        self.params = _init_params(self.model, params, quantize, seed)
        self.max_len = max_len
        self.prompt_buckets = _filter_buckets(prompt_buckets, max_len)
        assert all(b % page_size == 0 for b in self.prompt_buckets), (
            "prompt buckets must be page multiples")
        if n_pages is None:
            n_pages = max_batch * (max_len // page_size)
        self.cache = PagedKVCache(model_cfg, max_batch=max_batch,
                                  page_size=page_size, n_pages=n_pages,
                                  max_len=max_len)
        self.scheduler = Scheduler(max_batch)
        self.queue = RequestQueue()
        self.step_count = 0
        self.decode_steps = 0   # decode dispatches since the engine was built
        install_gc_spans()
        self._next_tokens = np.zeros((max_batch,), np.int32)
        # jax.jit caches one executable per prompt-bucket shape.
        self._prefill = jax.jit(
            lambda p, b, length: self.model.prefill_at(p, b, length))
        # Decode state lives on device between steps; host re-uploads it only
        # when batch membership changes (admission/retirement), and argmax +
        # seq-len advance run inside the jit so steady-state decode is a
        # single dispatch + one small token fetch.
        self._device_state = None
        self._membership_dirty = True

        def _decode_fn(p, t, kp, vp, pt, sl, act):
            logits, kp, vp = self.model.decode_paged(p, t, kp, vp, pt, sl,
                                                     act)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, kp, vp, sl + act.astype(sl.dtype)

        self._decode = jax.jit(_decode_fn, donate_argnums=(2, 3))

    # -- internals ---------------------------------------------------------

    def _lifetime_tokens(self, req: Request, bucket: int) -> int:
        return max(bucket, req.prompt_len + req.max_new_tokens)

    def _admit(self, req: Request) -> None:
        with span("engine.admit", rid=req.rid):
            slot = self.scheduler.bind(req)
            bucket = pick_bucket(req.prompt_len, self.prompt_buckets)
            req.bucket = bucket
            self.cache.bind_slot(slot, self._lifetime_tokens(req, bucket))
            with span("engine.prefill", rid=req.rid):
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :req.prompt_len] = req.prompt
                logits, kv = self._prefill(
                    self.params, {"tokens": jnp.asarray(tokens)},
                    jnp.int32(req.prompt_len))
            with span("engine.kv_write", rid=req.rid):
                self.cache.write_prefill(slot, kv, req.prompt_len)
            with span("engine.first_token", rid=req.rid):
                first = int(jnp.argmax(logits[0]))
            now = time.perf_counter()
            req.out_tokens.append(first)
            req.t_first_token = now
            if len(req.out_tokens) >= req.max_new_tokens:
                req.t_done = now
            self._next_tokens[slot] = first
            self._membership_dirty = True

    def _retire_finished(self) -> None:
        with span("engine.retire"):
            for slot in self.scheduler.finished_slots():
                self.scheduler.retire(slot)
                self.cache.release_slot(slot)
                self._membership_dirty = True

    def _decode_active(self, active: list) -> None:
        """One decode step of the slots in ``active``: upload the batch's
        state if its membership changed, dispatch, wait for the new tokens
        and hand them to their requests."""
        if self._membership_dirty or self._device_state is None:
            with span("engine.upload"):
                pt, sl, act = self.cache.device_views(active)
                # snapshot: _next_tokens is mutated after dispatch and the
                # host→device copy is async (see device_views)
                self._device_state = (jnp.asarray(self._next_tokens.copy()),
                                      pt, sl, act)
                self._membership_dirty = False
        tokens_d, pt, sl, act = self._device_state
        with span("engine.decode"):
            tokens_d, self.cache.k_pages, self.cache.v_pages, sl = \
                self._decode(self.params, tokens_d, self.cache.k_pages,
                             self.cache.v_pages, pt, sl, act)
        self._device_state = (tokens_d, pt, sl, act)
        self.decode_steps += 1
        with span("engine.fetch"):
            nxt = np.asarray(tokens_d)
        now = time.perf_counter()
        with span("engine.bookkeep"):
            for slot in active:
                req = self.scheduler.slots[slot]
                self.cache.seq_lens[slot] += 1
                if len(req.out_tokens) < req.max_new_tokens:
                    req.out_tokens.append(int(nxt[slot]))
                    if len(req.out_tokens) >= req.max_new_tokens:
                        req.t_done = now
                self._next_tokens[slot] = nxt[slot]

    def step(self) -> bool:
        """One scheduler iteration: retire → admit (+prefill) → decode.
        Returns True iff a decode step actually ran."""
        with jax.profiler.StepTraceAnnotation("engine.step",
                                             step_num=self.step_count):
            now = time.perf_counter()
            self._retire_finished()
            # Stamp eligibility (for TTFT) on everything that has arrived.
            for r in self.queue:
                if r.arrival_step <= self.step_count and r.t_eligible is None:
                    r.t_eligible = now
            while self.scheduler.has_capacity():
                head = self.queue.head()
                if head is None or head.arrival_step > self.step_count:
                    break
                bucket = pick_bucket(head.prompt_len, self.prompt_buckets)
                if not self.cache.can_admit(
                        self._lifetime_tokens(head, bucket)):
                    break  # FIFO head-of-line: wait for pages to free
                req = self.queue.pop_eligible(self.step_count)
                if req.t_eligible is None:
                    req.t_eligible = now
                self._admit(req)
            # A request whose budget was met at prefill (max_new_tokens == 1)
            # must not ride through a decode dispatch.
            self._retire_finished()
            active = self.scheduler.active_slots
            if active:
                self._decode_active(active)
            self.step_count += 1
            return bool(active)

    # -- public API --------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Queue a request, rejecting one that could never be admitted
        (lifetime exceeding ``max_len`` or the whole page pool)."""
        bucket = pick_bucket(req.prompt_len, self.prompt_buckets)
        lifetime = self._lifetime_tokens(req, bucket)
        if lifetime > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {req.prompt_len} + "
                f"max_new {req.max_new_tokens} exceeds max_len {self.max_len}")
        if self.cache.pages_needed(lifetime) > self.cache.n_pages:
            raise ValueError(
                f"request {req.rid}: needs "
                f"{self.cache.pages_needed(lifetime)} pages but the pool "
                f"only has {self.cache.n_pages} — it could never be "
                f"admitted")
        self.queue.push(req)

    def run(self, requests: list[Request]) -> WorkloadStats:
        """Serve a whole workload to completion; asserts no page leaked."""
        for r in requests:
            self.submit(r)
        # Arrival steps are relative to workload start; a reused engine must
        # not carry a prior run's step count into the gating.
        self.step_count = 0
        steps0 = self.decode_steps
        t0 = time.perf_counter()
        while self.queue or self.scheduler.has_active():
            self.step()
        wall = time.perf_counter() - t0
        self.cache.allocator.check_leaks()
        return _aggregate(requests, wall, self.decode_steps - steps0)


class StaticBatchEngine:
    """Classic static batching over the same workload API: groups of up to
    ``batch`` eligible requests are padded to a common prompt bucket,
    prefilled together, and decoded for max(output length) steps — the
    whole group holds its slots until the longest member finishes.  Output
    *tokens* for shorter-prompt members are computed at padded positions
    (standard static-batch behavior); this engine is the throughput/latency
    baseline, the numerics reference is ``ServeEngine``."""

    def __init__(self, model_cfg: ModelConfig, params=None, *,
                 batch: int = 8, max_len: int = 128,
                 prompt_buckets: tuple[int, ...] = DEFAULT_BUCKETS,
                 quantize: bool = False, seed: int = 0,
                 lowering: Optional[LoweringConfig] = None):
        self.cfg = model_cfg
        self.lowering = (lowering if lowering is not None
                         else LoweringConfig.from_registry())
        self.model = get_model(model_cfg, lowering=self.lowering)
        self.params = _init_params(self.model, params, quantize, seed)
        self.batch = batch
        self.max_len = max_len
        self.prompt_buckets = _filter_buckets(prompt_buckets, max_len)
        # jax.jit caches one executable per prompt-bucket shape.
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill(p, b, self.max_len))

        def _decode_fn(p, t, c, pos):
            logits, c = self.model.decode_step(p, t, c, pos)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), c

        self._decode = jax.jit(_decode_fn, donate_argnums=(2,))

    def run(self, requests: list[Request]) -> WorkloadStats:
        """Serve a workload in static groups (the baseline scheduler)."""
        queue = RequestQueue()
        for r in requests:
            queue.push(r)
        t0 = time.perf_counter()
        step_count = 0
        decode_steps = 0
        while queue:
            now = time.perf_counter()
            for r in queue:
                if r.arrival_step <= step_count and r.t_eligible is None:
                    r.t_eligible = now
            group = []
            while len(group) < self.batch:
                req = queue.pop_eligible(step_count)
                if req is None:
                    break
                if req.t_eligible is None:
                    req.t_eligible = now
                group.append(req)
            if not group:
                step_count += 1  # idle: wait for the next arrival
                continue
            bucket = pick_bucket(max(r.prompt_len for r in group),
                                 self.prompt_buckets)
            n_gen = max(r.max_new_tokens for r in group)
            # Decode writes KV at positions bucket..bucket+n_gen-2 (the last
            # generated token is never fed back).
            if bucket + n_gen - 1 > self.max_len:
                raise ValueError(
                    f"group needs positions up to {bucket + n_gen - 2} but "
                    f"the KV cache holds max_len={self.max_len}; decode "
                    f"writes past it would silently clamp")
            tokens = np.zeros((self.batch, bucket), np.int32)
            for i, r in enumerate(group):
                tokens[i, :r.prompt_len] = r.prompt
            logits, caches = self._prefill(
                self.params, {"tokens": jnp.asarray(tokens)})
            token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            host = np.asarray(token)  # sync before the TTFT stamp
            now = time.perf_counter()
            for i, r in enumerate(group):
                r.out_tokens.append(int(host[i]))
                r.t_first_token = now
                if r.max_new_tokens == 1:
                    r.t_done = now
            n_steps = n_gen - 1
            for j in range(n_steps):
                token, caches = self._decode(self.params, token, caches,
                                             jnp.int32(bucket + j))
                host = np.asarray(token)
                now = time.perf_counter()
                for i, r in enumerate(group):
                    if len(r.out_tokens) < r.max_new_tokens:
                        r.out_tokens.append(int(host[i]))
                        if len(r.out_tokens) >= r.max_new_tokens:
                            r.t_done = now
                # Requests whose virtual arrival falls inside this group's
                # decode start waiting *now*; stamping here (not after the
                # group drains) charges that head-of-line wait to their TTFT.
                for r in queue:
                    if (r.arrival_step <= step_count + j + 1
                            and r.t_eligible is None):
                        r.t_eligible = now
            step_count += n_steps
            decode_steps += n_steps
        wall = time.perf_counter() - t0
        return _aggregate(requests, wall, decode_steps)
