#!/usr/bin/env python3
"""Chip smoke run: serve llama110m at its published width on one TPU v5e.

Drives the continuous-batching serve path once, through the entry points a
user calls: ``ContinuousEngine`` → paged KV cache → ``repro.compile``
dispatch → Pallas kernels, with the lowering the platform picks (compiled
Pallas on a TPU).  Then it checks that

* every request got its ``max_new_tokens`` and no KV page leaked;
* the dispatcher lowered causal prefill attention (``attention_causal``)
  and ``rmsnorm`` to ISAX kernels at every prefill bucket, and the compiled
  prefill holds a ``tpu_custom_call``;
* prefill logits at each bucket, and the logits of the first paged decode
  step, agree with an engine on the XLA reference lowering, run on the same
  parameters on the same chip.

Run it from the repository root on a machine with one TPU v5e::

    python chip_smoke.py

It runs in one process and starts none.  Without a TPU v5e, or when any
check fails, it exits non-zero and prints no ok line.  The last line of its
standard output is ``{"ok": true, "device": {...}}``.  Times printed on the
way are smoke-run figures, not benchmark numbers.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile import LoweringConfig  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serve.engine import ContinuousEngine  # noqa: E402
from repro.serve.scheduler import Request, make_poisson_workload  # noqa: E402

#: Largest pallas-vs-xla logit difference accepted, as a share of the
#: reference logits' largest magnitude.  Both engines run the same XLA
#: projections and MLPs; they differ in attention and RMSNorm, where the
#: kernels and XLA's fusions round and accumulate float32 differently (TPU
#: matmuls at default precision round their operands to bfloat16, unit
#: roundoff 2^-9).  Such differences, compounded over 12 layers, stay near
#: 1e-2 of the logit scale; a wrong mask, head mapping or softmax moves the
#: logits by a large fraction of it.
LOGIT_TOL = 3e-2


@dataclasses.dataclass(frozen=True)
class Plan:
    """Sizes of one smoke run (defaults: llama110m on one v5e)."""

    max_batch: int = 8
    page_size: int = 16
    max_len: int = 1024
    #: 512 selects the burst-pipelined flash kernel, 64 and 128 the plain one.
    buckets: tuple[int, ...] = (64, 128, 512)
    n_requests: int = 16
    prompt_lens: tuple[int, int] = (40, 500)    # inclusive range
    out_lens: tuple[int, int] = (16, 128)       # inclusive range
    seed: int = 0


def device_stamp() -> dict:
    """The device as JAX reports it."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device(stamp: dict) -> None:
    """Refuse anything but a TPU v5e: the constants ``kernel_synth``
    schedules against (``core/interface_model.py``) describe only that
    chip."""
    if stamp["platform"] != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (platform "
                         f"{stamp['platform']!r}); nothing was run")
    kind = stamp["kind"].lower()
    if "v5 lite" not in kind and "v5e" not in kind:
        raise SystemExit(f"chip_smoke: device {stamp['kind']!r} is not a TPU "
                         f"v5e, the only chip kernel_synth models")


def build_engine(cfg, plan: Plan, lowering: LoweringConfig, params=None):
    """A ``ContinuousEngine`` at the plan's sizes; returns (engine, seconds)."""
    t0 = time.perf_counter()
    eng = ContinuousEngine(cfg, params, max_batch=plan.max_batch,
                           page_size=plan.page_size, max_len=plan.max_len,
                           prompt_buckets=plan.buckets, seed=plan.seed,
                           lowering=lowering)
    jax.block_until_ready(eng.params)
    return eng, time.perf_counter() - t0


def warm_up(eng: ContinuousEngine, plan: Plan) -> float:
    """Serve one two-token request per bucket, which compiles every prefill
    bucket and the decode step; returns the seconds it took."""
    rng = np.random.default_rng(plan.seed + 1)
    reqs = [Request(rid=i, prompt=rng.integers(0, eng.cfg.vocab, b,
                                               dtype=np.int32),
                    max_new_tokens=2)
            for i, b in enumerate(plan.buckets)]
    t0 = time.perf_counter()
    eng.run(reqs)
    return time.perf_counter() - t0


def workload(plan: Plan, vocab: int):
    """Seeded Poisson workload with prompt and output lengths drawn
    uniformly from the plan's ranges."""
    lo, hi = plan.prompt_lens
    olo, ohi = plan.out_lens
    return make_poisson_workload(
        plan.n_requests, rate=1.0, vocab=vocab,
        prompt_lens=tuple(range(lo, hi + 1)),
        out_lens=tuple(range(olo, ohi + 1)), seed=plan.seed)


def check_requests(eng: ContinuousEngine, reqs) -> None:
    """Every request got its full budget, and every page came back."""
    short = [(r.rid, len(r.out_tokens), r.max_new_tokens) for r in reqs
             if len(r.out_tokens) != r.max_new_tokens]
    if short:
        raise AssertionError(f"requests without their max_new_tokens "
                             f"(rid, got, wanted): {short}")
    eng.cache.allocator.check_leaks()


def dispatch_lines(eng: ContinuousEngine, plan: Plan) -> list[str]:
    """Check that causal prefill attention and RMSNorm lowered to ISAX
    kernels at every prefill bucket; returns one line per compile record of
    this engine."""
    recs = [r for r in eng.lowering.dispatcher.records.values()
            if r.key.backend == eng.lowering.backend]
    for b in plan.buckets:
        for op, rows in (("attention_causal", lambda s: s[1]),
                         ("rmsnorm", lambda s: s[0])):
            got = [r for r in recs if r.key.op == op and rows(r.key.shape) == b]
            if not got or any(r.impl != "isax" for r in got):
                raise AssertionError(
                    f"{op} at bucket {b} not lowered to isax: "
                    f"{[(r.key.shape, r.impl, r.note) for r in got]}")
    lines = []
    for r in recs:
        sched = r.schedule or {}
        lines.append(f"dispatch op={r.key.op} shape={list(r.key.shape)} "
                     f"impl={r.impl} pipelined={sched.get('pipelined', False)}"
                     f" note={r.note!r}")
    return lines


def prompt_batch(plan: Plan, bucket: int, vocab: int):
    """A seeded prompt three tokens short of ``bucket``, right-padded."""
    length = bucket - 3
    rng = np.random.default_rng(plan.seed + bucket)
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :length] = rng.integers(0, vocab, length)
    return {"tokens": jnp.asarray(tokens)}, jnp.int32(length)


def prefill_has_kernel(eng: ContinuousEngine, plan: Plan, bucket: int) -> bool:
    """Whether the compiled prefill program at ``bucket`` calls a Mosaic
    kernel."""
    batch, length = prompt_batch(plan, bucket, eng.cfg.vocab)
    hlo = (jax.jit(eng.model.prefill_at).lower(eng.params, batch, length)
           .compile().as_text())
    return "tpu_custom_call" in hlo


def _first_decode_logits(eng: ContinuousEngine, kv, length, token):
    """Logits of one paged decode step for a request in slot 0, whose
    prefill KV is ``kv``; the slot is released again."""
    cache = eng.cache
    cache.bind_slot(0, int(length) + 1)
    try:
        cache.write_prefill(0, kv, int(length))
        pt, sl, act = cache.device_views({0})
        tokens = jnp.zeros((eng.scheduler.max_batch,), jnp.int32).at[0].set(
            token)
        logits, _, _ = jax.jit(eng.model.decode_paged)(
            eng.params, tokens, cache.k_pages, cache.v_pages, pt, sl, act)
        return logits[0]
    finally:
        cache.release_slot(0)


def rel_err(got, want) -> float:
    """max |got - want| over max |want|."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def compare_logits(eng: ContinuousEngine, ref: ContinuousEngine,
                   plan: Plan) -> list[tuple[str, int, float]]:
    """Prefill logits at each bucket and the first paged decode step's
    logits of ``eng`` against ``ref``; returns (phase, bucket, rel_err)
    rows and raises when one exceeds ``LOGIT_TOL``."""
    rows = []
    for b in plan.buckets:
        batch, length = prompt_batch(plan, b, eng.cfg.vocab)
        got, kv = jax.jit(eng.model.prefill_at)(eng.params, batch, length)
        want, kv_ref = jax.jit(ref.model.prefill_at)(ref.params, batch,
                                                     length)
        rows.append(("prefill", b, rel_err(got, want)))
        token = jnp.argmax(want[0]).astype(jnp.int32)
        rows.append(("decode", b, rel_err(
            _first_decode_logits(eng, kv, length, token),
            _first_decode_logits(ref, kv_ref, length, token))))
    bad = [r for r in rows if not r[2] <= LOGIT_TOL]
    if bad:
        raise AssertionError(f"{eng.lowering.backend} logits differ from "
                             f"{ref.lowering.backend} beyond {LOGIT_TOL}: "
                             f"{bad}")
    return rows


def main() -> int:
    stamp = device_stamp()
    print(f"device platform={stamp['platform']} kind={stamp['kind']!r} "
          f"count={stamp['count']}", flush=True)
    check_device(stamp)
    print(f"compile cache: {enable_compile_cache()}")

    plan = Plan()
    cfg = get_config("llama110m")
    print(f"model {cfg.name}: n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"n_heads={cfg.n_heads} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"dtype={cfg.param_dtype}")
    print(f"engine max_batch={plan.max_batch} page_size={plan.page_size} "
          f"max_len={plan.max_len} buckets={plan.buckets}")

    eng, setup_s = build_engine(cfg, plan, LoweringConfig.from_registry())
    print(f"lowering backend: {eng.lowering.backend}")
    warm_s = warm_up(eng, plan)
    reqs = workload(plan, cfg.vocab)
    stats = eng.run(reqs)
    check_requests(eng, reqs)
    print(f"served {stats.n_requests} requests, {stats.total_tokens} tokens "
          f"in {stats.decode_steps} decode steps; all budgets met, no page "
          f"leaked")
    for line in dispatch_lines(eng, plan):
        print(line)
    for b in plan.buckets:
        if not prefill_has_kernel(eng, plan, b):
            raise AssertionError(f"compiled prefill at bucket {b} holds no "
                                 f"tpu_custom_call")
    print(f"compiled prefill at buckets {plan.buckets} holds tpu_custom_call")

    ref, _ = build_engine(cfg, plan, LoweringConfig.from_registry("xla"),
                          params=eng.params)
    for phase, b, err in compare_logits(eng, ref, plan):
        print(f"logits {phase} bucket={b}: max|pallas-xla|/max|xla| = {err!r}"
              f" (tolerance {LOGIT_TOL})")
    eng.cache.allocator.check_leaks()

    ttft = np.array([r.ttft_s for r in reqs])
    itl = np.array([r.itl_s for r in reqs])
    print("smoke-run figures (not benchmark numbers): "
          f"setup_s={setup_s!r} warmup_compile_s={warm_s!r} "
          f"ttft_median_s={float(np.median(ttft))!r} "
          f"ttft_max_s={float(ttft.max())!r} "
          f"itl_median_s={float(np.median(itl))!r} "
          f"tokens_per_s={stats.tokens_per_s!r}")
    print(json.dumps({"ok": True, "device": stamp}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
