"""Serving launcher CLI: ``python -m repro.launch.serve --arch <id> ...``.

``--continuous`` drives the paged-KV continuous-batching engine on a mixed-
length Poisson workload; the default drives the static-batch engine on a
uniform batch (the original one-shot demo).
"""

from __future__ import annotations

import argparse

import jax

from repro.compile import VALID_BACKENDS, LoweringConfig
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import ContinuousEngine, ServeEngine
from repro.serve.scheduler import make_poisson_workload
from repro.serve.telemetry import flash_tile_share, pad_share


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--backend", default=None, choices=VALID_BACKENDS,
                    help="kernel lowering backend (default: "
                         "REPRO_ATTENTION_IMPL env, else 'pallas' on a "
                         "TPU and 'xla' elsewhere)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--int8", action="store_true")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over a Poisson workload")
    ap.add_argument("--requests", type=int, default=16,
                    help="workload size for --continuous")
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    lowering = LoweringConfig.from_registry(backend=args.backend)

    if args.continuous:
        ps = args.page_size
        max_len = max(128, args.prompt_len + args.tokens + 16)
        max_len += (-max_len) % ps
        # Mixed-length workload scaled to the flags: prompts up to
        # --prompt-len, outputs up to --tokens.
        prompt_lens = tuple(sorted({max(4, args.prompt_len // 2),
                                    args.prompt_len}))
        out_lens = tuple(sorted({max(2, args.tokens // 4),
                                 max(2, args.tokens // 2), args.tokens}))
        # Buckets must be page multiples; derive them from the page size so
        # any --page-size works, growing until the largest prompt is covered.
        buckets, m = [], 1
        while ps * m <= max_len:
            buckets.append(ps * m)
            if ps * m >= args.prompt_len:
                break
            m *= 2
        if buckets[-1] < args.prompt_len:
            # Doubling overshot max_len before covering the prompt; a
            # rounded-up page multiple always fits (max_len ≥ prompt+tokens).
            buckets.append(args.prompt_len + (-args.prompt_len) % ps)
        buckets = tuple(buckets)
        eng = ContinuousEngine(cfg, max_batch=args.batch,
                               page_size=ps, max_len=max_len,
                               prompt_buckets=buckets, quantize=args.int8,
                               lowering=lowering)
        reqs = make_poisson_workload(args.requests, rate=2.0, vocab=cfg.vocab,
                                     prompt_lens=prompt_lens,
                                     out_lens=out_lens)
        stats = eng.run(reqs)
        print(f"arch={cfg.name} continuous int8={args.int8} "
              f"requests={stats.n_requests} tokens={stats.total_tokens} "
              f"TTFT={stats.mean_ttft_s * 1e3:.1f}ms "
              f"ITL={stats.mean_itl_s * 1e3:.2f}ms "
              f"({stats.tokens_per_s:.1f} tok/s, "
              f"{stats.decode_steps} decode steps)")
        print(f"engine: admissions={len(reqs)} "
              f"prefill_padding={100 * pad_share(reqs):.1f}% "
              f"flash_tiles_run={100 * flash_tile_share(reqs, cfg):.1f}% "
              f"decode_steps={eng.decode_steps}")
        return

    eng = ServeEngine(cfg, max_len=args.prompt_len + args.tokens + 8,
                      quantize=args.int8, lowering=lowering)
    prompts = jax.random.randint(jax.random.key(0),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)
    toks, stats = eng.generate({"tokens": prompts}, args.tokens)
    print(f"arch={cfg.name} int8={args.int8} out={toks.shape} "
          f"TTFT={stats.ttft_s * 1e3:.1f}ms ITL={stats.itl_s * 1e3:.2f}ms "
          f"({stats.tokens_per_s:.1f} tok/s)")


if __name__ == "__main__":
    main()
