"""Batched serving example (paper §6.5): prefill + decode with KV cache,
TTFT/ITL measurement, int8 weight quantization, resuming weights from the
train_llm checkpoint when present — then the same traffic served by the
continuous-batching engine (paged KV cache, rolling admissions).

    PYTHONPATH=src python examples/serve_llm.py --smoke --tokens 16
"""

import argparse

import jax
import numpy as np

from repro.compile import VALID_BACKENDS, LoweringConfig
from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serve.engine import ContinuousEngine, ServeEngine
from repro.serve.scheduler import Request
from repro.train import checkpoint as ckpt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama110m")
    ap.add_argument("--backend", default=None, choices=VALID_BACKENDS,
                    help="kernel lowering backend (default: "
                         "REPRO_ATTENTION_IMPL env, else 'pallas' on a "
                         "TPU and 'xla' elsewhere)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--int8", action="store_true",
                    help="serve the continuous-batching section with int8 "
                         "weights (the fp/int8 comparison above always runs "
                         "both)")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    lowering = LoweringConfig.from_registry(backend=args.backend)
    params = None
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        tree, mf = ckpt.load(args.ckpt_dir)
        params = tree["params"]
        print(f"restored step-{mf['step']} weights from {args.ckpt_dir}")
    max_len = args.prompt_len + args.tokens + 8
    prompts = jax.random.randint(jax.random.key(0),
                                 (args.batch, args.prompt_len), 0, cfg.vocab)

    for mode, quant in (("fp", False), ("int8", True)):
        eng = ServeEngine(cfg, params=params, max_len=max_len,
                          quantize=quant, lowering=lowering)
        toks, stats = eng.generate({"tokens": prompts}, args.tokens)
        print(f"[{mode:5s}] TTFT {stats.ttft_s * 1e3:8.1f} ms | "
              f"ITL {stats.itl_s * 1e3:7.2f} ms | "
              f"{stats.tokens_per_s:7.1f} tok/s | out {toks.shape}")

    # Continuous batching: the same prompts arrive as individual requests
    # (staggered arrivals, mixed output lengths) and share decode slots
    # through the paged KV cache.
    if cfg.family not in ("dense", "moe"):
        print(f"[cont ] skipped: no paged decode path for {cfg.family}")
        return
    bucket = args.prompt_len + (-args.prompt_len) % 16
    cmax_len = max(128, max_len, bucket + args.tokens)
    cmax_len += (-cmax_len) % 16
    ceng = ContinuousEngine(cfg, params=params, max_batch=args.batch,
                            page_size=16, max_len=cmax_len,
                            prompt_buckets=(16, 32, 64, bucket),
                            quantize=args.int8, lowering=lowering)
    host_prompts = np.asarray(prompts, np.int32)
    reqs = [Request(rid=i, prompt=host_prompts[i],
                    max_new_tokens=max(2, args.tokens // (1 + i % 3)),
                    arrival_step=2 * i)
            for i in range(args.batch)]
    wstats = ceng.run(reqs)
    print(f"[cont ] TTFT {wstats.mean_ttft_s * 1e3:8.1f} ms | "
          f"ITL {wstats.mean_itl_s * 1e3:7.2f} ms | "
          f"{wstats.tokens_per_s:7.1f} tok/s | "
          f"{wstats.total_tokens} tokens in {wstats.decode_steps} steps")


if __name__ == "__main__":
    main()
