"""End-to-end training driver: the paper's §6.5 model (Llama-2-110M arch) on
synthetic data with checkpointing, straggler monitoring, and auto-resume.

Full run (a few hundred steps of the real 110M config — several CPU-hours):
    PYTHONPATH=src python examples/train_llm.py --steps 300

Smoke run (reduced config, ~1 min):
    PYTHONPATH=src python examples/train_llm.py --smoke --steps 30
"""

import argparse
import json
import os

from repro.configs.base import reduced
from repro.configs.registry import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.adamw import AdamWConfig
from repro.train.fault_tolerance import run_with_restarts
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama110m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default="runs/train_llm")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    tc = TrainConfig(
        batch=args.batch, seq=args.seq, ckpt_dir=args.ckpt_dir,
        ckpt_every=25, total_steps=args.steps, warmup=min(20, args.steps // 5),
        optimizer=AdamWConfig(lr=3e-4, compress_grads=args.compress_grads))

    trainer = run_with_restarts(lambda: Trainer(cfg, tc), args.steps)
    log_path = os.path.join(args.ckpt_dir, "metrics.jsonl")
    os.makedirs(args.ckpt_dir, exist_ok=True)
    with open(log_path, "a") as f:
        for m in trainer.metrics_log:
            f.write(json.dumps(m) + "\n")
    first = trainer.metrics_log[0] if trainer.metrics_log else {}
    last = trainer.metrics_log[-1] if trainer.metrics_log else {}
    print(f"trained {args.arch}{' (reduced)' if args.smoke else ''} to step "
          f"{trainer.step}")
    print(f"loss {first.get('loss'):.4f} -> {last.get('loss'):.4f}; "
          f"stragglers flagged: {len(trainer.monitor.events)}")
    print(f"metrics: {log_path}; checkpoints: {args.ckpt_dir}")


if __name__ == "__main__":
    main()
