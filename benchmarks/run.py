"""Benchmark harness — one module per paper table/figure.

    Table 2  → bench_kernels       (per-ISAX speedups via the compiler)
    Table 3  → bench_compile_stats (e-graph compilation statistics)
    Fig 2/3  → bench_synthesis     (interface-model decision quality)
    Fig 8    → bench_llm_serve     (LLM TTFT/ITL, int8, continuous batching)
    §HW mem  → bench_membw         (burst-DMA pipelined vs unpipelined)
    §Roofline→ bench_roofline      (dry-run aggregate)

Prints ``name,us_per_call,derived`` CSV.  Modules with a ``JSON_RECORDS``
list get their per-scenario records written to a JSON artifact so CI can
archive the perf trajectory: ``llm_serve`` → ``BENCH_serve.json`` (schema:
scenario, ttft_s, itl_s, tokens_per_s, …), ``compile_stats`` →
``BENCH_compile.json`` (Table-3 rows plus the dispatch sweep's ISAX
match-rate / compile-cache hit-rate / burst-pipeline selections),
``membw`` → ``BENCH_membw.json`` (pipelined vs unpipelined time per kernel
with the cost model's predicted gain), and ``pointcloud`` →
``BENCH_pointcloud.json`` (reference vs Pallas vs burst-pipelined for the
point-cloud vertical).  Off-TPU the kernel sweeps run in interpret mode and
carry ``timing_meaningful: false``; modules flag that with a ``SUMMARY``
line printed after their rows.

Env: BENCH_SMOKE=0 for full sizes.  ``--only <name>[,<name>…]`` restricts
to a subset of modules (e.g. ``--only llm_serve,compile_stats`` in CI).
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

ARTIFACTS = {
    "llm_serve": "BENCH_serve.json",
    "compile_stats": "BENCH_compile.json",
    "membw": "BENCH_membw.json",
    "pointcloud": "BENCH_pointcloud.json",
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module subset to run")
    ap.add_argument("--artifact-dir", default=".",
                    help="where to write BENCH_serve.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (bench_compile_stats, bench_kernels,
                            bench_llm_serve, bench_membw, bench_pointcloud,
                            bench_roofline, bench_synthesis)
    modules = [
        ("synthesis", bench_synthesis),
        ("kernels", bench_kernels),
        ("compile_stats", bench_compile_stats),
        ("membw", bench_membw),
        ("pointcloud", bench_pointcloud),
        ("llm_serve", bench_llm_serve),
        ("roofline", bench_roofline),
    ]
    if args.only:
        wanted = set(args.only.split(","))
        valid = [n for n, _ in modules]
        unknown = wanted - set(valid)
        if unknown:
            raise SystemExit(
                f"unknown bench module(s): {sorted(unknown)}; "
                f"valid: {valid}")
        modules = [(n, m) for n, m in modules if n in wanted]
    print("name,us_per_call,derived")
    failed = 0
    for name, mod in modules:
        try:
            for row in mod.run():
                print(row, flush=True)
        except Exception as e:
            failed += 1
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            traceback.print_exc(file=sys.stderr)
        # run verdict (e.g. membw's "interpret-mode parity check" note, so
        # interpreter wall times are never mistaken for a measured win)
        summary = getattr(mod, "SUMMARY", None)
        if summary:
            print(f"# {name}: {summary}", flush=True)
        artifact = ARTIFACTS.get(name)
        if artifact and getattr(mod, "JSON_RECORDS", None):
            path = f"{args.artifact_dir}/{artifact}"
            with open(path, "w") as f:
                json.dump(mod.JSON_RECORDS, f, indent=2)
            print(f"# wrote {path} ({len(mod.JSON_RECORDS)} records)",
                  flush=True)
    if failed:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
