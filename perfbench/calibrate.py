#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from.

    python perfbench/calibrate.py --workload <cell> --seeds 101-112 \
        --controls 3 --seconds 8

One process on the chip.  For each seed it makes that seed's weights, serves
a short window of the cell's traffic at the cell's own load, lets every
request due in it finish, and compares a sample as large as a run's with the
reference, as ``run.py`` does: that is the program's reading.  On the first
``--controls`` seeds it also reads the controls, the reference computed in
int8 and in fp8 (``reference.py``), over the same prompts and served
tokens.  Prints one JSON line per seed.  The limit goes between the largest
program reading and the smallest control reading (see PERF.md).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]


def seeds_of(spec: str) -> list[int]:
    lo, _, hi = spec.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)

    from perfbench import bench, check, serve, traffic, weights
    from perfbench import run as harness
    from perfbench.reference import Reference

    cell = bench.load_cell(args.workload)
    bench.check_device(cell.chips)
    harness.setup_jax()
    mix, srv = cell.traffic, cell.config["serve"]
    seeds = seeds_of(args.seeds)
    params, eng = harness.build(cell, seeds[0])
    inst = serve.Instrumented(eng)
    serve.warm_up(eng, traffic.buckets_used(mix, args.seconds,
                                            srv["prompt_buckets"]),
                  cell.config["vocab_size"], 0)
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            eng.params = params = None
            params = weights.make(cell.config, seed, cell.arch)
            eng.params = params
        items = serve.items_for(cell, args.seconds, seed)
        served = serve.run_schedule(eng, inst, items,
                                    time.perf_counter() + 0.05, mix["ramp_s"],
                                    args.seconds, 600, keep_load=False)
        picked = check.sample(served.timed, srv["prompt_buckets"], seed,
                              mix["check_tokens"])
        controls = ("int8", "fp8") if i < args.controls else ()
        t = time.perf_counter()
        got = check.compare(Reference(cell.config, params, cell.arch), picked,
                            controls)
        got.update(seed=seed, reference_s=time.perf_counter() - t,
                   failed=serve.attempted_failed(served)[1])
        rows.append(got)
        print(json.dumps(got), flush=True)
    prog = [r["gap_max"] for r in rows]
    print(f"program gap_max over {len(prog)} seeds: max {max(prog)!r}",
          file=sys.stderr)
    for c in ("int8", "fp8"):
        got = [r[f"gap_max.{c}"] for r in rows if f"gap_max.{c}" in r]
        if got:
            print(f"control {c}: min {min(got)!r} over {len(got)} seeds",
                  file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
