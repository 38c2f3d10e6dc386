#!/usr/bin/env python3
"""Find the knee of a cell once: the highest offered rate it sustains.

    python perfbench/sweep.py --workload <cell> --rates 1,2,3,4 --seconds 20

One process on the chip, one seed's weights.  For each rate it offers the
cell's traffic at that rate (ramp, then a window of ``--seconds``), lets the
window's requests finish, and prints the offered and completed output tokens
per second, time to first token in the window's first and second halves
(a backlog that grows shows as a second half far above the first) and the
inter-token percentiles.  The cell's traffic file then fixes its rate at
about four fifths of the knee.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import numpy as np
    from perfbench import bench, serve, traffic
    from perfbench import run as harness

    cell = bench.load_cell(args.workload)
    bench.check_device(cell.chips)
    harness.setup_jax()
    srv = cell.config["serve"]
    params, eng = harness.build(cell, args.seed)
    inst = serve.Instrumented(eng)
    serve.warm_up(eng, srv["prompt_buckets"], cell.config["vocab_size"], 0)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        items = traffic.schedule(mix, args.seconds, args.seed,
                                 cell.config["vocab_size"])
        t0 = time.perf_counter() + 0.05
        s = serve.run_schedule(eng, inst, items, t0, mix["ramp_s"],
                               args.seconds, 300, keep_load=False)
        w0, w1 = s.window
        win = [t for t in s.timed if t.section == "window"]
        half = lambda lo, hi: [t.stamps[0] - t.due for t in win
                               if lo <= t.due < hi and t.stamps]
        mid = (w0 + w1) / 2
        e2e = serve.end_to_end(s)
        row = {
            "rate": rate,
            "offered_tokens_per_s": sum(t.req.max_new_tokens for t in win)
            / args.seconds,
            "ttft_p90_first_half": float(np.percentile(half(w0, mid), 90)),
            "ttft_p90_second_half": float(np.percentile(half(mid, w1), 90)),
            "drain_s": s.stopped - w1,
            **e2e,
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
