#!/usr/bin/env python3
"""The device's idle time in a traced pass of a cell, split by the engine's
own spans.

    python perfbench/engine_split.py --workload <cell> --seed <n> --seconds <s>

``ContinuousEngine`` writes host spans named ``engine.*`` into the
profiler's trace (``repro.serve.telemetry``), which ``tracereduce.Trace``
keeps as ``engine_spans``.  This command serves the cell as ``run.py --trace
1`` does (same engine, warm-up and schedule, the window's last ``trace_s``
seconds traced) but makes no comparison with the reference, and prints one
JSON object: ``engine_idle``, idle seconds by the innermost engine span
active at each instant (``host:harness`` where none is), 20 entries where
``run.py``'s breakdown has 10; ``idle_gaps``, idle seconds by harness span;
and ``step_host_ms``, as ``metrics/step_host_ms.py`` reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import tempfile
import time

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]

from perfbench.tracereduce import Trace, top  # noqa: E402


def traced_pass(workload: str, seed: int, seconds: float, *,
                require_device: bool = True, root=None) -> dict:
    """Serve ``workload`` once with the window's last ``trace_s`` seconds
    traced, and split the device's idle time (see the module docstring)."""
    import jax

    from perfbench import bench, run, serve, traffic
    from perfbench.metrics import step_host_ms
    from perfbench.metrics._common import Observed

    root = root or bench.ROOT
    cell = bench.load_cell(workload, root)
    if require_device:
        bench.check_device(cell.chips, root)
        run.setup_jax()
    mix, srv = cell.traffic, cell.config["serve"]
    _, eng = run.build(cell, seed)
    inst = serve.Instrumented(eng)
    serve.warm_up(eng, traffic.buckets_used(mix, seconds,
                                            srv["prompt_buckets"]),
                  cell.config["vocab_size"], seed)
    inst.reset()
    start = time.perf_counter() + 0.05
    w1 = start + mix["ramp_s"] + seconds
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory(prefix="engine-split-") as trace_dir:
        trace = (lambda: jax.profiler.start_trace(trace_dir,
                                                  profiler_options=opts),
                 jax.profiler.stop_trace,
                 max(start + mix["ramp_s"], w1 - mix["trace_s"]), w1)
        serve.run_schedule(eng, inst, serve.items_for(cell, seconds, seed),
                           start, mix["ramp_s"], seconds, mix["grace_s"],
                           trace=trace)
        tr = Trace.from_dir(trace_dir)
    obs = Observed(cfg=cell.config, peaks=None,
                   names=bench.trace_names(root), served=None, trace=tr)
    return {"engine_idle": top(tr.idle_by_engine_span(), 20),
            "idle_gaps": top(tr.idle_by_host(), 20),
            "step_host_ms": step_host_ms.read(obs)}


def main(argv=None) -> int:
    from perfbench import bench

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    try:
        out = traced_pass(a.workload, a.seed, a.seconds)
    except bench.NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
