#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process finds.

    python perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, traffic mix and
limits are files found by the names in ``BENCHMARK.json`` (see
``perfbench/bench.py``).  One run:

1. refuses a machine without an accelerator of a kind in ``peaks.json``, or
   with fewer chips than the cell asks for: exit code 3, no result line;
2. makes the weights on the device from the seed in one jitted call, builds
   ``ContinuousEngine`` at the configuration's sizes and warms the prefill
   buckets the traffic uses and the decode step (JAX's persistent compile
   cache sits in ``.jax_cache/`` of the checkout, so only a checkout's first
   run compiles);
3. offers the seeded open-loop traffic: a ramp, then the measured window of
   ``--seconds``, then load that goes on while the window's requests drain
   (``perfbench/serve.py``); set-up ends when the window opens;
4. reads the peak device memory, frees every array the engine holds on the
   device but the weights, and compares a seeded sample of the served tokens
   with the plain float32 reference (``perfbench/check.py``);
   With ``--control int8|fp8`` the control takes the program's place in that
   comparison (the tokens it puts first are judged instead of the served
   ones), which a sound limit must fail; measured runs never pass it;
5. prints the numbers compared beside their limits as the last lines of
   standard error, and as the last line of standard output one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
   ``--trace 1`` also ``breakdown``, and last ``checks``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler records the last ``trace_s`` seconds of the
window and the metrics are the cell's per-layer metrics.
"""

from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
# Run as a script, this file's directory would shadow modules of the standard
# library; import the benchmark as a package and the program from src/.
sys.path[0:1] = [str(_ROOT), str(_ROOT / "src")]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8", "fp8"), default=None,
                    help="put the control in the program's place in the "
                    "comparison: the served prompts and tokens are judged by "
                    "the tokens the reference in this precision puts first "
                    "(never in a measured run; see perfbench/check.py)")
    return ap.parse_args(argv)


def setup_jax() -> str:
    """Turn on the persistent compile cache for every program, small ones
    included, so that a run after the first compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def build(cell, seed: int):
    """Weights from the seed and an engine over them."""
    import jax
    from perfbench import weights
    from repro.compile import LoweringConfig
    from repro.serve.engine import ContinuousEngine

    srv = cell.config["serve"]
    params = weights.make(cell.config, seed, cell.arch)
    jax.block_until_ready(params)
    eng = ContinuousEngine(
        cell.arch.program_config(cell.config), params,
        max_batch=srv["max_batch"], page_size=srv["page_size"],
        max_len=srv["max_len"], n_pages=srv.get("n_pages"),
        prompt_buckets=tuple(srv["prompt_buckets"]),
        lowering=LoweringConfig.from_registry())
    return params, eng


def memory_peak() -> int:
    """Peak bytes in use on the fullest device, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def free_device_state(eng, keep) -> None:
    """Delete every device array the engine holds, whatever holds it (page
    pools, recurrent state, staged inputs), except the leaves of ``keep``."""
    import jax
    from perfbench import serve

    kept = {id(leaf) for leaf in jax.tree.leaves(keep)}
    for a in serve.device_arrays(eng):
        if id(a) not in kept:
            a.delete()


def run(args, *, require_device: bool = True, root=None,
        engine_hook=None) -> dict:
    """One run of a cell; returns the result object.  Tests pass
    ``require_device=False`` to drive it on the CPU, and ``engine_hook`` to
    break the engine under test."""
    from perfbench import bench, check, serve, traffic
    from perfbench.metrics._common import Observed
    from perfbench.reference import Reference
    from perfbench.tracereduce import Trace, top

    root = root or bench.ROOT
    cell = bench.load_cell(args.workload, root)
    if require_device:
        stamp, peaks = bench.check_device(cell.chips, root)
    else:
        import jax
        d = jax.devices()
        stamp = {"platform": d[0].platform, "kind": d[0].device_kind,
                 "count": len(d)}
        peaks = None
    bench.log(f"device platform={stamp['platform']} kind={stamp['kind']!r} "
              f"count={stamp['count']}")
    if require_device:
        bench.log(f"compile cache: {setup_jax()}")
    import jax

    mix, srv = cell.traffic, cell.config["serve"]
    buckets = traffic.buckets_used(mix, args.seconds, srv["prompt_buckets"])
    params, eng = build(cell, args.seed)
    if engine_hook is not None:
        engine_hook(eng)
    inst = serve.Instrumented(eng)
    serve.warm_up(eng, buckets, cell.config["vocab_size"], args.seed)
    items = serve.items_for(cell, args.seconds, args.seed)
    counter = serve.CompileCounter()
    inst.reset()

    trace_dir = None
    trace = None
    start = time.perf_counter() + 0.05
    w1 = start + mix["ramp_s"] + args.seconds
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        t_on = max(start + mix["ramp_s"], w1 - mix["trace_s"])
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        trace = (lambda: jax.profiler.start_trace(trace_dir,
                                                  profiler_options=opts),
                 jax.profiler.stop_trace, t_on, w1)
    served = serve.run_schedule(eng, inst, items, start, mix["ramp_s"],
                                args.seconds, mix["grace_s"], counter=counter,
                                trace=trace)
    setup_s = served.window[0] - PROCESS_START
    attempted, failed = serve.attempted_failed(served)
    bench.log(f"generator lateness: p50={float(_q(served.lag, 50))!r} s "
              f"max={float(served.lag.max())!r} s over {len(served.lag)} "
              f"requests")
    bench.log(f"compilations inside the window: {served.compiles}")
    bench.log(f"requests due in the window: {attempted}, not done: {failed}")

    metrics = {}
    breakdown = None
    device = dict(stamp, memory_peak_bytes=memory_peak())
    if args.trace:
        tr = Trace.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        on, off = served.trace_window
        calls = serve.Calls(
            prefill=[c for c in served.calls.prefill if on <= c[0] <= off],
            decode=[c for c in served.calls.decode if on <= c[0] <= off])
        obs = Observed(cfg=cell.config, peaks=peaks,
                       names=bench.trace_names(root), served=served,
                       trace=tr, calls=calls, trace_window=(on, off),
                       arch=cell.arch)
        for m in cell.per_layer:
            v = bench.metric_reader(m["name"])(obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s())
        breakdown = {"device_ops": top(tr.self_time_by_name()),
                     "idle_gaps": top(tr.idle_by_host()),
                     "engine_idle": top(tr.idle_by_engine_span())}
    else:
        e2e = serve.end_to_end(served)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    picked = check.sample(served.timed, srv["prompt_buckets"], args.seed,
                          mix["check_tokens"])
    # The program's state goes before the reference runs; the weights were
    # made by the benchmark and stay.
    free_device_state(eng, keep=params)
    del eng, inst
    t_ref = time.perf_counter()
    controls = (args.control,) if args.control else ()
    got = check.compare(Reference(cell.config, params, cell.arch), picked,
                        controls)
    bench.log(f"reference over {got['requests']} requests, {got['tokens']} "
              f"served tokens, took {time.perf_counter() - t_ref:.1f} s")
    if args.control:
        bench.log(f"control {args.control} in the program's place")
    checks = {}
    for name, spec in cell.checks["limits"].items():
        key = f"{name}.{args.control}" if args.control else name
        checks[name] = {"value": got[key], "limit": spec["limit"]}
    correct = bool(picked) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        bench.log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def _q(values, q):
    import numpy as np
    return np.percentile(values, q) if len(values) else float("nan")


def main(argv=None) -> int:
    args = parse(argv)
    from perfbench import bench

    try:
        result = run(args)
    except bench.NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.exit(main())
