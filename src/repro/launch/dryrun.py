"""Multi-pod dry-run: AOT lower + compile every (arch × shape × mesh) cell.

MUST be the process entry point (python -m repro.launch.dryrun ...): the
XLA_FLAGS below are read at first jax init, so they are set before ANY other
import, including repro modules that import jax.
"""

# --- these two lines must run before any other import (jax locks device
# --- count on first init) ---------------------------------------------------
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES, applicable_shapes    # noqa: E402
from repro.configs.registry import ASSIGNED_ARCHS, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh          # noqa: E402
from repro.models.registry import (                         # noqa: E402
    get_model, input_specs, param_specs)
from repro.optim.adamw import AdamWConfig, init_state       # noqa: E402
from repro.roofline.analysis import parse_collectives, roofline  # noqa: E402
from repro.compile import (                                 # noqa: E402
    get_default_backend, set_default_backend)
from repro.models import layers as mlayers                  # noqa: E402
from repro.sharding.policies import (                       # noqa: E402
    activation_specs, batch_sharding, cache_shardings, param_shardings)
from repro.train.trainer import TrainConfig, make_train_step  # noqa: E402


def _opt_cfg(cfg) -> AdamWConfig:
    big = cfg.n_params() > 50e9
    return AdamWConfig(state_dtype="bfloat16" if big else "float32")


def select_policy(cfg, mesh, kind: str, long_context: bool = False) -> str:
    """Arch/phase-aware sharding policy (EXPERIMENTS.md §Perf):

    GQA head_dim TP is a *win* for training when q-heads divide the model
    axis but kv-heads don't (GSPMD gathers the small KV instead of partial-
    summing scores: granite/yi/internlm, +17%); it is a *catastrophe* when
    q-heads don't divide either (arctic: 3×60 GB score all-reduces) and for
    decode (sequence-parallel caches are 33× better)."""
    model_size = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    if (kind == "decode" and cfg.family not in ("encdec", "hybrid")
            and not long_context):
        # contraction-dim 2-D weight sharding beats FSDP gathers at decode
        # (measured up to 29× incl. seq-parallel KV on internlm/yi, 2.8–3×
        # on paligemma/mamba2/dbrx); encdec, hybrid, and long-context SP
        # cells regress under it (0.4–0.96×) and keep fsdp_tp —
        # EXPERIMENTS.md §Perf addendum.
        return "serve"
    if (kind == "train" and cfg.n_heads and cfg.n_kv_heads
            and cfg.n_heads % model_size == 0
            and cfg.n_kv_heads % model_size != 0):
        return "fsdp_tp_hd"
    return "fsdp_tp"


def build_lowered(arch: str, shape_name: str, mesh,
                  act_sharding: bool = True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    specs = input_specs(cfg, shape)
    pspecs = param_specs(cfg)
    policy = select_policy(cfg, mesh, shape.kind,
                           long_context=shape.name == "long_500k")
    p_shard = param_shardings(cfg, mesh, model.param_axes(), pspecs, policy)
    mlayers.set_activation_shardings(
        activation_specs(cfg, mesh, shape.global_batch)
        if act_sharding else None)
    # (decode under xla_chunked needs no special-casing here anymore: the
    # dispatcher lowers single-row-query attention to the XLA reference,
    # which also avoids the sequence-parallel KV reshape-gather pathology —
    # §Perf granite decode iteration 4.)

    if shape.kind == "train":
        opt_cfg = _opt_cfg(cfg)
        opt_specs = jax.eval_shape(lambda p: init_state(p, opt_cfg), pspecs)
        opt_shard = {
            "step": NamedSharding(mesh, P()),
            "m": p_shard, "v": p_shard,
        }
        if "err" in opt_specs:
            opt_shard["err"] = p_shard
        tc = TrainConfig(total_steps=10_000, warmup=100, optimizer=opt_cfg)
        step = make_train_step(model, opt_cfg, tc)
        b_shard = batch_sharding(cfg, mesh, specs["batch"])
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                             donate_argnums=(0, 1))
            return jitted.lower(pspecs, opt_specs, specs["batch"])

    if shape.kind == "prefill":
        b_shard = batch_sharding(cfg, mesh, specs["batch"])

        def prefill_fn(params, batch):
            return model.prefill(params, batch)

        with jax.set_mesh(mesh):
            jitted = jax.jit(prefill_fn, in_shardings=(p_shard, b_shard))
            return jitted.lower(pspecs, specs["batch"])

    # decode / serve_step
    tok_shard = batch_sharding(cfg, mesh, {"t": specs["token"]})["t"]
    c_shard = cache_shardings(cfg, mesh, specs["caches"])
    pos_shard = NamedSharding(mesh, P())

    def serve_step(params, token, caches, pos):
        return model.decode_step(params, token, caches, pos)

    with jax.set_mesh(mesh):
        jitted = jax.jit(serve_step,
                         in_shardings=(p_shard, tok_shard, c_shard,
                                       pos_shard),
                         donate_argnums=(2,))
        return jitted.lower(pspecs, specs["token"], specs["caches"],
                            specs["pos"])


def _mem_dict(compiled):
    try:
        m = compiled.memory_analysis()
        return {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes") if hasattr(m, k)}
    except Exception as e:  # CPU backend may not implement it
        return {"error": str(e)}


def _model_flops(cfg, shape) -> float:
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
             skip_existing: bool = True) -> dict:
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell_id = f"{arch}__{shape_name}__{mesh_name}"
    out_path = os.path.join(out_dir, cell_id + ".json")
    if skip_existing and os.path.exists(out_path):
        with open(out_path) as f:
            return json.load(f)
    os.makedirs(out_dir, exist_ok=True)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_chips = 512 if multi_pod else 256
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": n_chips, "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        prior_impl = get_default_backend()
        try:
            lowered = build_lowered(arch, shape_name, mesh)
        finally:
            mlayers.set_activation_shardings(None)
            set_default_backend(prior_impl)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        cost = compiled.cost_analysis() or {}
        rec["cost"] = {k: float(v) for k, v in cost.items()
                       if isinstance(v, (int, float)) and (
                           k in ("flops", "bytes accessed", "transcendentals")
                           or k.startswith("bytes accessed"))}
        rec["memory"] = _mem_dict(compiled)
        hlo = compiled.as_text()
        # collectives inside the layer-scan while body execute n_layers times
        loop_trip = cfg.n_layers if cfg.family != "hybrid" else 1
        coll = parse_collectives(hlo, n_chips, loop_trip=loop_trip)
        rec["collectives"] = {
            "counts": coll.counts,
            "in_loop": coll.in_loop_counts,
            "result_bytes": coll.result_bytes,
            "wire_bytes_per_chip": coll.wire_bytes_per_chip,
        }
        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        rl = roofline(flops_dev * n_chips, bytes_dev * n_chips,
                      coll.wire_bytes_per_chip, n_chips,
                      model_flops=_model_flops(cfg, shape))
        rec["roofline"] = rl.row()
        rec["lower_s"] = t1 - t0
        rec["compile_s"] = t2 - t1
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["total_s"] = time.time() - t0
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--attn-impl", default="xla",
                    choices=["xla", "xla_chunked"],
                    help="xla_chunked = flash-style online-softmax attention")
    args = ap.parse_args()
    set_default_backend(args.attn_impl)

    archs = ASSIGNED_ARCHS if args.arch == "all" else [args.arch]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in applicable_shapes(cfg)]
                  if args.shape == "all" else [args.shape])
        for shape_name in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape_name, mp, args.out,
                               skip_existing=not args.force)
                tag = "OK " if rec["ok"] else "FAIL"
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
                rl = rec.get("roofline", {})
                print(f"[{tag}] {arch} {shape_name} "
                      f"{'2x16x16' if mp else '16x16'} "
                      f"compile={rec.get('compile_s', 0):.1f}s "
                      f"bottleneck={rl.get('bottleneck', '-')}"
                      + ("" if rec["ok"] else
                         f"  err={rec.get('error', '')[:120]}"),
                      flush=True)
    print(f"done: {n_ok} ok, {n_fail} failed")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
