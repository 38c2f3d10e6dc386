"""Perf hillclimb harness: lower a (arch × shape) cell under a named variant
and report its roofline terms — the §Perf iteration loop of EXPERIMENTS.md.

    python -m repro.launch.perf --arch qwen1.5-0.5b --shape train_4k \
        --variant dp_only

Variants:
    baseline      — the paper-faithful fsdp_tp policy (same as dryrun)
    dp_only       — pure 256-way DP (params replicated, batch on both axes)
    fsdp_2d       — params sharded over both mesh axes
    bf16_logits   — logits/loss in bf16 (halves the unembed traffic)
    int8_decode   — int8 weights inside the decode step (halves HBM bytes)
    noremat       — remat off (memory↔compute trade)
    int8_allgather— shard_map DP gradient sync with int8 wire payload
"""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("REPRO_EXTRA_XLA_FLAGS", ""))

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs.base import SHAPES                     # noqa: E402
from repro.configs.registry import get_config             # noqa: E402
from repro.launch.mesh import make_production_mesh        # noqa: E402
from repro.compile import set_default_backend  # noqa: E402
from repro.models import layers as mlayers                # noqa: E402
from repro.models.registry import (get_model, input_specs,  # noqa: E402
                                   param_specs)
from repro.optim.adamw import AdamWConfig, init_state     # noqa: E402
from repro.roofline.analysis import (parse_collectives,   # noqa: E402
                                     roofline)
from repro.sharding.policies import (activation_specs,    # noqa: E402
                                     batch_sharding, cache_shardings,
                                     param_shardings)
from repro.train.trainer import TrainConfig, make_train_step  # noqa: E402


def _quant_specs(pspecs):
    """ShapeDtypeStructs for an int8-quantized param tree."""
    def q(leaf):
        if len(leaf.shape) >= 2:
            return {"q": jax.ShapeDtypeStruct(leaf.shape, jnp.int8),
                    "scale": jax.ShapeDtypeStruct((), jnp.float32)}
        return leaf
    return jax.tree.map(q, pspecs)


def _quant_shardings(p_shard, pspecs, mesh):
    def q(sh, leaf):
        if len(leaf.shape) >= 2:
            return {"q": sh, "scale": NamedSharding(mesh, P())}
        return sh
    return jax.tree.map(q, p_shard, pspecs)


def _dequant(tree):
    def deq(x):
        if isinstance(x, dict) and "q" in x:
            return x["q"].astype(jnp.bfloat16) * x["scale"].astype(jnp.bfloat16)
        return x
    return jax.tree.map(deq, tree,
                        is_leaf=lambda x: isinstance(x, dict) and "q" in x)


def build_variant(arch: str, shape_name: str, mesh, variant: str):
    """``variant`` is a '+'-separated composition, e.g. 'dp_only+noremat'."""
    parts = set(variant.split("+"))
    cfg = get_config(arch)
    if "noremat" in parts:
        cfg = dataclasses.replace(cfg, remat="none")
    if "fullremat" in parts:
        cfg = dataclasses.replace(cfg, remat="full")
    if "dotsremat" in parts:
        cfg = dataclasses.replace(cfg, remat="dots")
    if "bf16_logits" in parts:
        cfg = dataclasses.replace(cfg, logit_dtype="bfloat16")
    if "moe_grouped" in parts and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="grouped"))
    shape = SHAPES[shape_name]
    model = get_model(cfg)
    specs = input_specs(cfg, shape)
    pspecs = param_specs(cfg)
    policy = next((p for p in ("dp_only", "fsdp_2d") if p in parts),
                  "fsdp_tp")
    if "flash" in parts:
        # the flash-attention ISAX path (online-softmax chunked attention)
        set_default_backend("xla_chunked")
    variant = ("int8_decode" if "int8_decode" in parts else variant)
    p_shard = param_shardings(cfg, mesh, model.param_axes(), pspecs, policy)
    mlayers.set_activation_shardings(
        activation_specs(cfg, mesh, shape.global_batch, policy))

    big = cfg.n_params() > 50e9
    opt_cfg = AdamWConfig(state_dtype="bfloat16" if big else "float32")

    if shape.kind == "train" and "pp" in parts:
        # GPipe pipeline-parallel backbone over the 'model' axis (16 stages);
        # proves PP lowers/compiles on the production mesh for layer-
        # divisible archs (yi-9b, internlm2: 48 = 16×3).
        mlayers.set_activation_shardings(None)
        from repro.models import transformer as T
        from repro.sharding.pipeline import gpipe
        n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        assert cfg.n_layers % n_stages == 0, (cfg.n_layers, n_stages)
        B, S = shape.global_batch, shape.seq_len
        n_micro = 16
        mb = B // n_micro
        mask = None  # built inside stage_fn (constant-folded)

        def stage_fn(bp, x):
            msk = jnp.tril(jnp.ones((S, S), bool))[None]
            pos = jnp.broadcast_to(jnp.arange(S)[None], (x.shape[0], S))

            def body(h, p):
                h2, _, _ = T.block_fwd(cfg, h, p, msk, pos)
                return h2, None

            h, _ = jax.lax.scan(body, x, bp)
            return h

        pipelined = gpipe(stage_fn, mesh, stage_axis="model",
                          data_axes=("data",))

        def fwd(blocks, x_micro):
            return pipelined(blocks, x_micro)

        bspecs = jax.eval_shape(
            lambda key: jax.vmap(lambda k: __import__(
                "repro.models.transformer", fromlist=["init_block"]
            ).init_block(cfg, k))(jax.random.split(key, cfg.n_layers)),
            jax.random.key(0))
        blk_shard = jax.tree.map(
            lambda l: NamedSharding(mesh, P(*(("model",)
                                              + (None,) * (len(l.shape) - 1)))),
            bspecs)
        x_specs = jax.ShapeDtypeStruct(
            (n_micro, mb, S, cfg.d_model),
            mlayers.dtype_of(cfg.compute_dtype))
        x_shard = NamedSharding(mesh, P(None, "data", None, None))
        with jax.set_mesh(mesh):
            jitted = jax.jit(fwd, in_shardings=(blk_shard, x_shard))
            return cfg, jitted.lower(bspecs, x_specs)

    if shape.kind == "train" and "int8_wire" in parts:
        # shard_map DP step with true int8 gradient wire (replicated params).
        # Inside shard_map everything is device-local — activation sharding
        # constraints (Auto-axis) are meaningless and must be off.
        mlayers.set_activation_shardings(None)
        from repro.optim.wire_compression import (init_err_state,
                                                  make_int8_wire_train_step)
        from repro.sharding.policies import dp_axes as _dpa
        dp = _dpa(mesh, "dp_only")
        step = make_int8_wire_train_step(model, opt_cfg, mesh, dp)
        opt_specs = jax.eval_shape(lambda p: init_state(p, opt_cfg), pspecs)
        err_specs = jax.eval_shape(init_err_state, pspecs)
        rep = NamedSharding(mesh, P())
        p_rep = jax.tree.map(lambda _: rep, p_shard)
        o_rep = jax.tree.map(lambda _: rep, opt_specs)
        b_shard = batch_sharding(cfg, mesh, specs["batch"], "dp_only")
        with jax.set_mesh(mesh):
            jitted = jax.jit(step,
                             in_shardings=(p_rep, o_rep, rep, b_shard),
                             donate_argnums=(0, 1, 2))
            return cfg, jitted.lower(pspecs, opt_specs, err_specs,
                                     specs["batch"])

    if shape.kind == "train":
        opt_specs = jax.eval_shape(lambda p: init_state(p, opt_cfg), pspecs)
        opt_shard = {"step": NamedSharding(mesh, P()), "m": p_shard,
                     "v": p_shard}
        tc = TrainConfig(total_steps=10_000, warmup=100, optimizer=opt_cfg)
        step = make_train_step(model, opt_cfg, tc)
        b_shard = batch_sharding(cfg, mesh, specs["batch"], policy)
        with jax.set_mesh(mesh):
            jitted = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                             donate_argnums=(0, 1))
            return cfg, jitted.lower(pspecs, opt_specs, specs["batch"])

    if shape.kind == "prefill":
        b_shard = batch_sharding(cfg, mesh, specs["batch"], policy)
        with jax.set_mesh(mesh):
            jitted = jax.jit(lambda p, b: model.prefill(p, b),
                             in_shardings=(p_shard, b_shard))
            return cfg, jitted.lower(pspecs, specs["batch"])

    tok_shard = batch_sharding(cfg, mesh, {"t": specs["token"]}, policy)["t"]
    c_shard = cache_shardings(cfg, mesh, specs["caches"], policy=policy)
    pos_shard = NamedSharding(mesh, P())

    if variant == "int8_decode":
        qspecs = _quant_specs(pspecs)
        q_shard = _quant_shardings(p_shard, pspecs, mesh)

        def serve_step(qparams, token, caches, pos):
            return model.decode_step(_dequant(qparams), token, caches, pos)

        with jax.set_mesh(mesh):
            jitted = jax.jit(serve_step,
                             in_shardings=(q_shard, tok_shard, c_shard,
                                           pos_shard),
                             donate_argnums=(2,))
            return cfg, jitted.lower(qspecs, specs["token"], specs["caches"],
                                     specs["pos"])

    if "int8_kv" in parts and "k" in specs["caches"]:
        # int8 KV cache: halves the dominant decode HBM traffic.  Per-
        # (layer, kv-head) scales; dequant on read, requant on write.
        cs = specs["caches"]
        Lk, Bk, Tk, Kk, hdk = cs["k"].shape
        q_caches = dict(cs)
        q_caches["k"] = jax.ShapeDtypeStruct(cs["k"].shape, jnp.int8)
        q_caches["v"] = jax.ShapeDtypeStruct(cs["v"].shape, jnp.int8)
        q_caches["k_scale"] = jax.ShapeDtypeStruct((Lk, Kk), jnp.float32)
        q_caches["v_scale"] = jax.ShapeDtypeStruct((Lk, Kk), jnp.float32)
        qc_shard = dict(c_shard)
        qc_shard["k_scale"] = NamedSharding(mesh, P())
        qc_shard["v_scale"] = NamedSharding(mesh, P())

        def serve_step(params, token, qcaches, pos):
            sk = qcaches["k_scale"][:, None, None, :, None]
            sv = qcaches["v_scale"][:, None, None, :, None]
            caches = {k2: v2 for k2, v2 in qcaches.items()
                      if k2 not in ("k", "v", "k_scale", "v_scale")}
            caches["k"] = qcaches["k"].astype(jnp.bfloat16) * sk.astype(
                jnp.bfloat16)
            caches["v"] = qcaches["v"].astype(jnp.bfloat16) * sv.astype(
                jnp.bfloat16)
            logits, new = model.decode_step(params, token, caches, pos)
            out = dict(qcaches)
            out["k"] = jnp.clip(jnp.round(new["k"].astype(jnp.float32)
                                          / sk), -127, 127).astype(jnp.int8)
            out["v"] = jnp.clip(jnp.round(new["v"].astype(jnp.float32)
                                          / sv), -127, 127).astype(jnp.int8)
            return logits, out

        with jax.set_mesh(mesh):
            jitted = jax.jit(serve_step,
                             in_shardings=(p_shard, tok_shard, qc_shard,
                                           pos_shard),
                             donate_argnums=(2,))
            return cfg, jitted.lower(pspecs, specs["token"], q_caches,
                                     specs["pos"])

    def serve_step(params, token, caches, pos):
        return model.decode_step(params, token, caches, pos)

    with jax.set_mesh(mesh):
        jitted = jax.jit(serve_step,
                         in_shardings=(p_shard, tok_shard, c_shard,
                                       pos_shard),
                         donate_argnums=(2,))
        return cfg, jitted.lower(pspecs, specs["token"], specs["caches"],
                                 specs["pos"])


def run_variant(arch: str, shape_name: str, variant: str,
                out_dir: str = "runs/perf", multi_pod: bool = False) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    cell = f"{arch}__{shape_name}__{mesh_name}__{variant}"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "ok": False}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        try:
            cfg, lowered = build_variant(arch, shape_name, mesh, variant)
        finally:
            mlayers.set_activation_shardings(None)
            set_default_backend("xla")
        compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        loop_trip = cfg.n_layers if cfg.family != "hybrid" else 1
        coll = parse_collectives(hlo, n_chips, loop_trip=loop_trip)
        flops_dev = float(cost.get("flops", 0.0))
        bytes_dev = float(cost.get("bytes accessed", 0.0))
        rl = roofline(flops_dev * n_chips, bytes_dev * n_chips,
                      coll.wire_bytes_per_chip, n_chips)
        rec["roofline"] = rl.row()
        rec["collectives"] = {"counts": coll.counts,
                              "result_bytes": coll.result_bytes,
                              "wire_bytes_per_chip":
                                  coll.wire_bytes_per_chip}
        try:
            m = compiled.memory_analysis()
            rec["memory"] = {
                "argument_size_in_bytes": int(m.argument_size_in_bytes),
                "temp_size_in_bytes": int(m.temp_size_in_bytes)}
        except Exception:
            pass
        rec["ok"] = True
    except Exception as e:
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-3000:]
    rec["total_s"] = time.time() - t0
    with open(os.path.join(out_dir, cell + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="runs/perf")
    args = ap.parse_args()
    rec = run_variant(args.arch, args.shape, args.variant, args.out,
                      args.multi_pod)
    rl = rec.get("roofline", {})
    print(json.dumps({k: rec.get(k) for k in ("arch", "shape", "variant",
                                              "ok", "error")}, indent=1))
    if rl:
        print(f"compute={rl['compute_s']:.4f}s memory={rl['memory_s']:.4f}s "
              f"collective={rl['collective_s']:.4f}s "
              f"bottleneck={rl['bottleneck']}")


if __name__ == "__main__":
    main()
