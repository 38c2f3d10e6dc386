"""Quickstart: the full Aquas-on-TPU pipeline in one script.

1. Hardware side (§4): model the memory interfaces, synthesize a DMA
   schedule, derive Pallas kernel tile shapes.
2. Software side (§5): e-graph-compile a syntactically divergent attention
   loop onto the flash-attention ISAX and execute it.
3. System side: one train step + a short generation on a reduced model.

Run: PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

from repro.core import aquas_ir as ir
from repro.core.expr import arr, const, for_, var
from repro.core.interface_model import paper_example_interfaces, tpu_interfaces
from repro.core.kernel_synth import choose_flash_blocks
from repro.core.offload import compile_program, evaluate
from repro.targets import isax_library
from repro.core.synthesis import synthesize
from repro.kernels.ops import register_kernel_intrinsics
from repro.launch.compile_cache import enable_compile_cache


def hardware_side():
    print("== 1. Interface-aware synthesis (paper §4) ==")
    t = synthesize(ir.FunctionalProgram("fir7", [
        ir.FuncOp("transfer", "src", 108, ir.Space.GLOBAL,
                  ir.Space.SCRATCHPAD, "load", ir.CacheHint.COLD),
        ir.FuncOp("transfer", "bias", 28, ir.Space.GLOBAL,
                  ir.Space.SCRATCHPAD, "load", ir.CacheHint.WARM,
                  scratchpad="bias")],
        {"bias": ir.ScratchpadDecl("bias", 28, ir.CacheHint.WARM,
                                   compute_cycles_per_elem=8.0)}),
        paper_example_interfaces())
    print(f"  fir7 schedule: {t.total_cycles:.0f} cycles; decisions:")
    for k, v in sorted(t.decisions.items()):
        print(f"    {k} = {v}")
    sched = choose_flash_blocks(4096, 4096, 128)
    print(f"  flash-attention tiles (synthesized for TPU): "
          f"{sched.block_shapes}, {sched.buffering}-deep buffering, "
          f"{sched.decisions['bound']}-bound")
    # compute-bound prefill: BlockSpec's implicit double buffering suffices;
    # memory-bound short-query/long-KV: explicit deep staging wins.
    for label, s in (("prefill 4k×4k", sched),
                     ("decode-ish 64×4k", choose_flash_blocks(64, 4096, 64))):
        print(f"  burst-DMA pipeline [{label}]: {s.decisions['pipeline']} "
              f"(est {s.est_serial_cycles:.0f} baseline → "
              f"{s.est_total_cycles:.0f} cycles)")
    print()


def software_side():
    print("== 2. E-graph retargetable compiler (paper §5) ==")
    register_kernel_intrinsics()
    i = var("i")
    q = ("load", arr("Q"), i)
    # deliberately divergent: scale inside matvec, no max-shift softmax
    s = ("/", ("exp", ("matvec", arr("K"), ("*", var("scale"), q))),
         ("rowsum", ("exp", ("matvec", arr("K"), ("*", var("scale"), q)))))
    sw = for_("i", const(0), var("n_q"), const(1),
              ("store", arr("P"), i, s),
              ("store", arr("O"), i,
               ("matvec", ("transpose", arr("V")), ("load", arr("P"), i))))
    res = compile_program(sw, isax_library(), case="quickstart")
    s = res.stats
    print(f"  matched ISAXs: {s.matched_isaxes}")
    print(f"  rewrites: {s.internal_rewrites} internal / "
          f"{s.external_rewrites} external; "
          f"e-nodes {s.initial_enodes} -> {s.saturated_enodes}")
    rng = np.random.default_rng(0)
    nq, nk, d = 8, 16, 32
    env = dict(Q=rng.normal(size=(nq, d)), K=rng.normal(size=(nk, d)),
               V=rng.normal(size=(nk, d)), scale=d ** -0.5, n_q=nq,
               P=np.zeros((nq, nk)), O=np.zeros((nq, d)))
    env2 = {k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in env.items()}
    evaluate(sw, env)
    evaluate(res.program, env2)
    print(f"  offloaded == original: "
          f"{np.allclose(env['O'], env2['O'], atol=1e-6)}\n")


def system_side():
    print("== 3. Train + serve (reduced llama110m) ==")
    import jax.numpy as jnp
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    from repro.optim.adamw import AdamWConfig
    from repro.serve.engine import ServeEngine
    from repro.train.trainer import TrainConfig, Trainer

    cfg = reduced(get_config("llama110m"))
    tr = Trainer(cfg, TrainConfig(batch=4, seq=32, total_steps=5,
                                  optimizer=AdamWConfig(lr=1e-3)))
    last = tr.train(5)
    print(f"  5 train steps, loss: "
          f"{tr.metrics_log[0]['loss']:.3f} -> {last['loss']:.3f}")
    eng = ServeEngine(cfg, params=tr.params, max_len=48, quantize=True)
    toks, stats = eng.generate({"tokens": jnp.ones((2, 8), jnp.int32)}, 6)
    print(f"  generated {toks.shape} tokens, "
          f"TTFT {stats.ttft_s * 1e3:.1f} ms, ITL {stats.itl_s * 1e3:.1f} ms")


if __name__ == "__main__":
    enable_compile_cache()
    hardware_side()
    software_side()
    system_side()
    print("\nquickstart OK")
