"""Point-cloud set abstraction through the full co-design stack.

The paper's second application domain, end to end:

1. Software side (§5): the divergently-spelled FPS / ball-query /
   group-aggregate loops e-graph-compile onto the point-cloud ISAXes
   (expanded squared distance → compact form, neg∘min∘neg → max-pool).
2. Hardware side (§4): the synthesis flow schedules the memory-bound
   gathers — streamed tile shapes plus the burst-DMA pipeline go/no-go.
3. System side: one PointNet++-style set-abstraction stage (sample →
   group → aggregate) runs through the compile-dispatch cache and matches
   the jnp references.

Run: PYTHONPATH=src python examples/pointcloud.py
"""

import numpy as np

from repro.compile import Dispatcher, LoweringConfig
from repro.compile.trace import trace_term
from repro.core.kernel_synth import choose_ball_blocks, choose_group_blocks
from repro.core.offload import compile_program, evaluate
from repro.targets import isax_library
from repro.pointcloud import ref
from repro.pointcloud.ops import register_pointcloud_intrinsics
from repro.launch.compile_cache import enable_compile_cache


def software_side():
    print("== 1. E-graph compilation of the point-cloud loops (§5) ==")
    register_pointcloud_intrinsics()
    for kind, want in (("fps", "fps"), ("ball_query", "ball_query"),
                       ("group_aggregate", "group_agg")):
        res = compile_program(trace_term(kind), isax_library(), case=kind)
        s = res.stats
        print(f"  {kind:16s} matched={s.matched_isaxes} "
              f"(int={s.internal_rewrites} rewrites, "
              f"e-nodes {s.initial_enodes} -> {s.saturated_enodes})")

    # offloaded fps program == reference program (numpy evaluator)
    rng = np.random.default_rng(0)
    n, n_s = 64, 8
    X = rng.normal(size=(n, 3))
    env = dict(Xp=X, n_s=n_s, Dp=np.full((1, n), 1e30),
               Sp=np.zeros(n_s, np.int64))
    env2 = {k: (v.copy() if isinstance(v, np.ndarray) else v)
            for k, v in env.items()}
    res = compile_program(trace_term("fps"), isax_library(), case="fps")
    evaluate(trace_term("fps"), env)
    evaluate(res.program, env2)
    print(f"  offloaded fps == original: "
          f"{bool((env['Sp'] == env2['Sp']).all())}\n")


def hardware_side():
    print("== 2. Synthesis schedules for the gather/scatter shapes (§4) ==")
    for label, sched in (
            ("ball_query 256c/4096pts/k16", choose_ball_blocks(256, 4096, 16)),
            ("group_agg 64c/4096pts/k8/c64", choose_group_blocks(64, 4096, 8, 64)),
            ("group_agg 512c/512pts/k64/c256 (compute-bound)",
             choose_group_blocks(512, 512, 64, 256))):
        print(f"  {label}: tiles={sched.block_shapes} "
              f"burst={sched.decisions['pipeline']}")
    print()


def system_side():
    print("== 3. Set abstraction through compile dispatch ==")
    import jax.numpy as jnp
    rng = np.random.default_rng(1)
    B, N, M, K, C = 1, 128, 32, 8, 16
    xyz = jnp.asarray(rng.normal(size=(B, N, 3)), jnp.float32)
    feats = jnp.asarray(rng.normal(size=(B, N, C)), jnp.float32)

    disp = Dispatcher()
    lw = LoweringConfig.from_registry("pallas_interpret", dispatcher=disp)
    sel = lw.fps(xyz, M)
    centers = jnp.take_along_axis(xyz, sel[..., None], axis=1)
    idx = lw.ball_query(xyz, centers, 1.2, K)
    agg = lw.group_aggregate(feats, idx)

    ok = (bool((np.asarray(sel) == np.asarray(ref.fps_ref(xyz, M))).all())
          and bool((np.asarray(idx)
                    == np.asarray(ref.ball_query_ref(xyz, centers, 1.2,
                                                     K))).all())
          and np.allclose(np.asarray(agg),
                          np.asarray(ref.group_aggregate_ref(feats, idx))))
    print(f"  sample({M}) -> group(k={K}) -> aggregate({C}ch): "
          f"parity={'OK' if ok else 'FAIL'}")
    for rec in disp.records.values():
        sched = rec.schedule or {}
        print(f"  {rec.key.op:16s} impl={rec.impl} "
              f"burst_pipeline={sched.get('pipelined', False)} "
              f"(gain={sched.get('pipeline_gain', 1.0):.2f}x)")
    assert ok, "point-cloud dispatch parity failed"


if __name__ == "__main__":
    enable_compile_cache()
    software_side()
    hardware_side()
    system_side()
    print("\npointcloud example OK")
