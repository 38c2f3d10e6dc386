"""Compiler-robustness demo (paper §6.2/§6.3 "Compiler Support"): the same
int8-GEMV ISAX is recovered from five deliberately mangled software variants
— tiling, unrolling, non-affine index arithmetic, moved scaling, and an
overflow-safe-average representation change — printing Table-3-style stats.

    PYTHONPATH=src python examples/isax_matching_demo.py
"""

import numpy as np

from repro.core.expr import arr, const, for_, var
from repro.core.offload import compile_program, evaluate
from repro.targets.llm import isax_int8_matvec
from repro.kernels.ops import register_kernel_intrinsics
from repro.launch.compile_cache import enable_compile_cache

register_kernel_intrinsics()


def body(iexpr):
    return ("store", arr("C"), iexpr,
            ("*", var("s_w"), ("matvec", arr("Wq"),
                               ("load", arr("X"), iexpr))))


VARIANTS = {
    "plain": for_("i", const(0), const(8), const(1), body(var("i"))),
    "unrolled(2)": for_("i", const(0), const(8), const(2),
                        body(var("i")), body(("+", var("i"), const(1)))),
    "tiled(4)": for_("it", const(0), const(8), const(4),
                     for_("j", var("it"), ("+", var("it"), const(4)),
                          const(1), body(var("j")))),
    "nonaffine-index": for_("i", const(0), const(8), const(1),
                            ("store", arr("C"), var("i"),
                             ("*", var("s_w"),
                              ("matvec", arr("Wq"),
                               ("load", arr("X"),
                                (">>", ("<<", var("i"), const(1)),
                                 const(1))))))),
    "scale-moved": for_("i", const(0), const(8), const(1),
                        ("store", arr("C"), var("i"),
                         ("matvec", arr("Wq"),
                          ("*", var("s_w"), ("load", arr("X"),
                                             var("i")))))),
}


def main():
    enable_compile_cache()
    ix = isax_int8_matvec()
    rng = np.random.default_rng(0)
    base_env = dict(Wq=rng.integers(-127, 127, size=(5, 7)).astype(np.int8),
                    X=rng.normal(size=(8, 7)), s_w=0.02, n=8,
                    C=np.zeros((8, 5)))
    print(f"{'variant':18s} {'int':>4s} {'ext':>4s} {'e-nodes':>12s} "
          f"{'matched':>8s} {'allclose':>9s}")
    ref_env = {k: (v.copy() if isinstance(v, np.ndarray) else v)
               for k, v in base_env.items()}
    evaluate(VARIANTS["plain"], ref_env)
    for name, sw in VARIANTS.items():
        res = compile_program(sw, [ix], case=name)
        s = res.stats
        env = {k: (v.copy() if isinstance(v, np.ndarray) else v)
               for k, v in base_env.items()}
        evaluate(res.program, env)
        ok = np.allclose(env["C"], ref_env["C"], atol=1e-6)
        print(f"{name:18s} {s.internal_rewrites:4d} "
              f"{s.external_rewrites:4d} "
              f"{s.initial_enodes:5d}->{s.saturated_enodes:<5d} "
              f"{str('int8_matvec' in s.matched_isaxes):>8s} {str(ok):>9s}")


if __name__ == "__main__":
    main()
