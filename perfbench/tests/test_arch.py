"""Architectures enter the benchmark as files: a module per ``model_type``
under ``perfbench/arch/``, found in the checkout a run is given; and the
generic parts those modules build on."""

import pathlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import bench, serve
from perfbench import run as harness
from perfbench.reference import Matmul, attention
from perfbench.tests import tiny

#: The files a new architecture adds, relative to ``perfbench/``.
ADDED = {"arch/toy.py", "configs/tiny.json", "traffic/test.json",
         "checks/tiny.test.json"}
TOY = '''"""A toy architecture: the Llama layout under a name of its own."""

from perfbench.arch.llama import (  # noqa: F401
    decode_flops, flash_calls, prefill_flops, program_config,
    reference_logits, weight_shapes, weight_std)
'''


def _args(seed=2 ** 31 + 13):
    return harness.parse(["--workload", "tiny.test", "--seed", str(seed),
                          "--seconds", "2", "--trace", "0"])


def _files(root: pathlib.Path) -> set:
    return {str(p.relative_to(root)) for p in root.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_new_model_type_enters_by_files_alone(tmp_path):
    root = tiny.make_root(tmp_path, config=dict(tiny.CONFIG,
                                                model_type="toy"))
    (root / "perfbench" / "arch" / "toy.py").write_text(TOY)
    cell = bench.load_cell("tiny.test", root)
    assert pathlib.Path(cell.arch.__file__) == (
        root / "perfbench" / "arch" / "toy.py")
    res = harness.run(_args(), require_device=False, root=root)
    assert res["correct"] is True, res["checks"]
    # beside the added files, the copy is the repository's benchmark
    copy, repo = root / "perfbench", tiny.REPO / "perfbench"
    kept = _files(copy) - ADDED
    assert kept == _files(repo) - {f for f in _files(repo)
                                   if f.startswith("tests/")}
    for f in kept:
        assert (copy / f).read_bytes() == (repo / f).read_bytes(), f


def test_unknown_model_type_names_the_module_to_add(tmp_path):
    root = tiny.make_root(tmp_path, config=dict(tiny.CONFIG,
                                                model_type="nosuch"))
    with pytest.raises(SystemExit, match=r"add perfbench/arch/nosuch\.py"):
        bench.load_cell("tiny.test", root)


def test_engine_state_is_freed_and_weights_kept(tmp_path):
    """After a run's engine is freed, its page pools and decode state are
    deleted on the device, and the weights the reference reads are not."""
    cell = bench.load_cell("tiny.test", tiny.make_root(tmp_path))
    params, eng = harness.build(cell, 5)
    serve.warm_up(eng, [32], tiny.CONFIG["vocab_size"], 5)
    pools = [eng.cache.k_pages, eng.cache.v_pages, *eng._device_state]
    harness.free_device_state(eng, keep=params)
    assert all(a.is_deleted() for a in pools)
    leaves = jax.tree.leaves(params)
    assert not any(a.is_deleted() for a in leaves)
    assert {id(a) for a in serve.device_arrays(eng)} <= {id(a)
                                                         for a in leaves}


def test_state_under_any_name_is_freed():
    """State a cache keeps beside its K/V pages, in attributes, dicts and
    tuples under names of its own, goes too."""
    w = jnp.ones((2, 2))
    held = [jnp.zeros(3), jnp.zeros((1, 4)), jnp.ones(2)]
    eng = SimpleNamespace(
        params={"w": w},
        cache=SimpleNamespace(conv_state=held[0],
                              ssm={"layers": [(held[1], 3)]}),
        staged=(held[2], np.zeros(2)), step=lambda: None)
    harness.free_device_state(eng, keep={"w": w})
    assert all(a.is_deleted() for a in held) and not w.is_deleted()


def _qkv(T=512, H=2, K=1, hd=8):
    r = np.random.default_rng(0)
    return [jnp.asarray(r.normal(size=(T, n, hd)), jnp.float32)
            for n in (H, K, K)]


def test_attention_window_covering_the_sequence_is_causal():
    q, k, v = _qkv()
    mm = Matmul()
    assert np.array_equal(attention(q, k, v, 0.3, mm),
                          attention(q, k, v, 0.3, mm, window=512))


def test_attention_window_of_one_sees_only_itself():
    q, k, v = _qkv()
    got = attention(q, k, v, 0.3, Matmul(), window=1)
    np.testing.assert_allclose(got, jnp.repeat(v, 2, axis=1), rtol=1e-6)
    assert not np.allclose(attention(q, k, v, 0.3, Matmul(), window=2), got)
