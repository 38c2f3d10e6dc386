"""``chip_smoke.py`` on the CPU: its phases at reduced width through the
Pallas interpreter, and its refusal to run without a TPU."""

import importlib.util
import pathlib
import sys

import pytest

from repro.compile import Dispatcher, LoweringConfig
from repro.configs.base import reduced
from repro.configs.registry import get_config

SCRIPT = pathlib.Path(__file__).resolve().parent.parent / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod     # dataclasses resolve it by name
    spec.loader.exec_module(mod)
    return mod


def test_phases_pass_at_reduced_width(smoke):
    cfg = reduced(get_config("llama110m"))
    plan = smoke.Plan(max_batch=4, max_len=128, buckets=(16, 32, 64),
                      n_requests=6, prompt_lens=(5, 60), out_lens=(2, 8))
    eng, _ = smoke.build_engine(
        cfg, plan, LoweringConfig.from_registry(
            "pallas_interpret", dispatcher=Dispatcher()))
    smoke.warm_up(eng, plan)
    reqs = smoke.workload(plan, cfg.vocab)
    eng.run(reqs)
    smoke.check_requests(eng, reqs)

    lines = smoke.dispatch_lines(eng, plan)
    # single-row decode attention stays on the reference, as a recorded
    # decision the script prints
    assert any("op=attention_paged" in ln and "impl=reference" in ln
               and "degenerate query tile" in ln for ln in lines)

    ref, _ = smoke.build_engine(cfg, plan,
                                LoweringConfig.from_registry("xla"),
                                params=eng.params)
    rows = smoke.compare_logits(eng, ref, plan)
    assert [(phase, b) for phase, b, _ in rows] == [
        (phase, b) for b in plan.buckets for phase in ("prefill", "decode")]
    # float32 on the CPU: both lowerings agree far inside the chip tolerance
    assert max(err for _, _, err in rows) < 1e-4
    eng.cache.allocator.check_leaks()


def test_dispatch_check_fails_without_kernels(smoke):
    """On the XLA lowering no bucket reaches an ISAX kernel, so the check
    that the kernels ran must fail."""
    cfg = reduced(get_config("llama110m"))
    plan = smoke.Plan(max_batch=2, max_len=64, buckets=(16,))
    eng, _ = smoke.build_engine(
        cfg, plan, LoweringConfig.from_registry("xla",
                                                dispatcher=Dispatcher()))
    smoke.warm_up(eng, plan)
    with pytest.raises(AssertionError, match="not lowered to isax"):
        smoke.dispatch_lines(eng, plan)


def test_main_exits_nonzero_on_cpu_without_ok_line(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main()
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert "platform=cpu" in out
    assert '"ok"' not in out
