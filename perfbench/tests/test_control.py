"""The control of the correctness check, at a size a test run holds.

The control is the reference computed through the program's own int8 path
(per-tensor int8 weights, products in bfloat16), one precision below the
configuration's bfloat16, put in the program's place.  On the same prompts
and served tokens it must fail a limit that the program passes.  On the chip
the cells' limits come from the same readings at full size (PERF.md).
"""

import pytest

from perfbench import run as harness
from perfbench.tests import tiny

#: Four layers of width 512 with heads of 128: the smallest size at which
#: int8 rounding stands clear of bfloat16 rounding (CPU readings: program
#: 0-0.04, control 0.095-0.20 over 100 served tokens, seeds 0-4).
CONFIG = dict(tiny.CONFIG, num_hidden_layers=4, hidden_size=512,
              head_dim=128, num_attention_heads=4, num_key_value_heads=2,
              intermediate_size=1536, vocab_size=4096)
TRAFFIC = dict(tiny.TRAFFIC, check_tokens=100)
LIMIT = 0.07
SECONDS = 4


@pytest.mark.parametrize("control", [None, "int8"],
                         ids=["program", "int8_control"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 5, 77])
def test_int8_control_fails_where_program_passes(tmp_path, seed, control):
    """A whole run through ``run.py``'s own comparison: correct with the
    program, not correct with the control in its place."""
    root = tiny.make_root(tmp_path, config=CONFIG, traffic=TRAFFIC,
                          limits={"limits": {"gap_max": {"limit": LIMIT}}})
    argv = ["--workload", "tiny.test", "--seed", str(seed),
            "--seconds", str(SECONDS), "--trace", "0"]
    if control:
        argv += ["--control", control]
    res = harness.run(harness.parse(argv), require_device=False, root=root)
    assert res["correct"] is (control is None), res["checks"]
