"""Paper §4.1/§4.3 fidelity: interface model, canonicalization, synthesis."""

import itertools
import os

import pytest

if os.environ.get("CI", "").lower() not in ("", "0", "false"):
    # CI must run the interface-model properties, never skip them (the
    # workflow installs the dev extra; see tests/test_egraph.py).
    import hypothesis  # noqa: F401
else:
    pytest.importorskip(
        "hypothesis", reason="install the dev extra: pip install -e .[dev]")
from hypothesis import given, settings, strategies as st

from repro.core import aquas_ir as ir
from repro.core.interface_model import (
    MemInterface,
    approx_latency,
    paper_example_interfaces,
    sequence_latency,
    tpu_interfaces,
)
from repro.core.synthesis import (
    elide_scratchpads,
    schedule_transactions,
    select_interfaces,
    synthesize,
)


class TestModel:
    def test_legal_transactions(self):
        bus = paper_example_interfaces()["busitfc"]
        assert bus.is_legal_transaction(64)
        assert bus.is_legal_transaction(4)
        assert not bus.is_legal_transaction(12)   # not a power-of-two beats
        assert not bus.is_legal_transaction(128)  # exceeds M·W
        assert not bus.is_legal_transaction(8, addr=4)  # misaligned

    def test_figure4b_canonicalization(self):
        """Paper Fig. 4(b): a 108-byte request on the system bus decomposes
        into 64-, 32-, 8-, and 4-byte legal transfers."""
        bus = paper_example_interfaces()["busitfc"]
        assert bus.decompose(108) == [64, 32, 8, 4]

    def test_recurrence_single_transaction(self):
        itf = MemInterface("t", W=4, M=1, I=1, L=2, E=1, C=64)
        # a_1 = 1 + max(-1, -1) = 0;  b_1 = 1 + max(-1, 0+2-1) = 2
        assert sequence_latency(itf, [4], "load") == 2
        # store: b_1 = 1 + 1 + max(-1, -1) = 1
        assert sequence_latency(itf, [4], "store") == 1

    def test_recurrence_inflight_pipelining(self):
        """I=2 overlaps two loads; I=1 serializes them."""
        i1 = MemInterface("a", W=4, M=1, I=1, L=6, E=1, C=64)
        i2 = MemInterface("b", W=4, M=1, I=2, L=6, E=1, C=64)
        sizes = [4] * 8
        assert sequence_latency(i2, sizes, "load") < \
            sequence_latency(i1, sizes, "load")

    def test_figure2_suboptimal_gap(self):
        """Paper Fig. 2: improper interface selection costs extra cycles —
        the narrow low-latency port loses to the burst bus on a bulk load."""
        itfcs = paper_example_interfaces()
        cpu, bus = itfcs["cpuitfc"], itfcs["busitfc"]
        m = 108
        lat_cpu = sequence_latency(cpu, cpu.decompose(m), "load")
        lat_bus = sequence_latency(bus, bus.decompose(m), "load")
        assert lat_bus < lat_cpu
        assert lat_cpu - lat_bus >= 7  # paper: "7- to 9-cycle penalty" scale

    @given(st.lists(st.sampled_from([4, 8, 16, 32, 64]), min_size=1,
                    max_size=12),
           st.sampled_from(["load", "store"]))
    @settings(max_examples=50, deadline=None)
    def test_latency_monotone_in_sequence(self, sizes, direction):
        """Adding a transaction never reduces completion time; latency is
        positive; approximation model stays within 3x of the recurrence."""
        bus = paper_example_interfaces()["busitfc"]
        full = sequence_latency(bus, sizes, direction)
        prefix = sequence_latency(bus, sizes[:-1], direction)
        assert full >= prefix
        assert full > 0
        approx = approx_latency(bus, [[s] for s in sizes], direction)
        assert approx <= 3 * full + 10
        assert full <= 3 * approx + 10

    def test_tpu_interfaces_sane(self):
        t = tpu_interfaces()
        assert t["hbm_vmem"].W * t["hbm_vmem"].M >= 512 * 1024  # big bursts
        assert t["vmem_vreg"].L < t["hbm_vmem"].L < t["ici_link"].L


def _fir7_program():
    """The paper's fir7 kernel: src (108B), coef (28B, warm), bias (28B,
    elidable — per-element loads hide behind the MAC chain)."""
    sp = {
        "bias": ir.ScratchpadDecl("bias", 28, ir.CacheHint.WARM,
                                  compute_cycles_per_elem=8.0, elem_bytes=4),
        "coef": ir.ScratchpadDecl("coef", 28, ir.CacheHint.WARM,
                                  reuse_factor=7, elem_bytes=4),
    }
    ops = [
        ir.FuncOp("transfer", "src", 108, ir.Space.GLOBAL,
                  ir.Space.SCRATCHPAD, "load", ir.CacheHint.COLD),
        ir.FuncOp("transfer", "coef", 28, ir.Space.GLOBAL,
                  ir.Space.SCRATCHPAD, "load", ir.CacheHint.WARM,
                  scratchpad="coef"),
        ir.FuncOp("transfer", "bias", 28, ir.Space.GLOBAL,
                  ir.Space.SCRATCHPAD, "load", ir.CacheHint.WARM,
                  scratchpad="bias"),
        ir.FuncOp("read_smem", "bias_rd", 28, ir.Space.SCRATCHPAD,
                  ir.Space.REG, "load", scratchpad="bias"),
        ir.FuncOp("transfer", "dst", 80, ir.Space.REG, ir.Space.GLOBAL,
                  "store", ir.CacheHint.COLD),
    ]
    return ir.FunctionalProgram("fir7", ops, sp)


class TestSynthesis:
    def test_elision_decisions(self):
        """bias elides (latency hidden); coef kept (reuse would thrash)."""
        prog = _fir7_program()
        out, decisions = elide_scratchpads(prog, paper_example_interfaces())
        assert decisions["scratchpad:bias"] == "elided"
        assert decisions["scratchpad:coef"] == "kept"
        assert "bias" not in out.scratchpads
        assert "coef" in out.scratchpads
        kinds = [(o.kind, o.name) for o in out.ops]
        assert ("fetch", "bias_rd") in kinds          # read_smem → fetch
        assert ("transfer", "bias") not in kinds      # staging removed

    def test_elision_respects_legality_guards(self):
        itfcs = paper_example_interfaces()
        sp = ir.ScratchpadDecl("t", 28, accessed_in_unrolled_region=True,
                               compute_cycles_per_elem=100.0)
        prog = ir.FunctionalProgram("p", [
            ir.FuncOp("transfer", "t", 28, ir.Space.GLOBAL,
                      ir.Space.SCRATCHPAD, "load", scratchpad="t")],
            {"t": sp})
        _, decisions = elide_scratchpads(prog, itfcs)
        assert decisions["scratchpad:t"] == "kept"

    def test_interface_selection_routes_bulk_to_bus(self):
        """Paper §4.3: the 108-byte src goes over the high-bandwidth bus."""
        prog, _ = elide_scratchpads(_fir7_program(),
                                    paper_example_interfaces())
        arch = select_interfaces(prog, paper_example_interfaces())
        assert arch.decisions["itfc:src"] == "busitfc"
        src_ops = [o for o in arch.ops if o.name == "src"]
        assert [o.size_bytes for o in src_ops] == [64, 32, 8, 4]

    def test_selection_is_optimal_vs_bruteforce(self):
        """The chosen assignment achieves the brute-force-minimal objective."""
        from repro.core.synthesis import _assign_exact, _objective
        itfcs = list(paper_example_interfaces().values())
        ops = [ir.FuncOp("fetch", f"q{i}", sz, ir.Space.GLOBAL, ir.Space.REG,
                         "load")
               for i, sz in enumerate([4, 28, 64, 108])]
        assign, cost = _assign_exact(ops, itfcs, "load")
        for trial in itertools.product(range(len(itfcs)), repeat=len(ops)):
            assert cost <= _objective(trial, ops, itfcs, "load") + 1e-9

    def test_schedule_beats_naive_order(self):
        """Memoized transaction ordering ≤ any fixed order (paper Fig. 3)."""
        itfcs = paper_example_interfaces()
        prog, _ = elide_scratchpads(_fir7_program(), itfcs)
        arch = select_interfaces(prog, itfcs)
        temporal = schedule_transactions(arch)
        assert temporal.total_cycles > 0
        issues = [o for o in temporal.ops if o.kind == "copy_issue"]
        waits = [o for o in temporal.ops if o.kind == "copy_wait"]
        assert issues and waits
        # after-chains are well-formed: each issue after its predecessor
        ids = {o.op_id for o in temporal.ops}
        for o in temporal.ops:
            assert o.after is None or o.after in ids

    def test_full_pipeline_decisions_logged(self):
        t = synthesize(_fir7_program(), paper_example_interfaces())
        assert "scratchpad:bias" in t.decisions
        assert any(k.startswith("itfc:") for k in t.decisions)
        assert any(k.startswith("order:") for k in t.decisions)

    @given(st.integers(1, 512))
    @settings(max_examples=40, deadline=None)
    def test_decompose_covers_request(self, m):
        """Decomposition covers ≥ m bytes with only legal sizes (property)."""
        for itf in paper_example_interfaces().values():
            chunks = itf.decompose(m)
            assert sum(chunks) >= m
            assert sum(chunks) < m + itf.W
            for c in chunks:
                assert itf.is_legal_transaction(c)


class TestKernelSynth:
    def test_matmul_blocks_fit_and_align(self):
        from repro.core.interface_model import MXU_DIM, TPU_VMEM_BUDGET
        from repro.core.kernel_synth import choose_matmul_blocks
        s = choose_matmul_blocks(4096, 4096, 4096)
        assert s.vmem_bytes <= TPU_VMEM_BUDGET
        assert s.block("b")[1] % MXU_DIM == 0
        # compute-bound GEMM: BlockSpec's implicit double buffering already
        # hides the DMA, so the explicit burst pipeline must not be selected
        assert not s.pipelined
        # memory-bound skinny GEMM: deep burst staging predicted to win
        skinny = choose_matmul_blocks(8, 4096, 8192, dtype_bytes=1)
        assert skinny.pipelined and skinny.buffering > 2

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_blocks_prefer_streaming_kv(self, causal):
        from repro.core.kernel_synth import choose_flash_blocks
        from repro.core.tiling import causal_tiles
        s = choose_flash_blocks(4096, 4096, 128, causal=causal)
        assert s.decisions["kv_hint"] == "cold"
        assert s.decisions["q_hint"] == "warm"
        assert s.vmem_bytes <= 64 * 1024 * 1024
        if causal:  # costed over the tiles at or below the diagonal
            below, crossing, total = causal_tiles(
                4096, 4096, s.block("q")[0], s.block("kv")[0])
            assert s.decisions["tiles_run"] == f"{below + crossing}/{total}"
            assert below + crossing < total

    def test_ssd_blocks(self):
        from repro.core.kernel_synth import choose_ssd_blocks
        s = choose_ssd_blocks(4096, 80, 64, 128)
        assert s.block("chunk")[0] in (128, 256, 512)
