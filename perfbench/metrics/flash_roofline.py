"""Kernels: share of its roofline that the prefill's flash attention kernel
reaches.  Per prefill, the least time of its attention calls (the
architecture's ``flash_calls`` at the bucket's shape, each call's causal
operations over peak or q/k/v/o bytes over HBM bandwidth, whichever is
larger) over the kernel's device time per prefill program execution.  Alike
calls are counted together: a model whose L layers make alike calls reads L
times one call's least time."""

from collections import Counter

from perfbench import flops


def read(obs):
    if obs.trace is None or not obs.calls.prefill:
        return None
    kernel = obs.trace.matching(obs.names["flash_kernel"])
    runs = obs.trace.matching(obs.names["prefill_program"], "modules")
    if not kernel or not runs:
        return None
    device = sum(e[2] for e in kernel) / 1e9 / len(runs)
    least = sum(
        sum(k * flops.least_time(*call, obs.peaks) for call, k in
            Counter(obs.arch.flash_calls(obs.cfg, bucket)).items())
        for _, bucket, _ in obs.calls.prefill) / len(obs.calls.prefill)
    return 100.0 * least / device
