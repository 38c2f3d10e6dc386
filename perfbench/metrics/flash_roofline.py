"""Kernels: share of its roofline that the prefill's flash attention kernel
reaches.  Per prefill, the least time of its attention calls (one per layer,
at the bucket's shape: causal operations over peak or q/k/v/o bytes over HBM
bandwidth, whichever is larger) over the kernel's device time per prefill
program execution."""

from perfbench import flops


def read(obs):
    if obs.trace is None or not obs.calls.prefill:
        return None
    kernel = obs.trace.matching(obs.names["flash_kernel"])
    runs = obs.trace.matching(obs.names["prefill_program"], "modules")
    if not kernel or not runs:
        return None
    device = sum(e[2] for e in kernel) / 1e9 / len(runs)
    layers = obs.cfg["num_hidden_layers"]
    least = sum(layers * flops.least_time(*flops.flash_call(obs.cfg, bucket),
                                          obs.peaks)
                for _, bucket, _ in obs.calls.prefill) / len(obs.calls.prefill)
    return 100.0 * least / device
