"""Model step, prefill: model operations of the real prompt tokens (the
architecture's ``prefill_flops``; bucket padding not counted) per prefill,
over the prefill program's device time per execution times the bf16
peak."""

from perfbench.metrics._common import per_call


def read(obs):
    if obs.trace is None or not obs.calls.prefill:
        return None
    t = per_call(obs.trace.matching(obs.names["prefill_program"], "modules"))
    if t is None:
        return None
    work = sum(obs.arch.prefill_flops(obs.cfg, n)
               for _, _, n in obs.calls.prefill) / len(obs.calls.prefill)
    return 100.0 * work / (t * obs.peaks["bf16_flops_per_s"])
