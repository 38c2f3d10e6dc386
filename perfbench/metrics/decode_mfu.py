"""Model step, decode: model operations of the tokens decoded per step
(the architecture's ``decode_flops`` over the slots in use and their
contexts), over the decode program's device time per execution times the
bf16 peak."""

from perfbench.metrics._common import per_call


def read(obs):
    if obs.trace is None or not obs.calls.decode:
        return None
    t = per_call(obs.trace.matching(obs.names["decode_program"], "modules"))
    if t is None:
        return None
    work = sum(obs.arch.decode_flops(obs.cfg, ctx)
               for _, _, ctx in obs.calls.decode) / len(obs.calls.decode)
    return 100.0 * work / (t * obs.peaks["bf16_flops_per_s"])
