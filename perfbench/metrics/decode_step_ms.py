"""Model step, decode: device milliseconds per execution of the decode
program, from the trace."""

from perfbench.metrics._common import per_call


def read(obs):
    if obs.trace is None:
        return None
    t = per_call(obs.trace.matching(obs.names["decode_program"], "modules"))
    return None if t is None else 1e3 * t
