"""Scheduler: 90th percentile of the wait from a request's due time to the
start of its own prefill call, over the requests due in the window (before
the profiler started, in a traced run, so that starting it stalls none of
them).  A request never prefilled counts as waiting until the run stopped."""

import numpy as np


def read(obs):
    s = obs.served
    cut = obs.trace_window[0] - 1.0 if obs.trace_window else s.window[1]
    waits = [(t.t_prefill if t.t_prefill is not None else s.stopped) - t.due
             for t in s.timed if t.section == "window" and t.due < cut]
    return float(np.percentile(waits, 90)) if waits else None
