"""Engine and scheduler: device idle milliseconds per decode step while the
host is inside the engine's ``step()``.  The harness's ``step`` span wraps
each ``eng.step()`` call exactly, and its ``admit``, ``prefill`` and
``decode`` spans lie inside it; their idle in the traced window, over the
decode program's executions there, is the host's turnaround between one
decode and the next (token fetch, uploads, bookkeeping), which
``itl_p50_s`` pays on top of ``decode_step_ms``."""

STEP_SPANS = ("step", "admit", "prefill", "decode")


def read(obs):
    if obs.trace is None or not obs.trace.spans or not obs.trace.devices():
        return None
    runs = obs.trace.matching(obs.names["decode_program"], "modules")
    if not runs:
        return None
    idle = obs.trace.idle_by_host()
    return 1e3 * sum(idle.get(s, 0.0) for s in STEP_SPANS) / len(runs)
