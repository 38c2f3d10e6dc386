"""Device: share of the traced window in which no operation ran on the
device (1 - union of op intervals / window), averaged over chips."""


def read(obs):
    if obs.trace is None or not obs.trace.devices():
        return None
    return 100.0 * obs.trace.idle_share()
