"""Engine and scheduler: share of the prefilled tokens that were bucket
padding, 100 × (bucket − prompt) / bucket, summed over the requests whose
prefill began in the traced window.  The bucket is the one the engine
records on each request it admits (``Request.bucket``); a program that
records none reads None."""


def read(obs):
    if obs.trace_window is None:
        return None
    on, off = obs.trace_window
    reqs = [t.req for t in obs.served.timed
            if t.t_prefill is not None and on <= t.t_prefill <= off]
    buckets = [getattr(r, "bucket", None) for r in reqs]
    if not reqs or None in buckets:
        return None
    padded = sum(buckets)
    return 100.0 * (padded - sum(r.prompt_len for r in reqs)) / padded
