"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests the engine finished is
drawn from the seed: the longest of them, one from each prompt bucket that
finished requests used, and further ones until the sample holds
``check_tokens`` served tokens.  The plain reference runs once over each
prompt followed by its served tokens.  At every position where a token was
served, the number compared is how far that token's reference logit lies
below the reference's best logit there; ``gap_max`` is the widest of these.

The engine decodes greedily, so a served token is the argmax of the
program's logits, and its reference gap is at most twice the program's
largest logit error at that position.  A wrong mask, cache page, position or
kernel moves logits by far more than bfloat16 rounding does.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from perfbench.reference import Reference


def sample(timed, buckets, seed: int, check_tokens: int) -> list:
    """The finished requests to compare, drawn from ``seed``."""
    done = [t for t in timed if t.done]
    if not done:
        return []
    rng = np.random.default_rng([int(seed), 1])
    order = [done[i] for i in rng.permutation(len(done))]
    size = lambda t: t.req.prompt_len + t.req.max_new_tokens
    picked = [max(done, key=size)]
    bucket = lambda t: next(b for b in buckets if t.req.prompt_len <= b)
    for b in sorted({bucket(t) for t in done}):
        if all(bucket(t) != b for t in picked):
            picked.append(next(t for t in order if bucket(t) == b))
    for t in order:
        if sum(len(p.req.out_tokens) for p in picked) >= check_tokens:
            break
        if t not in picked:
            picked.append(t)
    return picked


def served_gaps(ref: Reference, prompt, out_tokens, controls=()):
    """Reference gap of each served token, and for each control the
    reference gap of the token that the control puts first."""
    tokens = np.concatenate([prompt, np.asarray(out_tokens[:-1], np.int32)])
    rows = np.arange(len(prompt) - 1, len(tokens))
    want = ref.logits(tokens, rows)
    best = jnp.max(want, axis=-1)
    at = lambda idx: jnp.take_along_axis(want, idx[:, None], axis=-1)[:, 0]
    gap = np.asarray(best - at(jnp.asarray(np.asarray(out_tokens, np.int32))))
    ctrl = {c: np.asarray(best - at(jnp.argmax(
        ref.logits(tokens, rows, control=c), axis=-1))) for c in controls}
    return gap, ctrl


def compare(ref: Reference, picked, controls=()) -> dict:
    """``gap_max`` of the program over the sample, and of each control
    (as ``gap_max.<control>``)."""
    out = {"gap_max": 0.0, "tokens": 0, "requests": len(picked)}
    out.update({f"gap_max.{c}": 0.0 for c in controls})
    for t in picked:
        gap, ctrl = served_gaps(ref, t.req.prompt, t.req.out_tokens, controls)
        out["gap_max"] = max(out["gap_max"], float(gap.max()))
        out["tokens"] += len(gap)
        for c, g in ctrl.items():
            out[f"gap_max.{c}"] = max(out[f"gap_max.{c}"], float(g.max()))
    return out
