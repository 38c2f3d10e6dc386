"""The one traffic generator: an open-loop schedule from a mix file and a seed.

A schedule has three sections: ``ramp`` (load offered before the window
opens, so that the window starts in steady state), ``window`` (the
``--seconds`` that are measured) and ``tail`` (load offered while the
window's requests drain, so that they finish under the same load).

Every section draws its lengths and gaps as stratified quantiles of the mix's
distributions, shuffled once by a fixed key (``ORDER_KEY``), and only the
token ids from the run's seed.  So every seed offers the same prompt
lengths, answer lengths and arrival times in the same order: runs on
different seeds do the same work and differ only as the system does.  (A
per-seed order moved the window's output tokens per second by 7% and its
time-to-first-token tail by 15% from seed to seed, through which requests
straddle the window's edges and which bursts meet long prompts, while two
runs of one seed agreed within 0.3%; see PERF.md.)
"""

from __future__ import annotations

import dataclasses
import statistics

import numpy as np

SECTIONS = ("ramp", "window", "tail")
#: Key of the one shuffle of lengths and gaps that every seed shares.
ORDER_KEY = 20261016


@dataclasses.dataclass
class Item:
    """One request of a schedule."""

    due_s: float          # offset from the start of the schedule
    section: str
    prompt: np.ndarray    # (prompt_len,) int32
    max_new_tokens: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of a length distribution, in ascending order.

    ``lognormal``: ``median * exp(sigma * z)``; ``uniform``: integers from
    ``min`` to ``max`` inclusive.  Both are clipped to ``[min, max]``.
    """
    u = _quantiles(n)
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        v = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    elif spec["dist"] == "uniform":
        v = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(v, lo, hi).astype(np.int64)


def gaps(rate: float, n: int, span_s: float) -> np.ndarray:
    """``n`` stratified exponential gaps, scaled to sum to ``span_s``."""
    g = -np.log1p(-_quantiles(n)) / rate
    return g * (span_s / g.sum())


def section_sizes(mix: dict, seconds: float) -> dict:
    """Number of requests offered in each section."""
    rate = mix["rate_per_s"]
    spans = {"ramp": mix["ramp_s"], "window": seconds, "tail": mix["grace_s"]}
    return {s: (max(1, round(rate * spans[s])), spans[s]) for s in SECTIONS}


def schedule(mix: dict, seconds: float, seed: int, vocab: int) -> list[Item]:
    """The schedule of a run, sorted by due time; ``seed`` draws the token
    ids."""
    order = np.random.default_rng(ORDER_KEY)
    ids = np.random.default_rng(seed)
    out, t0 = [], 0.0
    for sec, (n, span) in section_sizes(mix, seconds).items():
        p = order.permutation(lengths(mix["prompt_len"], n))
        o = order.permutation(lengths(mix["output_len"], n))
        g = order.permutation(gaps(mix["rate_per_s"], n, span))
        due = t0 + np.cumsum(g)
        for i in range(n):
            out.append(Item(
                due_s=float(due[i]), section=sec,
                prompt=ids.integers(0, vocab, int(p[i]), dtype=np.int32),
                max_new_tokens=int(o[i])))
        t0 += span
    return out


def buckets_used(mix: dict, seconds: float, buckets) -> list[int]:
    """The prompt buckets that the mix's requests fall into."""
    used = set()
    for n, _ in section_sizes(mix, seconds).values():
        for p in np.unique(lengths(mix["prompt_len"], n)):
            used.add(next(b for b in sorted(buckets) if p <= b))
    return sorted(used)

