"""The one general generator of serving traffic.

A traffic mix is a data file (``benchmarks/traffic/<mix>.json``); this
module turns its parameters and a seed into a fixed schedule of
requests before the window opens. The same seed gives the same arrival
times, lengths and token ids; another seed gives others. Nothing here
looks at the system under test, so offered load never depends on how
fast it answers (an open loop).

Parameters read (all under the traffic file's top level):

    arrivals     {"process": "poisson", "rate_per_s": r, "count": "fixed"}
                 every window holds round(r x seconds) requests:
                 exponential gaps, scaled to fill the window (the
                 Poisson process given its count), so runs differ in
                 when requests come, not in how many
    prompt_len   a length distribution (below)
    output_len   a length distribution
    max_total    prompt + output is cut to this (output first, then
                 prompt), e.g. the model's positions - 1

A length distribution is {"dist": "lognormal", "median": m, "sigma": s,
"min": a, "max": b, "stratified": true}: one draw from each of n
equal-probability slices of the distribution, in random order, clipped
to [a, b], so that every run asks for nearly the same total of tokens
and the seed decides which request gets which length. Token ids are
uniform over the vocabulary.

That is what the mixes that exist use. Another arrival process (bursts),
another distribution or prompts that share a prefix is refused here by
name; the cell that needs one brings it (PERF.md, Open questions).
"""
from __future__ import annotations

import statistics

import numpy as np


class Schedule:
    """``due_s[i]``: when request i is due, seconds from the window's
    start; ``prompts[i]``: its token ids; ``output_len[i]``: its budget."""

    def __init__(self, due_s, prompts, output_len):
        self.due_s = due_s
        self.prompts = prompts
        self.output_len = output_len

    def __len__(self):
        return len(self.due_s)

    @property
    def prompt_len(self):
        return np.asarray([len(p) for p in self.prompts], np.int64)


def _rng(seed, stream):
    return np.random.default_rng([int(seed), stream])


def _only(group, key, value):
    if group.get(key) != value:
        raise ValueError(f"{key!r}: {group.get(key)!r} is not generated "
                         f"here, only {value!r}")


def draw_lengths(dist, n, rng):
    _only(dist, "dist", "lognormal")
    _only(dist, "stratified", True)
    u = rng.permutation((np.arange(n) + rng.uniform(0, 1, n)) / n)
    inv_cdf = statistics.NormalDist().inv_cdf
    z = np.asarray([inv_cdf(float(v)) for v in np.clip(u, 1e-12, 1 - 1e-12)])
    vals = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.floor(vals).astype(np.int64), dist["min"], dist["max"])


def draw_arrivals(arrivals, seconds, rng):
    """Due times in [0, seconds)."""
    _only(arrivals, "process", "poisson")
    _only(arrivals, "count", "fixed")
    rate = float(arrivals["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = rng.exponential(1.0 / rate, n + 1)
    return np.cumsum(gaps[:n]) * (seconds / gaps.sum())


def make_schedule(traffic, seed, seconds, vocab_size):
    due = draw_arrivals(traffic["arrivals"], seconds, _rng(seed, 1))
    n = len(due)
    rng = _rng(seed, 2)
    prompt_len = draw_lengths(traffic["prompt_len"], n, rng)
    out_len = draw_lengths(traffic["output_len"], n, rng)
    cap = int(traffic["max_total"])
    trng = _rng(seed, 4)
    prompts = []
    for i in range(n):
        own = int(min(prompt_len[i], cap - 1))
        out_len[i] = min(out_len[i], cap - own)
        prompts.append(trng.integers(0, vocab_size, own, dtype=np.int32))
    return Schedule(due, prompts, out_len)
