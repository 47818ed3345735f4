"""Percentile and spread arithmetic, in one place.

``percentile`` interpolates linearly between order statistics (numpy's
default); a missing observation (a request that failed or never got its
token) is passed as ``math.inf`` and so counts against every percentile
above the share of observations that exist. ``spread`` is the driver's
measure of run-to-run noise: the distance between the quartiles over
the median.
"""
from __future__ import annotations

import math


def percentile(values, q):
    """The ``q``-th percentile (0..100) of ``values``; None when empty."""
    vals = sorted(values)
    if not vals:
        return None
    pos = (len(vals) - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(vals) - 1)
    if vals[hi] == math.inf:
        return math.inf if pos > lo or vals[lo] == math.inf else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


def median(values):
    return percentile(values, 50)


def spread(values):
    """(q75 - q25) / median of ``values``; None for fewer than two, or a
    median of 0."""
    if len(values) < 2:
        return None
    med = median(values)
    if not med:
        return None
    return (percentile(values, 75) - percentile(values, 25)) / abs(med)


def mean(values):
    values = list(values)
    return sum(values) / len(values) if values else None
