"""Measured cost + numerics gate for the schedule search.

Timing follows the PERF.md discipline the repo's benches established:
one untimed warmup call absorbs trace+compile, every timed section
blocks on the *outputs* (dependency-chained ``block_until_ready``, so
async dispatch cannot hide device time), and the reported cost is the
MINIMUM over R rounds of K iterations — min-of-rounds is what absorbs a
scheduler burst landing on exactly one round (the perf_gate / obs_bench
methodology).

Validation is the tuner's safety property: a candidate schedule may
only win if its outputs agree with the reference schedule's — exact
equality on integer grids (int8/int32 outputs), tight elementwise
tolerance for floats (block decomposition legitimately reorders
float accumulation by a ULP). A candidate that fails is *rejected*,
never timed into the table.
"""
from __future__ import annotations

import time

from . import _STATS

__all__ = ["time_min_ms", "outputs_match", "FLOAT_RTOL", "FLOAT_ATOL"]

# float agreement bar between schedule candidates: online-softmax block
# decomposition reorders f32 accumulation, so bitwise is not physical —
# but anything beyond a few ULP at these magnitudes is a wrong kernel
FLOAT_RTOL = 2e-5
FLOAT_ATOL = 2e-5
# the same bar for 16-bit float outputs (bf16 keeps 8 bits of mantissa:
# two tilings may round the same float32 accumulator one ulp apart)
HALF_RTOL = 2e-2
HALF_ATOL = 2e-2


def _leaves(out):
    if isinstance(out, (tuple, list)):
        leaves = []
        for o in out:
            leaves.extend(_leaves(o))
        return leaves
    return [out]


def block_on(out):
    import jax

    jax.block_until_ready(out)
    return out


def time_min_ms(fn, args, rounds=3, iters=5):
    """min over ``rounds`` of mean-of-``iters`` wall ms for ``fn(*args)``,
    blocking on the outputs each round (never timing dispatch alone).
    The caller has already run the warmup call."""
    best = float("inf")
    for _ in range(max(1, int(rounds))):
        t0 = time.perf_counter()
        out = None
        for _ in range(max(1, int(iters))):
            out = fn(*args)
        block_on(out)
        best = min(best, (time.perf_counter() - t0) / max(1, iters) * 1e3)
    return best


def outputs_match(ref, got, rtol=FLOAT_RTOL, atol=FLOAT_ATOL):
    """-> (ok, max_abs_err). Integer outputs must be exactly equal;
    float outputs must agree within (rtol, atol) elementwise (16-bit
    floats within the wider :data:`HALF_RTOL` / :data:`HALF_ATOL`).
    Structure (leaf count/shape/dtype) must match exactly."""
    import numpy as np

    ref_l, got_l = _leaves(ref), _leaves(got)
    if len(ref_l) != len(got_l):
        return False, float("inf")
    worst = 0.0
    for r, g in zip(ref_l, got_l):
        r = np.asarray(r)
        g = np.asarray(g)
        if r.shape != g.shape or r.dtype != g.dtype:
            return False, float("inf")
        if np.issubdtype(r.dtype, np.integer) or r.dtype == np.bool_:
            if not np.array_equal(r, g):
                return False, float(
                    np.max(np.abs(r.astype(np.int64) - g.astype(np.int64))))
            continue
        leaf_rtol, leaf_atol = rtol, atol
        if r.dtype.itemsize == 2:
            leaf_rtol = max(rtol, HALF_RTOL)
            leaf_atol = max(atol, HALF_ATOL)
        r64 = r.astype(np.float64)
        g64 = g.astype(np.float64)
        err = np.abs(r64 - g64)
        worst = max(worst, float(err.max()) if err.size else 0.0)
        if not np.allclose(r64, g64, rtol=leaf_rtol, atol=leaf_atol,
                           equal_nan=True):
            return False, worst
    return True, worst


def note_rejected():
    _STATS["autotune_rejected"] += 1


def note_timed():
    _STATS["autotune_candidates"] += 1
