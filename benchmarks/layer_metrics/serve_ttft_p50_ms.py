"""Median of due -> first token, client side. Not judged: a 30 s window
at this cell's rate holds 72 requests, and the median of 72 times that
each carry a uniform 0-100 ms wait for the running decode step spreads by
6 % from run to run (PERF.md, section 2). Layer: client_view."""
from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.facts["ttft_s"], 50) * 1e3
