"""90th percentile of due -> first token, client side. Not judged (see
``serve_ttft_p50_ms``). Layer: client_view."""
from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.facts["ttft_s"], 90) * 1e3
