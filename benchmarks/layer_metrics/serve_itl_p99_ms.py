"""99th percentile gap between consecutive tokens of one stream, client
side. Not judged: it falls between "two prefills in front of a step" and
"three", and lands on either from run to run (271 - 419 ms in six runs;
PERF.md, section 2). Layer: client_view."""
from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.facts["itl_s"], 99) * 1e3
