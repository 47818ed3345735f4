"""99th percentile of how late the generator sent a request against
its due time. A late generator flatters first-token times measured from
the send; here they are measured from the due time, and this says by how
much the two differ. Layer: load_generator."""
from benchmarks.harness import stats


def read(run):
    return stats.percentile(run.facts["late_s"], 99) * 1e3
