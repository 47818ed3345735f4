"""Mean share of the decode step's ``max_seqs`` slots that held a live
sequence, over the ``decode.step`` spans of the window (their ``live``
attribute). Layer: batcher (``serving/batcher.py``)."""
from benchmarks.harness import stats


def read(run):
    spans = run.program_spans("decode.step")
    if not spans:
        return None
    live = stats.mean(s["attrs"]["live"] for s in spans)
    return 100.0 * live / run.facts["max_seqs"]
