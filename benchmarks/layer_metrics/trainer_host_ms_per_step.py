"""Mean duration of the program's ``train.sharded_step`` spans in the
window: the host's time to issue one step (the span closes when the
asynchronous call returns, not when the device is done). It bounds the
step only where ``device_idle_share`` is not ~0. Layer: trainer
(``parallel/trainer.py``)."""
from benchmarks.harness import stats


def read(run):
    spans = run.program_spans("train.sharded_step")
    if not spans:
        return None
    return stats.mean(s["dur_ns"] for s in spans) / 1e6
