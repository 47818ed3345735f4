"""Median wait from the instant a request was due to the start of its
``decode.admit`` span (pages granted, prefill about to run). Admission
is first come, first served, so the k-th admit is the k-th request;
with a preemption in the window (a sequence re-queued at the front) the
order no longer identifies requests and nothing is returned, and the
same where an admit's ``ctx`` is not its request's prompt length.
Layer: batcher."""
from benchmarks.harness import stats


def read(run):
    admits = sorted(run.program_spans("decode.admit"),
                    key=lambda s: s["t0_ns"])
    if not admits or run.facts["engine"]["decode_preemptions"]:
        return None
    t0 = run.window[0]
    waits = []
    for k, span in enumerate(admits):
        if span["attrs"]["ctx"] != run.facts["prompt_len"][k]:
            return None
        waits.append(span["t0_ns"] / 1e9 - (t0 + run.facts["due_s"][k]))
    return stats.percentile(waits, 50) * 1e3
