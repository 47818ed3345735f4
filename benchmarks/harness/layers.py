"""What several per-layer readers share (each reader stays a file of its
own under ``benchmarks/layer_metrics/``; a reader returns None where it
finds nothing to read: a rehearsal then leaves the metric out, and a
chip run, where every metric listed for the cell has to be there,
fails)."""
from __future__ import annotations

from . import xplane


def chips(run):
    """The reduced timelines of the chips this cell used, or []."""
    if run.trace is None:
        return []
    devs = run.trace["devices"]
    return [devs[n] for n in sorted(devs)[:len(run.devices)]]


def chip(run):
    """Chip 0's reduced timeline, or None."""
    used = chips(run)
    return used[0] if used else None


def idle_share(run):
    used = chips(run)
    if not used:
        return None
    window = run.trace["window_s"]
    return 100.0 * max(1.0 - d["busy_s"] / window for d in used)


def peak_gb(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9


def collective_ms(run, exposed):
    """Per training step, on the chip where it is largest."""
    worst = None
    for dev in chips(run):
        runs = xplane.step_runs(dev)
        if not runs:
            continue
        lo, hi = runs[0][0], runs[-1][1]
        spans = xplane.clip(dev["collectives"], lo, hi)
        if exposed:
            spans = xplane.subtract(spans, dev["compute"])
        ms = xplane.total(spans) / len(runs) / 1e6
        worst = ms if worst is None else max(worst, ms)
    return worst
