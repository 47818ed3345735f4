"""Device-busy time of one training step: the union of op intervals
inside each complete run of the step's executable in the traced window,
averaged, chip 0. Layer: program (the XLA executable of the step)."""
from benchmarks.harness import layers, xplane


def read(run):
    dev = layers.chip(run)
    if dev is None:
        return None
    runs = xplane.step_runs(dev)
    if not runs:
        return None
    busy = sum(xplane.total(xplane.clip(dev["busy"], s, e)) for s, e in runs)
    return busy / len(runs) / 1e6
