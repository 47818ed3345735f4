"""Device time of custom calls (Pallas kernels: ``tpu_custom_call``) per
training step, chip 0, from the xplane. Layer: kernels
(``ops/pallas_kernels.py``)."""
from benchmarks.harness import layers, xplane


def read(run):
    dev = layers.chip(run)
    if dev is None:
        return None
    secs = xplane.per_step(dev, lambda name, cat: cat == "custom call")
    return None if secs is None else secs * 1e3
