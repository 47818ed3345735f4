"""``device_idle_share`` for serving cells (a per-layer metric is reported
only where the end-to-end metric it moves is, so serving has its own
name). Layer: device."""
from benchmarks.harness import layers


def read(run):
    return layers.idle_share(run)
