"""``peak_hbm_gb`` for serving cells. Layer: device."""
from benchmarks.harness import layers


def read(run):
    return layers.peak_gb(run)
