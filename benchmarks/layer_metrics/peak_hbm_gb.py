"""Peak bytes in use on the fullest chip right after the window, in GB
(``device.memory_stats()["peak_bytes_in_use"]``; the reference's own
memory comes later and is not in it). Layer: device."""
from benchmarks.harness import layers


def read(run):
    return layers.peak_gb(run)
