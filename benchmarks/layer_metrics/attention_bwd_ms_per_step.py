"""Device time of attention's backward pass per training step, chip 0:
backward ops under the ``attention`` scope -- today the scan's ``while``
and its body (``benchmarks/attribution.py``). Layer: kernels."""
from benchmarks import attribution


def read(run):
    return attribution.attention_ms(run, "backward")
