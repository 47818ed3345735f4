"""Device time of attention's backward pass per training step, chip 0:
backward ops under the ``attention`` scope, whatever implements them
(``benchmarks/attribution.py``): since PR 31 the kernel
``flash_attention_bwd`` and the ``D`` row sum beside it, before it a
``lax.scan``'s ``while`` and its body. Layer: kernels."""
from benchmarks import attribution


def read(run):
    return attribution.attention_ms(run, "backward")
