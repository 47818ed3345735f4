"""Device time of attention's forward pass per training step, chip 0:
forward ops under the ``attention`` scope ``MultiHeadAttention`` opens
around scores, softmax and values (not the projections), whatever
implements them -- the Pallas ``flash_attention_fwd`` kernel today
(``benchmarks/attribution.py``). Layer: kernels."""
from benchmarks import attribution


def read(run):
    return attribution.attention_ms(run, "forward")
