"""Device time of linear attention's forward pass per training step,
chip 0: forward ops under the ``linear_attention`` scope
``contrib.nn.GatedDeltaNet`` opens around everything but its four
projections (convolution, gates, the chunked delta rule, the gated
norm), whatever implements them (``benchmarks/scopes.py``). Layer:
kernels."""
from benchmarks import scopes


def read(run):
    return scopes.scope_ms(run, "linear_attention", "forward")
