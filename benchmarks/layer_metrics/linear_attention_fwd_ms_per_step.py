"""Device time of linear attention's forward pass per training step,
chip 0: forward ops under the ``linear_attention`` scope
``contrib.nn.GatedDeltaNet`` opens around everything but its four
projections (convolution, gates, the chunked delta rule, the gated
norm), whatever implements them (``benchmarks/scopes.py``). Layer:
kernels."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "linear_attention"


def read(run):
    return scopes.scope_ms(run, "linear_attention", "forward")
