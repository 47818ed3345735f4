"""Device time of the Mamba-2 layers' forward pass per training step,
chip 0: forward ops under the ``ssm`` scope that
``contrib.nn.Mamba2Mixer`` opens around everything but its two
projections (the short convolution with its SiLU, dt and A, the chunked
state-space scan, the gated norm), whatever implements it
(``benchmarks/ssm_scope.py``). Layer: kernels."""
from benchmarks import ssm_scope

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "ssm"


def read(run):
    return ssm_scope.scope_ms(run, "forward")
