"""Device time of the Mamba-2 layers' backward pass per training step,
chip 0: backward ops under the ``ssm`` scope, the forward pass a
``Remat`` layer recomputes and the scan's per-chunk recomputation among
them (``benchmarks/ssm_scope.py``). Layer: kernels."""
from benchmarks import ssm_scope

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "ssm"


def read(run):
    return ssm_scope.scope_ms(run, "backward")
