"""Device time of linear attention's backward pass per training step,
chip 0: backward ops under the ``linear_attention`` scope, the
recomputed forward of a ``Remat`` layer among them
(``benchmarks/scopes.py``). Layer: kernels."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "linear_attention"


def read(run):
    return scopes.scope_ms(run, "linear_attention", "backward")
