"""Device time of the expert layers' backward pass per training step,
chip 0: backward ops under the ``moe`` scope, the recomputed forward of
a ``Remat`` layer among them (``benchmarks/scopes.py``). Layer: moe."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "moe"


def read(run):
    return scopes.scope_ms(run, "moe", "backward")
