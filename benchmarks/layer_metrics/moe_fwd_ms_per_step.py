"""Device time of the expert layers' forward pass per training step,
chip 0: forward ops under the ``moe`` scope ``contrib.nn.SparseMoE``
opens around router, grouped experts, shared expert and their sum
(``benchmarks/scopes.py``). Layer: moe."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "moe"


def read(run):
    return scopes.scope_ms(run, "moe", "forward")
