"""Device time of the expert layers' forward pass per training step,
chip 0: forward ops under the ``moe`` scope ``contrib.nn.SparseMoE``
opens around router, grouped experts, shared expert and their sum
(``benchmarks/scopes.py``). Layer: moe."""
from benchmarks import scopes


def read(run):
    return scopes.scope_ms(run, "moe", "forward")
