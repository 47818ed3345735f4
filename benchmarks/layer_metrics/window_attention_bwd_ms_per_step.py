"""Device time of window attention's backward pass per training step,
chip 0: backward ops under the ``window_attention`` scope, the forward
pass a ``Remat`` half recomputes among them
(``benchmarks/window_attention.py``). The layers that see the whole
sequence are ``attention_bwd_ms_per_step`` less this. Layer: kernels."""
from benchmarks import window_attention

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "window_attention"


def read(run):
    return window_attention.scope_ms(run, "backward")
