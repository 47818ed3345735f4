"""Device time of window attention's forward pass per training step,
chip 0: forward ops under the ``window_attention`` scope that
``contrib.nn.GatedAttention`` opens, inside ``attention``, around the
core of a layer whose queries see a window of the sequence, whatever
implements it (``benchmarks/window_attention.py``). The layers that see
the whole sequence are ``attention_fwd_ms_per_step`` less this. Layer:
kernels."""
from benchmarks import window_attention

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "window_attention"


def read(run):
    return window_attention.scope_ms(run, "forward")
