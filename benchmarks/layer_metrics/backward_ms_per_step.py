"""Device time of the backward pass per training step, chip 0: self time
of the step's ops whose ``op_name`` has ``transpose(`` (the transposed
half of ``value_and_grad``), recomputation under remat included: it
counts where it runs (``benchmarks/attribution.py``). Layer: program."""
from benchmarks import attribution


def read(run):
    return attribution.phase_ms(run, "backward")
