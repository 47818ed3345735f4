"""Device time of the optimizer's update per training step, chip 0: self
time of the step's ops under the ``optimizer`` scope the trainer opens
around the update (``benchmarks/attribution.py``). An update XLA fused
into a weight-gradient fusion counts with that fusion's root; the log of
``step_attributed_share`` says how much time such fusions hold. Layer:
program."""
from benchmarks import attribution


def read(run):
    return attribution.phase_ms(run, "optimizer")
