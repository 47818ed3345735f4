"""Device time of the forward pass per training step, chip 0: self time
of the step's ops whose ``op_name`` (the program's own map,
``observability.perf.op_names``) has ``jvp(`` and no ``transpose(``;
``benchmarks/attribution.py`` has the rules. Layer: program."""
from benchmarks import attribution


def read(run):
    return attribution.phase_ms(run, "forward")
