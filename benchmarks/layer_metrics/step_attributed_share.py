"""Share of the step's device time that the program's own names put down
to forward, backward or optimizer: (the three) / ``step_device_ms``.
The rest -- copies and layout changes XLA added, asynchronous pairs,
anything with no ``op_name`` -- is logged by instruction name, with the
table of step time by phase x block kind. Layer: program."""
from benchmarks import attribution
from benchmarks.harness import manifest


def read(run):
    att = attribution.of_run(run)
    step_ms = manifest.module("layer_metrics", "step_device_ms").read(run)
    if att is None or not step_ms:
        return None
    named = sum(att.ms_per_step(p) for p in attribution.PHASES)
    return 100.0 * named / step_ms
