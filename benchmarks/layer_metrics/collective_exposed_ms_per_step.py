"""The part of ``collective_ms_per_step`` during which no other op runs
on that chip: communication nothing hides. Layer: collectives."""
from benchmarks.harness import layers


def read(run):
    return layers.collective_ms(run, exposed=True)
