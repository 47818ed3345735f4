"""Time per training step in which a collective (all-reduce,
all-gather, reduce-scatter, ...) is in flight on the chip, start to
done, the slowest chip. Layer: collectives (GSPMD in the step)."""
from benchmarks.harness import layers


def read(run):
    return layers.collective_ms(run, exposed=False)
