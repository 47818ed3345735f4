"""Compile requests jax made inside the measured window (must be 0: a
request there is a shape the warm-up missed, and its time was measured
as if it were work). Layer: compile_cache."""


def read(run):
    counts = run.compile_counts
    return counts["window_end"]["requests"] - counts["setup"]["requests"]
