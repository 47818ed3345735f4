"""Seconds of set-up inside ``import mxnet_tpu``: the program's
``setup.import`` span, from the first line of its ``__init__`` to its
last (jax's import too where that is what loads jax). Layer: package."""
from benchmarks import attribution


def read(run):
    return attribution.setup_seconds(run, ("setup.import",))
