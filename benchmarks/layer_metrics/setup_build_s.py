"""Seconds of set-up spent building the model and the trainer before the
window: the union of the program's ``setup.initialize``
(``Block.initialize``), ``setup.infer_shape`` (deferred shapes fixed
during the first forward) and ``setup.trainer`` (optimizer state made,
parameters, aux and state placed on the mesh) spans. Layer: gluon."""
from benchmarks import attribution


def read(run):
    return attribution.setup_seconds(
        run, ("setup.initialize", "setup.infer_shape", "setup.trainer"))
