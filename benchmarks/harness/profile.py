"""The jax profiler around a sub-window, and the benchmark's host spans.

``annotate(name)`` writes a host span into the profiler's own trace
(``jax.profiler.TraceAnnotation``), so it lands on the device's
timeline; with no trace running it costs one flag check.

The program's spans (``observability/trace.py``) are stamped with
``time.perf_counter_ns``, which the profiler does not know. ``sync``
writes an annotation that carries the perf-counter reading taken as it
opens; the reduction reads the pair back and so can place every
perf-counter time on the trace's clock (``xplane.Clock``).
"""
from __future__ import annotations

import glob
import os
import time

from .xplane import SYNC


def annotate(name, **stats):
    import jax

    return jax.profiler.TraceAnnotation(name, **stats)


def sync():
    with annotate(SYNC, t_perf_ns=time.perf_counter_ns()):
        pass


class DeviceTrace:
    """One traced sub-window, written under ``out_dir``."""

    def __init__(self, out_dir):
        self.out_dir = out_dir
        self.t_start_ns = self.t_stop_ns = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # no per-call Python events: they
        opts.enable_hlo_proto = False   # slow the host and swell the file
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        sync()
        self.t_start_ns = time.perf_counter_ns()

    def stop(self):
        import jax

        self.t_stop_ns = time.perf_counter_ns()
        sync()
        jax.profiler.stop_trace()

    def path(self):
        found = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None
