"""Seconds of set-up spent compiling captured executables before the
window: the union of the program's ``capture.trace_lower`` (trace,
lower, AOT export or load) and ``capture.compile`` (XLA, or the
persistent cache's answer) spans; the log splits them by label and by
whether the cache served the compile. Layer: compile_cache."""
from benchmarks import attribution


def _part(span):
    attrs = span["attrs"]
    served = {True: " (cache)", False: " (compiled)"}.get(
        attrs.get("cache_hit"), "")
    return f"{span['name']} {attrs.get('label')}{served}"


def read(run):
    return attribution.setup_seconds(
        run, ("capture.trace_lower", "capture.compile"), split=_part)
