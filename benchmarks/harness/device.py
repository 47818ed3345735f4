"""The device a run measures: the chip check, its record, its peaks.

The benchmark keeps its own peaks table. ``observability.perf.DEVICE_PEAKS``
holds the same figures, but a PR may change the program and must not be
able to move the yardstick with it.
"""
from __future__ import annotations

# Published per-chip peaks, keyed by jax ``device_kind``. Source: Google
# Cloud TPU documentation, "TPU v5e" (system architecture): 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect. A kind that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197.0e12,
                    "int8_ops_per_s": 393.0e12,
                    "hbm_bytes_per_s": 819.0e9,
                    "hbm_bytes": 16.0e9,
                    "ici_bytes_per_s": 200.0e9},
}


class NoChip(RuntimeError):
    """jax found no accelerator, or fewer chips than the cell asks for."""


def peaks(kind):
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r}; add a "
                       "row with its source to benchmarks/harness/device.py")
    return PEAKS[kind]


def record(devices):
    """``{"platform", "kind", "count"}`` as jax reports them."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_chips(n, allow_cpu=False):
    """The first ``n`` devices of jax's default backend and the record of
    all of them. Raises :class:`NoChip` on the CPU (unless ``allow_cpu``,
    the rehearsal's flag) or with fewer than ``n`` devices."""
    import jax

    devices = jax.devices()
    rec = record(devices)
    if rec["platform"] == "cpu" and not allow_cpu:
        raise NoChip(f"jax reports {rec['count']} cpu device(s) and no "
                     "accelerator; the benchmark does not run on the CPU")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chip(s), jax reports "
                     f"{rec['count']} {rec['platform']} device(s)")
    return devices[:n], rec


def memory_peak_bytes(devices):
    """Peak bytes on the fullest of ``devices``, read after the window;
    None where the backend reports no memory statistics (XLA-CPU).

    On this TPU runtime ``peak_bytes_in_use`` counts arrays only: the
    scratch memory of the programs that ran is reserved apart and shows
    as ``peak_bytes_reserved`` (ResNet-50 at batch 256: 1.8 GB of arrays,
    9.0 GB reserved, the step's temporaries by the compiler's count). So
    the peak is the larger of the arrays' own peak, which may date from
    set-up, and what the window held: arrays now + that reservation."""
    peak = None
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            held = int(stats["peak_bytes_in_use"])
            if "peak_bytes_reserved" in stats:
                held = max(held, int(stats["bytes_in_use"])
                           + int(stats["peak_bytes_reserved"]))
            peak = max(peak or 0, held)
    return peak
