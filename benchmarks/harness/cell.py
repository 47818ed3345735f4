"""One run of one cell: set-up, window, checks, readers, result.

``run_cell`` is what ``benchmarks/run.py`` (on the chip) and
``benchmarks/rehearse.py`` (on the CPU, tiny preset, labelled) both
call. The loop named by the traffic file builds the system through the
configuration's model file, opens and closes the window on the ``Run``
it is handed, and returns the end-to-end values with the counts and the
verdict. A traced run then reduces the device trace and calls one
reader per per-layer metric of the cell.
"""
from __future__ import annotations

import os
import sys
import time

from . import compiles, device, manifest, profile, xplane


class Run:
    """What a loop and the per-layer readers see of one run."""

    def __init__(self, cell, seed, seconds, devices, device_record, out_dir,
                 trace, t_process_start, counter, rehearsal=False):
        self.cell = cell
        self.rehearsal = rehearsal
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = int(seconds)
        self.devices = devices
        self.device_record = device_record
        self.out_dir = out_dir
        self.tracer = profile.DeviceTrace(os.path.join(out_dir, "trace")) \
            if trace else None
        self.model = manifest.module("models", cell.config["model"])
        self.reference = manifest.module("references",
                                         cell.config["reference"])
        self.annotate = profile.annotate
        self.facts = {}             # what the loop measured, for readers
        self.end_to_end = {}
        self.trace = None           # xplane.reduce()'s summary
        self.peak_bytes = None
        self.window = None          # (t0, t1) on time.perf_counter
        self.setup_s = None
        self.compile_counts = {}
        self._t_process_start = t_process_start
        self._counter = counter

    def log(self, msg):
        print(f"[{self.cell.name}] {msg}", flush=True)

    def open_window(self):
        t0 = time.perf_counter()
        self.setup_s = t0 - self._t_process_start
        self.compile_counts["setup"] = self._counter.snapshot()
        self.window = (t0, None)
        return t0

    def close_window(self, t1):
        self.window = (self.window[0], t1)
        self.compile_counts["window_end"] = self._counter.snapshot()
        return t1

    def peak_bytes_after_window(self):
        self.peak_bytes = device.memory_peak_bytes(self.devices)
        self.log(f"memory_stats of chip 0: {self.devices[0].memory_stats()}")

    def peaks(self):
        """Published peaks of the chip the run is on. A rehearsal has no
        chip: it takes the v5e's row so that the readers' arithmetic
        runs, and prints none of their values."""
        return device.peaks("TPU v5 lite" if self.rehearsal
                            else self.device_record["kind"])

    def program_spans(self, name=None, in_window=True):
        """The program's own span records, by default those that started
        inside the window (none when the run is not traced: spans are
        off)."""
        from mxnet_tpu.observability import trace as obs_trace

        spans = obs_trace.spans(name=name)
        if not in_window:
            return spans
        lo, hi = (int(t * 1e9) for t in self.window)
        return [s for s in spans if lo <= s["t0_ns"] < hi]


def program_scopes():
    """The scopes the program's own name maps hold: every part of every
    ``op_name`` (and of the names inside a fusion that has none) of
    every executable in ``observability.perf``'s ledger. Empty for a
    program from before the maps."""
    from benchmarks import attribution
    from mxnet_tpu.observability import perf

    if not attribution.program_names_its_parts():
        return set()
    parts = set()
    for key in perf.ledger():
        for entry in (perf.op_names(key) or {}).values():
            for name in [entry["op_name"], *entry["called"]]:
                parts.update(name.split("/"))
    return parts


def _read_layers(run):
    """One reader per per-layer metric BENCHMARK.json lists for the cell.
    A reader that finds nothing to read returns None. On the chip that
    is a fault -- the span, counter or executable it reads was renamed
    or is gone, and the metric would leave the ledger unseen -- so the
    run fails and says which; a rehearsal, which has no device trace,
    leaves the metric out. One answer is no fault: the driver runs the
    PARENT of a PR with the readers the PR adds, and a program that
    predates a scope has nothing under it. A reader whose module names
    the scope it reads (``SCOPE``) is left out of the line where that
    scope is in none of the program's own name maps; where the program
    does name the scope and the reader still finds nothing, the run
    fails as before."""
    values, silent, scopes = {}, [], None
    for metric in run.cell.per_layer:
        reader = manifest.module("layer_metrics", metric["name"])
        value = reader.read(run)
        if value is not None:
            values[metric["name"]] = {"value": float(value),
                                      "unit": metric["unit"]}
            continue
        scope = getattr(reader, "SCOPE", None)
        if scope is not None and not run.rehearsal:
            scopes = program_scopes() if scopes is None else scopes
            if scope not in scopes:
                run.log(f"{metric['name']}: the program predates this "
                        f"metric (no scope {scope!r} in its name maps); "
                        "left out of the line")
                continue
        silent.append(metric["name"])
    if silent and not run.rehearsal:
        raise RuntimeError(
            f"per-layer metric(s) {silent} of {run.cell.name!r} found "
            "nothing to read: what their readers (benchmarks/layer_metrics/)"
            " take from the trace, the program's spans or its counters is "
            "no longer there")
    return values


def run_cell(cell, seed, seconds, trace, t_process_start, out_root,
             rehearsal=False):
    """Returns the result object of the last line (``run.py`` prints it;
    ``rehearse.py`` labels it)."""
    if trace:
        # the program's spans, on for the whole traced run; the ring has
        # to hold every decode.step / decode.token record of a window
        os.environ["MXNET_TPU_OBS_TRACE"] = "1"
        os.environ["MXNET_TPU_OBS_SPAN_RING"] = "1000000"
    devices, rec = device.require_chips(cell.chips, allow_cpu=rehearsal)
    counter = compiles.CompileCounter()
    out_dir = os.path.join(out_root, cell.name, f"seed{seed}_trace{trace}")
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell, seed, seconds, devices, rec, out_dir, trace,
              t_process_start, counter, rehearsal)
    loop = manifest.module("loops", cell.traffic["loop"])
    result = loop.run(run)
    run.end_to_end = dict(result["end_to_end"])
    run.end_to_end["setup_s"] = run.setup_s
    for note in result["notes"]:
        run.log(f"NOT CORRECT: {note}")
    in_window = (run.compile_counts["window_end"]["requests"]
                 - run.compile_counts["setup"]["requests"])
    run.log(f"setup {run.setup_s:.2f} s ({run.compile_counts['setup']}); "
            f"{in_window} compile request(s) inside the window; "
            f"peak {run.peak_bytes} bytes")
    run.log("end to end: " + ", ".join(
        f"{k} {v:.4f}" for k, v in sorted(run.end_to_end.items())))

    dev = dict(rec)
    dev["memory_peak_bytes"] = run.peak_bytes
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "device": dev}
    # what the loop reports besides (a routing's counts), then each
    # number ``correct`` compared, beside its limit: last in the line
    line.update(result.get("reported", {}))
    compared = result.get("compared", {})
    if not trace:
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = sorted(set(units) - set(run.end_to_end))
        if missing:
            raise RuntimeError(f"loop {cell.traffic['loop']!r} did not "
                               f"report {missing}")
        line["metrics"] = {k: {"value": float(run.end_to_end[k]),
                               "unit": units[k]} for k in units}
        line["compared"] = compared
        return line

    path = run.tracer.path()
    if path is None:
        raise RuntimeError("the profiler wrote no .xplane.pb")
    run.trace = xplane.reduce(
        xplane.read(xplane.open_trace(path)),
        (run.tracer.t_start_ns, run.tracer.t_stop_ns),
        run.program_spans(in_window=False))
    if run.trace is not None:
        used = [run.trace["devices"][n]
                for n in sorted(run.trace["devices"])[:len(devices)]]
        dev["busy_s"] = sum(d["busy_s"] for d in used) / len(used)
        dev["window_s"] = run.trace["window_s"]
        line["breakdown"] = xplane.breakdown(run.trace)
        run.log(f"traced window {dev['window_s']:.3f} s, busy "
                f"{dev['busy_s']:.3f} s, clock drift "
                f"{run.trace['clock'].drift_ns / 1e9:.6f} s; {path} "
                f"({os.path.getsize(path)} bytes)")
    elif not rehearsal:
        raise RuntimeError(f"{path} holds no device plane")
    line["metrics"] = _read_layers(run)
    line["compared"] = compared
    return line


def compared_lines(line):
    """The numbers compared, each beside its limit, as lines for the end
    of standard error."""
    return [f"compared {name}: {pair['value']!r} (limit {pair['limit']!r})"
            for name, pair in line.get("compared", {}).items()]


def fail(msg, code=3):
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)
