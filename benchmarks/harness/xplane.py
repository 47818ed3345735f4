"""Reduction of a profiler trace (.xplane.pb) to the numbers the
per-layer readers and the ``breakdown`` use.

Read with ``jax.profiler.ProfileData`` and nothing else. What a trace of
this installation's TPU holds (looked at by hand, PR 22), and what is
taken from it:

- plane ``/device:TPU:<n>``, line ``XLA Ops``: one event per executed
  HLO instruction. The event's name is the instruction's text,
  ``%fusion.12 = bf16[..] fusion(..), kind=kOutput, calls=..``; the
  profiler gives no category, so opcode, fusion kind and custom-call
  target are parsed out of that text. Events nest (a ``while`` holds its
  body), so per-op time is SELF time and busy time is the union;
- the same plane, ``XLA Modules``: one event per run of an executable,
  ``jit_step(<fingerprint>)``; ``Async XLA Ops``: one event per
  asynchronous operation from its start to its done;
- plane ``/host:CPU``: the benchmark's own ``bench.*`` annotations on
  whichever thread wrote them, among them the clock-sync pair of
  ``harness/profile.py``.

Busy time of a chip is the union of its op intervals inside the traced
window; idle share is 1 - busy / window. A collective's time runs from
its start to its done (or is the op itself where it is synchronous); its
EXPOSED part is where no other op runs on that chip. An idle gap is
labelled with what the host was doing at its middle: the innermost of
the benchmark's annotations and of the program's spans (moved onto the
trace's clock) that covers it.

``python benchmarks/harness/xplane.py <file>`` prints what a trace
holds, for looking at one by hand.
"""
from __future__ import annotations

import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE, ASYNC_LINE = "XLA Ops", "XLA Modules", "Async XLA Ops"
SYNC = "bench.clock_sync"

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv")
MOVES = ("copy", "transpose", "reshape", "bitcast", "slice", "dynamic-slice",
         "dynamic-update-slice", "concatenate", "pad", "gather", "scatter",
         "async-start", "async-done")
# a gap this short is the device's own pause between two ops, not the
# host's doing; it is not looked up among the host's spans
SHORT_GAP_NS = 5000.0
BETWEEN_OPS = "between ops on the device (each under 5 us)"
CATEGORIES = ("matmul or convolution fusion", "reduction fusion",
              "elementwise fusion", "copy", "custom call", "collective",
              "other")
KERNEL_TARGET = "tpu_custom_call"      # a Pallas / Mosaic kernel

_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")
_KIND = re.compile(r"kind=(\w+)")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


# ------------------------------------------------------------------ reading

def open_trace(path):
    from jax.profiler import ProfileData

    if path.endswith(".textproto.gz"):      # a committed fixture
        import gzip

        with gzip.open(path, "rt", encoding="utf-8") as f:
            return ProfileData.from_text_proto(f.read())
    if path.endswith(".textproto"):
        with open(path, encoding="utf-8") as f:
            return ProfileData.from_text_proto(f.read())
    return ProfileData.from_file(path)


def parse_op(text):
    """HLO instruction text -> (short name, {"op", "kind", "target"}).
    ``%fusion.12 = bf16[8]{0} fusion(...), kind=kLoop`` gives
    ``("fusion.12", {"op": "fusion", "kind": "kLoop", "target": ""})``;
    a bare name gives itself with its numeric suffix cut as the opcode."""
    short = text.lstrip("%").split(" ", 1)[0]
    _, eq, rest = text.partition(" = ")
    op = _OPCODE.search(" " + rest) if eq else None
    kind = _KIND.search(rest)
    target = _TARGET.search(rest)
    return short, {"op": op.group(1) if op else base_name(short),
                   "kind": kind.group(1) if kind else "",
                   "target": target.group(1) if target else ""}


def _ops(line):
    out = []
    for e in line.events:
        short, meta = parse_op(e.name)
        out.append((short, float(e.start_ns), float(e.duration_ns), meta))
    return out


def read(profile):
    """ProfileData -> ``{"devices": {n: {"ops", "async", "modules"}},
    "host": [...]}``. An op is (short name, start_ns, dur_ns, {"op",
    "kind", "target"}); a module run (name, start_ns, dur_ns, {}); a host
    event (name, start_ns, dur_ns, stats)."""
    out = {"devices": {}, "host": []}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                int(m.group(1)), {"ops": [], "async": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"].extend(_ops(line))
                elif line.name == ASYNC_LINE:
                    dev["async"].extend(_ops(line))
                elif line.name == MODULES_LINE:
                    dev["modules"].extend(
                        (e.name, float(e.start_ns), float(e.duration_ns), {})
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend(
                    (e.name, float(e.start_ns), float(e.duration_ns),
                     dict(e.stats))
                    for e in line.events if e.name.startswith("bench."))
    return out


# ---------------------------------------------------------------- intervals

def union(intervals):
    """Sorted, merged copy of ``[(start, end)]``."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a, b):
    """The parts of merged intervals ``a`` that no interval of merged
    ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(events):
    """Self time of each event of one line, where events nest by time:
    an event's duration less that of the events directly inside it.
    Returns ``[(name, start_ns, self_ns, meta)]`` in start order."""
    order = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    selfs = [ev[2] for ev in order]
    stack = []
    for i, (_, start, dur, _) in enumerate(order):
        while stack and order[stack[-1]][1] + order[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            selfs[stack[-1]] -= dur
        stack.append(i)
    return [(ev[0], ev[1], max(0.0, s), ev[3])
            for ev, s in zip(order, selfs)]


# --------------------------------------------------------------- categories

def base_name(name):
    """``fusion.123`` -> ``fusion``; ``fusion.1480.remat4`` -> ``fusion``."""
    return re.sub(r"[.\d]+(\.(remat|clone)\d*)*$", "", name)


def is_collective(name, meta=None):
    return base_name(name).startswith(COLLECTIVES) \
        or bool(meta) and meta["op"].startswith(COLLECTIVES)


def categorize(name, meta):
    """One of CATEGORIES. XLA names a fusion after the ops at its root
    (``multiply_reduce_fusion``, ``convolution_add_fusion``) and states
    its kind: an output fusion (``kOutput``) is rooted in a matrix
    multiplication or convolution, whatever rides along."""
    base, op = base_name(name), meta["op"]
    if is_collective(name, meta):
        return "collective"
    if op == "custom-call":
        return "custom call" if meta["target"] == KERNEL_TARGET else "other"
    if "reduce" in base or op in ("reduce", "reduce-window"):
        return "reduction fusion"
    if meta["kind"] == "kOutput" or "convolution" in base or "dot" in base \
            or op in ("convolution", "dot"):
        return "matmul or convolution fusion"
    if op.startswith(MOVES) or base.startswith(MOVES) \
            or "dynamic-update-slice" in base or "dynamic_slice" in base:
        return "copy"
    if op == "fusion" and meta["kind"] in ("kLoop", "kInput", ""):
        return "elementwise fusion"
    if op in ("convert", "add", "multiply", "subtract", "divide", "select",
              "broadcast", "iota", "compare", "maximum", "exponential"):
        return "elementwise fusion"
    return "other"


# ---------------------------------------------------------------- the clock

class Clock:
    """perf_counter_ns -> the trace's nanoseconds, from the sync
    annotations (each carries the perf-counter reading taken as it
    opened)."""

    def __init__(self, host_events):
        pairs = [(ev[3]["t_perf_ns"], ev[1]) for ev in host_events
                 if ev[0] == SYNC and "t_perf_ns" in ev[3]]
        if not pairs:
            raise ValueError("the trace holds no clock-sync annotation")
        offsets = sorted(float(t) - s for t, s in pairs)
        self.offset_ns = offsets[len(offsets) // 2]
        self.drift_ns = offsets[-1] - offsets[0]

    def to_trace(self, perf_ns):
        return perf_ns - self.offset_ns


# ------------------------------------------------------------- the reduction

def _collective_spans(ops, async_ops=()):
    """[(start, end)] of every collective. An asynchronous one runs from
    its ``x-start`` to its ``x-done``: read off the async line where the
    profiler wrote it, and paired on the op line by numeric suffix where
    that matches, else with the oldest open start of that kind. Anything
    else is its own span."""
    open_, spans = {}, [(start, start + dur)
                        for name, start, dur, meta in async_ops
                        if is_collective(name, meta)]
    for name, start, dur, meta in sorted(ops, key=lambda ev: ev[1]):
        if not is_collective(name, meta):
            continue
        m = re.match(r"^(.*)-(start|done)([.\d]*)$", name)
        if m and m.group(2) == "start":
            open_.setdefault(m.group(1), []).append((m.group(3), start))
            spans.append((start, start + dur))
        elif m and open_.get(m.group(1)):
            waiting = open_[m.group(1)]
            i = next((i for i, (sfx, _) in enumerate(waiting)
                      if sfx == m.group(3)), 0)
            spans.append((waiting.pop(i)[1], start + dur))
        else:
            spans.append((start, start + dur))
    return union(spans)


def _label_at(t, labelled):
    """Innermost (shortest) labelled interval that covers ``t``."""
    best = None
    for name, s, e in labelled:
        if s <= t < e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no benchmark or program span"


def reduce(trace, window_perf_ns=None, program_spans=()):
    """The summary the readers use. ``window_perf_ns`` is the traced
    window as (start, stop) perf-counter readings; without it (a fixture
    that has no sync pair) the window runs from the first device event to
    the last. ``program_spans``: the program's span records (``name``,
    ``t0_ns``, ``dur_ns`` on the perf counter)."""
    devices = trace["devices"]
    if not devices:
        return None
    clock = None
    if window_perf_ns is not None:
        clock = Clock(trace["host"])
        lo, hi = (clock.to_trace(t) for t in window_perf_ns)
    else:
        every = [ev for d in devices.values()
                 for ev in d["ops"] + d["modules"]]
        lo = min(ev[1] for ev in every)
        hi = max(ev[1] + ev[2] for ev in every)
    labelled = [(ev[0], ev[1], ev[1] + ev[2]) for ev in trace["host"]
                if ev[0] != SYNC]
    if clock is not None:
        # decode.token is a record of a gap between tokens, written after
        # the fact, not something the host was doing
        labelled += [(s["name"], clock.to_trace(s["t0_ns"]),
                      clock.to_trace(s["t0_ns"] + s["dur_ns"]))
                     for s in program_spans if s["name"] != "decode.token"]
    labelled = [(name, s, e) for name, s, e in labelled if e > lo and s < hi]

    per_device = {}
    for n, dev in sorted(devices.items()):
        ops = [ev for ev in dev["ops"] if ev[1] + ev[2] > lo and ev[1] < hi]
        busy = clip(union([(ev[1], ev[1] + ev[2]) for ev in ops]), lo, hi)
        coll = clip(_collective_spans(ops, dev.get("async", ())), lo, hi)
        compute = clip(union([(ev[1], ev[1] + ev[2]) for ev in ops
                              if not is_collective(ev[0], ev[3])]), lo, hi)
        by_op, by_cat, op_selfs = {}, {c: 0.0 for c in CATEGORIES}, []
        for name, start, self_ns, meta in self_times(ops):
            cat = categorize(name, meta)
            by_cat[cat] += self_ns
            op_selfs.append((start, name, cat, self_ns))
            seen = by_op.setdefault((name, cat), [0.0, 0])
            seen[0] += self_ns
            seen[1] += 1
        idle_by = {}
        for s, e in subtract([(lo, hi)], busy):
            label = BETWEEN_OPS if e - s < SHORT_GAP_NS \
                else _label_at((s + e) / 2, labelled)
            seen = idle_by.setdefault(label, [0.0, 0, 0.0])
            seen[0] += e - s
            seen[1] += 1
            seen[2] = max(seen[2], e - s)
        per_device[n] = {
            "busy_s": total(busy) / 1e9,
            "busy": busy,
            "collectives": coll,
            "compute": compute,
            "op_selfs": op_selfs,
            "collective_s": total(coll) / 1e9,
            "collective_exposed_s": total(subtract(coll, compute)) / 1e9,
            "category_s": {c: v / 1e9 for c, v in by_cat.items()},
            "ops": sorted(((k[0], k[1], v[0] / 1e9, v[1])
                           for k, v in by_op.items()),
                          key=lambda r: (-r[2], r[0])),
            "idle_by": sorted(((k, v[0] / 1e9, v[1], v[2] / 1e9)
                               for k, v in idle_by.items()),
                              key=lambda r: (-r[1], r[0])),
            "modules": [(ev[0], ev[1], ev[1] + ev[2]) for ev in dev["modules"]
                        if ev[1] >= lo and ev[1] + ev[2] <= hi],
        }
    return {"window_s": (hi - lo) / 1e9, "devices": per_device,
            "clock": clock}


def step_runs(dev):
    """Complete runs inside the window of the executable that takes most
    of the chip's time (the training step): ``[(start, end)]``."""
    by_name = {}
    for name, s, e in dev["modules"]:
        by_name.setdefault(name, []).append((s, e))
    if not by_name:
        return []
    return sorted(max(by_name.values(), key=total))


def per_step(dev, want):
    """Self time (s) per step of the ops ``want(name, category)`` picks,
    over the complete steps of the window; None without step runs."""
    runs = step_runs(dev)
    if not runs:
        return None
    lo, hi = runs[0][0], runs[-1][1]
    picked = sum(self_ns for start, name, cat, self_ns in dev["op_selfs"]
                 if lo <= start < hi and want(name, cat))
    return picked / 1e9 / len(runs)


def breakdown(summary, chip=0, top=10):
    """The ``breakdown`` of the result line, from one chip: the ops that
    took most self time, and idle time by what the host was doing."""
    dev = summary["devices"][chip]
    return {
        "device_ops": [[f"{name} [{cat}] x{count}", secs]
                       for name, cat, secs, count in dev["ops"][:top]],
        "idle_gaps": [[f"{label} ({count} gaps, longest {longest * 1e3:.3f} "
                       "ms)", secs]
                      for label, secs, count, longest in dev["idle_by"][:top]],
    }


def describe(profile, head=6):
    """What a trace holds, for looking at one by hand."""
    for plane in profile.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} line(s)")
        for line in lines:
            events = list(line.events)
            names = {}
            for e in events:
                b = base_name(parse_op(e.name)[0])
                names[b] = names.get(b, 0) + 1
            top = sorted(names.items(), key=lambda kv: -kv[1])[:12]
            print(f"  LINE {line.name!r}: {len(events)} event(s); {top}")
            for e in events[:head]:
                print(f"    {e.name[:300]!r} start {e.start_ns:.0f} dur "
                      f"{e.duration_ns:.0f} {dict(e.stats)}")


if __name__ == "__main__":
    describe(open_trace(sys.argv[1]))
