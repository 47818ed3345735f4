"""A trace in the reduction's own form, written as an XSpace text proto.

``jax.profiler.ProfileData.from_text_proto`` reads it back through the
same code that reads a recorded ``.xplane.pb``. Two users: the fixture
under ``benchmarks/fixtures/`` (a window of a real chip trace, cut to
the planes, lines and fields the reduction reads, small enough to
commit), and tests that build a trace with a known answer.

An op's name is written as the shortest HLO text that parses to the same
fields: ``%fusion.12 = x fusion(), kind=kOutput``.

    python benchmarks/harness/xplane_text.py <in.xplane.pb> <out.textproto> \
        [first_module_run last_module_run]

cuts a recorded trace to the given runs of its busiest executable (all
of it without them).
"""
from __future__ import annotations

import os
import sys


def _quote(text):
    return '"' + str(text).replace("\\", "\\\\").replace('"', '\\"') + '"'


def op_text(short, meta):
    text = f"%{short} = x {meta['op']}()"
    if meta["kind"]:
        text += f", kind={meta['kind']}"
    if meta["target"]:
        text += f', custom_call_target="{meta["target"]}"'
    return text


class _Plane:
    def __init__(self, plane_id, name):
        self.id, self.name = plane_id, name
        self.events, self.stats, self.lines = {}, {}, []

    def _id(self, table, name):
        return table.setdefault(name, len(table) + 1)

    def add_line(self, name, events, t0_ns, as_ops=False):
        rows = []
        for ev_name, start, dur, extra in events:
            text = op_text(ev_name, extra) if as_ops else ev_name
            fields = [f"metadata_id: {self._id(self.events, text)}",
                      f"offset_ps: {int(round((start - t0_ns) * 1000))}",
                      f"duration_ps: {int(round(dur * 1000))}"]
            for key, value in ({} if as_ops else extra).items():
                if isinstance(value, (int, float)):
                    fields.append(
                        f"stats {{ metadata_id: "
                        f"{self._id(self.stats, key)} int64_value: "
                        f"{int(value)} }}")
            rows.append("    events { " + " ".join(fields) + " }")
        self.lines.append(
            f"  lines {{\n    id: {len(self.lines) + 1}\n    name: "
            f"{_quote(name)}\n    timestamp_ns: {int(t0_ns)}\n"
            + "\n".join(rows) + "\n  }")

    def text(self):
        meta = [f"  event_metadata {{ key: {i} value {{ id: {i} name: "
                f"{_quote(n)} }} }}" for n, i in self.events.items()]
        meta += [f"  stat_metadata {{ key: {i} value {{ id: {i} name: "
                 f"{_quote(n)} }} }}" for n, i in self.stats.items()]
        return (f"planes {{\n  id: {self.id}\n  name: {_quote(self.name)}\n"
                + "\n".join(self.lines + meta) + "\n}")


def to_text_proto(trace):
    """``xplane.read``'s form -> XSpace text proto. Times are kept to the
    picosecond, relative to the earliest event."""
    every = [ev for dev in trace["devices"].values()
             for ev in dev["ops"] + dev["modules"]] + list(trace["host"])
    t0 = min(ev[1] for ev in every)
    planes = []
    for n, dev in sorted(trace["devices"].items()):
        plane = _Plane(len(planes) + 1, f"/device:TPU:{n}")
        plane.add_line("XLA Modules", dev["modules"], t0)
        plane.add_line("XLA Ops", dev["ops"], t0, as_ops=True)
        if dev.get("async"):
            plane.add_line("Async XLA Ops", dev["async"], t0, as_ops=True)
        planes.append(plane)
    if trace["host"]:
        plane = _Plane(len(planes) + 1, "/host:CPU")
        plane.add_line("benchmark", trace["host"], t0)
        planes.append(plane)
    return "\n".join(p.text() for p in planes) + "\n"


def cut(trace, first, last):
    """Keep the runs ``first..last`` (inclusive) of each chip's busiest
    executable and the events inside their span."""
    out = {"devices": {}, "host": []}
    lo = hi = None
    for n, dev in trace["devices"].items():
        by_name = {}
        for ev in dev["modules"]:
            by_name.setdefault(ev[0], []).append(ev)
        runs = sorted(max(by_name.values(),
                          key=lambda evs: sum(e[2] for e in evs)),
                      key=lambda ev: ev[1])[first:last + 1]
        lo, hi = runs[0][1], runs[-1][1] + runs[-1][2]

        def inside(ev):
            return ev[1] >= lo and ev[1] + ev[2] <= hi

        out["devices"][n] = {
            "modules": runs,
            "ops": [ev for ev in dev["ops"] if inside(ev)],
            "async": [ev for ev in dev.get("async", ()) if inside(ev)]}
    out["host"] = [ev for ev in trace["host"]
                   if ev[1] + ev[2] >= lo and ev[1] <= hi]
    return out


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from benchmarks.harness import xplane

    recorded = xplane.read(xplane.open_trace(sys.argv[1]))
    if len(sys.argv) > 3:
        recorded = cut(recorded, int(sys.argv[3]), int(sys.argv[4]))
    with open(sys.argv[2], "w", encoding="utf-8") as f:
        f.write(to_text_proto(recorded))
