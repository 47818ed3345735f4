"""Share of the device's busy time spent in prefill executables. A run
of an executable on the chip belongs to the program span
(``decode.prefill`` or ``decode.step``) that was the last to start before
it: the engine thread issues one call and waits for it before the next.
Spans are moved onto the trace's clock by the sync pair. Layer: engine."""
from benchmarks.harness import layers, xplane


def read(run):
    dev = layers.chip(run)
    if dev is None or run.trace["clock"] is None or not dev["modules"]:
        return None
    clock = run.trace["clock"]
    calls = sorted((clock.to_trace(s["t0_ns"]), s["name"])
                   for s in run.program_spans()
                   if s["name"] in ("decode.prefill", "decode.step"))
    if not calls:
        return None
    busy = {"decode.prefill": 0.0, "decode.step": 0.0}
    i = 0
    for _, start, end in sorted(dev["modules"], key=lambda m: m[1]):
        while i + 1 < len(calls) and calls[i + 1][0] <= start:
            i += 1
        if calls[i][0] <= start:
            busy[calls[i][1]] += xplane.total(
                xplane.clip(dev["busy"], start, end))
    both = sum(busy.values())
    return 100.0 * busy["decode.prefill"] / both if both else None
