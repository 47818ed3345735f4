#!/usr/bin/env python3
"""CPU rehearsal of one cell: the same code path at a tiny preset.

    python3 benchmarks/rehearse.py --workload <name> [--seconds 4] [--trace 1]
    python3 benchmarks/rehearse.py --workload <training cell> --degrade

Costs no chip time and finds wrong paths, arguments, shapes and meshes
before a chip call does. It pins jax to the CPU (with as many virtual
devices as the cell has chips), overrides the cell's sizes with
``benchmarks/rehearsal/<workload>.json`` -- a file only this entry reads,
never ``run.py`` -- and runs ``harness.cell.run_cell`` as the chip run
does, float32 reference and logits checks included. A CPU run says
nothing about the device: the line it prints is labelled a rehearsal
and carries the names of the metrics it would report, not their values.
``--degrade`` rehearses ``degrade.py`` instead: the preset's check has to
catch the program with its weights at 3 bits of mantissa.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, default=4)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--degrade", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks.harness import manifest

    cell = manifest.Cell(manifest.load(pending=True),
                         args.workload).rehearse()
    os.environ["JAX_PLATFORMS"] = "cpu"
    if cell.chips > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell.chips}")

    if args.degrade:
        from benchmarks import degrade

        return 0 if degrade.degraded_check(cell, args.seed,
                                           allow_cpu=True) else 1

    from benchmarks.harness import cell as cell_mod

    line = cell_mod.run_cell(cell, args.seed, args.seconds, args.trace,
                             T_PROCESS_START,
                             os.path.join(ROOT, ".bench_out", "rehearsal"),
                             rehearsal=True)
    print("CPU REHEARSAL, not a chip result: " + json.dumps({
        "rehearsal": True, "workload": cell.name,
        "correct": line["correct"], "attempted": line["attempted"],
        "failed": line["failed"], "would_report": sorted(line["metrics"]),
        "device": {k: line["device"][k]
                   for k in ("platform", "kind", "count")}}), flush=True)
    return 0 if line["correct"] and not line["failed"] else 1


if __name__ == "__main__":
    sys.exit(main())
