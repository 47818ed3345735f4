#!/usr/bin/env python3
"""Find a serving cell's knee: one process, several offered rates.

    python3 benchmarks/sweep.py --workload <serving cell> \
        --rates 4,6,8,10,12 --seconds 20 [--seed 0]

Run once, on the chip, when a cell is defined (or by a later benchmark
PR when an optimisation has moved the knee); the rate a cell then runs
at is a fixed number in its traffic file. The system is built and
warmed up once, so the rates share one compile; each rate gets a fresh
batcher and the cell's own traffic at that rate for ``--seconds``, from
an empty system. The knee is the highest rate with no growing backlog:
requests still unanswered when the window closes stay near zero and the
first tokens of the window's second half are no later than the first
half's. A window has to be several request lifetimes long to show it.
Without a chip it exits 3, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import compiles, device, manifest, stats

    cell = manifest.Cell(manifest.load(pending=True), args.workload)
    try:
        devices, rec = device.require_chips(cell.chips)
    except device.NoChip as e:
        cell_mod.fail(str(e))
    out_dir = os.path.join(ROOT, ".bench_out", "sweep", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    run = cell_mod.Run(cell, args.seed, args.seconds, devices, rec, out_dir,
                       0, T_PROCESS_START, compiles.CompileCounter())
    loop = manifest.module("loops", cell.traffic["loop"])
    job = run.model.build_server(cell.config, cell.traffic, args.seed,
                                 devices, run.reference)
    loop.warm_up(job.predictor)
    print(f"[{cell.name}] built and warm in "
          f"{time.perf_counter() - T_PROCESS_START:.1f} s on {rec}",
          flush=True)

    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic,
                       arrivals=dict(cell.traffic["arrivals"],
                                     rate_per_s=rate))
        seen = loop.serve_window(job, run, traffic, args.seconds)
        n = len(seen["schedule"])
        half = seen["t0"] + args.seconds / 2
        first = [t for t, due in zip(seen["ttft_s"], seen["schedule"].due_s)
                 if seen["t0"] + due < half]
        second = [t for t, due in zip(seen["ttft_s"], seen["schedule"].due_s)
                  if seen["t0"] + due >= half]

        def ms(values, q):
            v = stats.percentile(values, q)
            return None if v is None or v == math.inf else v * 1e3

        window = seen["t1"] - seen["t0"]
        row = {"rate_per_s": rate, "offered": n, "failed": seen["failed"],
               "completed_per_s": seen["done_requests"] / window,
               "out_tok_per_s": seen["tokens"] / window,
               "unanswered_at_close": seen["unanswered_at_close"],
               "running_at_close": seen["running_at_close"],
               "ttft_p50_ms_first_half": ms(first, 50),
               "ttft_p50_ms_second_half": ms(second, 50),
               "ttft_p50_ms": ms(seen["ttft_s"], 50),
               "ttft_p90_ms": ms(seen["ttft_s"], 90),
               "itl_p50_ms": ms(seen["itl_s"], 50),
               "itl_p99_ms": ms(seen["itl_s"], 99),
               "decode_steps": seen["engine"]["decode_steps"],
               "step_period_ms": window / max(
                   1, seen["engine"]["decode_steps"]) * 1e3,
               "preemptions": seen["engine"]["decode_preemptions"],
               "backpressure": seen["engine"]["decode_backpressure"],
               "pages_inuse_peak": seen["engine"]["decode_pages_inuse_peak"],
               "peak_bytes": run.peak_bytes}
        rows.append(row)
        print(f"[{cell.name}] " + json.dumps(row), flush=True)
    with open(os.path.join(out_dir, "sweep.json"), "w",
              encoding="utf-8") as f:
        json.dump({"workload": cell.name, "device": rec,
                   "seconds": args.seconds, "seed": args.seed,
                   "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
