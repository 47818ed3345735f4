#!/usr/bin/env python3
"""One run of one benchmark cell, in this process, on this machine's chips.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in BENCHMARK.json; inputs and
weights are made from ``--seed``. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer
metrics with ``--trace 1``), ``device``, traced ``breakdown``, and last
``compared``: each number ``correct`` compared beside its limit, which
are also the last lines of standard error. Everything else goes on
earlier lines or under ``.bench_out/``.

Without an accelerator, or with fewer chips than the cell asks for, it
exits 3 and prints no result. The compile cache is the program's own
(``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set):
only the first run of a cell in a checkout compiles.
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
# libtpu otherwise writes its logs to the fixed /tmp/tpu_logs, which two
# checkouts measured side by side would share
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import device, manifest

    try:
        cell = manifest.Cell(manifest.load(), args.workload)
    except manifest.ManifestError as e:
        cell_mod.fail(str(e), code=2)
    try:
        line = cell_mod.run_cell(cell, args.seed, args.seconds, args.trace,
                                 T_PROCESS_START,
                                 os.path.join(ROOT, ".bench_out"))
    except device.NoChip as e:
        cell_mod.fail(str(e), code=3)
    for said in cell_mod.compared_lines(line):
        print(said, file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
