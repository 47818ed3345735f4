#!/usr/bin/env python3
"""Does a training cell's check fail when the program computes worse?

    python3 benchmarks/degrade.py --workload <training cell> --seed <n>

The proof that ``correct`` has teeth, run on the chip when a check or a
tolerance is set (PERF.md records the runs). The cell's system is built
as ``run.py`` builds it; then the program's weights -- the trainer's and
those of the forward pass the check probes, not the reference's -- are
rounded to 3 bits of mantissa (an 8-bit float with bf16's exponent: what
a quantised copy of the weights would hold). One step and the cell's own
check follow. A check that can see the trunk says NOT CORRECT: exit 0
then, and 1 if the degraded program passed. Weights at 4, 5 and 6 bits
are then put through the check's forward pass only and reported, to show
where the check stops seeing. No time is measured and no result line is
printed. Without a chip it exits 3, as ``run.py``
does; ``rehearse.py --degrade`` runs it at the tiny CPU preset.

    python3 benchmarks/degrade.py --workload <training cell> --seed <n> \
        --program-key sliding_window=8192

plants a fault instead: the PROGRAM is built from the configuration
with the keys given changed, the reference from the file's own, and the
cell's check has to say NOT CORRECT (a window layer that sees every
earlier key is the reading a largest-logit limit is held under).
"""
from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

WIDTHS = (3, 4, 5, 6)   # bits of mantissa; the first has to be caught


def rounder(bits):
    """float array -> its values at ``bits`` bits of mantissa (of bf16's
    seven), nearest, in the array's own type."""
    import jax
    import jax.numpy as jnp

    drop = 7 - bits

    def lower(v):
        if not jnp.issubdtype(v.dtype, jnp.floating):
            return v
        raw = jax.lax.bitcast_convert_type(
            v.astype(jnp.bfloat16), jnp.uint16).astype(jnp.uint32)
        raw = ((raw + (1 << (drop - 1))) >> drop) << drop
        return jax.lax.bitcast_convert_type(
            raw.astype(jnp.uint16), jnp.bfloat16).astype(v.dtype)

    return jax.jit(lower)


def _built(cell, seed, allow_cpu, program_keys=None):
    """(job, first batch) as the loop has them before the first step:
    built, the ring made, what the traffic asks of the job before the
    warm-up (a balanced routing) done. With ``program_keys`` the program
    is built from the configuration with those keys changed and the
    reference keeps the sizes of the file's own."""
    from benchmarks.harness import device, manifest

    devices, rec = device.require_chips(cell.chips, allow_cpu=allow_cpu)
    model = manifest.module("models", cell.config["model"])
    reference = manifest.module("references", cell.config["reference"])
    config, sizes = cell.config, getattr(model, "reference_sizes", None)
    if program_keys:
        config = {**cell.config, **program_keys}
        if sizes is not None:
            model.reference_sizes = lambda _: sizes(cell.config)
    try:
        job = model.build_trainer(config, cell.traffic, seed, devices,
                                  reference)
    finally:
        if sizes is not None:
            model.reference_sizes = sizes
    ring = job.make_ring(seed, manifest.module(
        "loops", cell.traffic["loop"]).ring_of(cell.traffic))
    job.prepare(ring, cell.traffic,
                lambda msg: print(f"[{cell.name}] {msg}", flush=True))
    return job, ring[0]


def faulted_check(cell, seed, program_keys, allow_cpu=False):
    """True if the cell's check catches the program built with
    ``program_keys`` in place of the configuration's."""
    job, (x, y) = _built(cell, seed, allow_cpu, program_keys)
    checked = job.check(float(job.step(x, y)), x, y, seed)
    print(f"[{cell.name}] the program built with {program_keys}: "
          f"{checked['said']}")
    for note in checked["notes"]:
        print(f"[{cell.name}] NOT CORRECT: {note}")
    print(f"[{cell.name}] the faulted program "
          + ("is caught" if checked["notes"] else "PASSED the check"),
          flush=True)
    return bool(checked["notes"])


def degraded_check(cell, seed, allow_cpu=False):
    """True if the cell's check catches the program at ``WIDTHS[0]``."""
    job, (x, y) = _built(cell, seed, allow_cpu)
    lower = rounder(WIDTHS[0])
    job.trainer.params = {k: lower(v) for k, v in job.trainer.params.items()}
    first = float(job.step(x, y))       # taken once: it trains

    def forward_only(label, checked):
        seen = [n for n in checked["notes"] if "forward pass" in n]
        print(f"[{cell.name}] weights {label}, forward pass only: "
              + ", ".join(f"{k} {v:.6f}" for k, v in checked.items()
                          if k.startswith(("logits", "statistics")))
              + (": caught" if seen else ": not seen"))

    forward_only("as they are", job.check(first, x, y, seed))
    for bits in WIDTHS:
        lower = rounder(bits)
        checked = job.check(
            first, x, y, seed,
            params={k: lower(v) for k, v in job.initial_params().items()})
        if bits != WIDTHS[0]:
            forward_only(f"at {bits} bits of mantissa", checked)
            continue
        caught = bool(checked["notes"])
        print(f"[{cell.name}] weights at {bits} bits of mantissa: "
              f"{checked['said']}")
        for note in checked["notes"]:
            print(f"[{cell.name}] NOT CORRECT: {note}")
    print(f"[{cell.name}] the degraded program "
          + ("is caught" if caught else "PASSED the check"), flush=True)
    return caught


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--program-key", action="append", default=[],
                    metavar="KEY=JSON", help="plant a fault: build the "
                    "program, not the reference, with this key changed")
    args = ap.parse_args(argv)

    import json

    from benchmarks.harness import cell as cell_mod
    from benchmarks.harness import device, manifest

    cell = manifest.Cell(manifest.load(), args.workload)
    keys = {k: json.loads(v) for k, v in
            (pair.split("=", 1) for pair in args.program_key)}
    try:
        if keys:
            return 0 if faulted_check(cell, args.seed, keys) else 1
        return 0 if degraded_check(cell, args.seed) else 1
    except device.NoChip as e:
        cell_mod.fail(str(e))


if __name__ == "__main__":
    sys.exit(main())
