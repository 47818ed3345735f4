"""Share of the step's device time that the program's names put down to
forward, backward or optimizer once each instruction with no name of
its own counts under its owner: ``attribution.attribute`` on a copy of
the step's map in which such an instruction's ``op_name`` is its
``owner`` (``observability.perf.parse_op_names``; the walk and the
rules are in ``benchmarks/owners.py``), (the three) /
``step_device_ms``. On a program whose map has no owners it reads what
``step_attributed_share`` reads, and says so. The log splits what is
still not in the three phases: named outside them (casts before
``value_and_grad``, the fingerprint fold), owned outside them, and no
name and no owner, each by instruction. Layer: program."""
from benchmarks import attribution, owners
from benchmarks.harness import manifest

TOP = 8     # instructions logged a part, largest first


def read(run):
    found = owners.step(run)
    step_ms = manifest.module("layer_metrics", "step_device_ms").read(run)
    if found is None or not step_ms:
        return None
    ops, names, n_steps, module = found
    if not owners.has_owners(names):
        run.log("the program's map has no owners (older than PR 40): "
                "step_owned_share reads step_attributed_share")
    att = attribution.attribute(ops, owners.owned(names), n_steps)
    share = 100.0 * sum(att.ms_per_step(p)
                        for p in attribution.PHASES) / step_ms
    parts = {"named outside the phases": {}, "owned outside the phases": {},
             "no name and no owner": {}}
    for instruction, ns in owners.self_ns(ops).items():
        entry = names.get(instruction)
        if entry is None:
            part = "no name and no owner"
        elif not owners.nameless(entry):
            op_name = entry["op_name"] or entry["called"][-1]
            if attribution.phase_of(instruction, op_name) \
                    != attribution.REST:
                continue
            part = "named outside the phases"
        elif entry.get("owner"):
            if attribution.phase_of(instruction, entry["owner"]) \
                    != attribution.REST:
                continue
            part = "owned outside the phases"
        else:
            part = "no name and no owner"
        base = parts[part]
        base[instruction] = base.get(instruction, 0.0) + ns / n_steps / 1e6
    run.log(f"{module}: {share:.3f} % of {step_ms:.3f} ms owned by forward, "
            "backward or optimizer: " + ", ".join(
                f"{p} {att.ms_per_step(p):.3f}"
                for p in attribution.PHASES + (attribution.REST,)))
    for part, seen in parts.items():
        total = sum(seen.values())
        run.log(f"  {part}: {total:.3f} ms, {100 * total / step_ms:.3f} % of "
                f"the step, {len(seen)} instruction(s); largest: " + ", ".join(
                    f"{k} {v:.3f}" for k, v in sorted(
                        seen.items(), key=lambda kv: -kv[1])[:TOP]))
    return share
