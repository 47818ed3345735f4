"""Device time per training step, chip 0, of the step's instructions that
have no name of the program's, of their own or inside them: the zero
fills, copies, layout changes, async pairs and compiler kernels
(``ragged-dot-none``) XLA made after jax traced
(``benchmarks/owners.py``). It needs only the map's ``op_name`` and
``called``, so it reads the same on a program from before owners. The
log is its split by instruction kind x the owner's scope x the owner's
phase (the map's ``owner``, ``observability.perf.parse_op_names``),
largest first, each row with its owners' last two name parts; the rows
sum to the value. A PR that removes a fill or a copy moves this and
``step_device_ms`` together; one that only renames moves neither.
Layer: program."""
from benchmarks import owners

SMALL_MS = 0.05     # rows under this are logged as one sum


def read(run):
    found = owners.step(run)
    if found is None:
        return None
    ops, names, n_steps, module = found
    rows, tails, unknown = owners.unnamed_rows(ops, names, n_steps)
    total = sum(rows.values())
    run.log(f"{module}: {total:.3f} ms a step in instructions with no name"
            f" ({unknown} traced op(s) not in the program's map); by kind x"
            " owner's scope x owner's phase"
            + ("" if owners.has_owners(names) else
               " (the program's map has no owners: every row is "
               f"{owners.NO_OWNER})") + ":")
    ordered = sorted(rows.items(), key=lambda kv: -kv[1])
    small = [ms for _, ms in ordered if ms < SMALL_MS]
    for (kind, scope, phase), ms in ordered[:len(ordered) - len(small)]:
        largest = sorted(tails[kind, scope, phase].items(),
                         key=lambda kv: -kv[1])[:2]
        run.log(f"  {kind:24s} {scope:36s} {phase:9s} {ms:9.3f} ms  "
                + ", ".join(f"{tail or '-'} {v:.3f}" for tail, v in largest))
    if small:
        run.log(f"  {len(small)} smaller row(s), each under {SMALL_MS} ms: "
                f"{sum(small):.3f} ms")
    return total
