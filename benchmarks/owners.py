"""Device time of the ops the program's names leave out, put down to the
part each was made for: what the readers of ``step_owned_share`` and
``unnamed_ms_per_step`` share. (It sits beside ``attribution.py``, whose
``step_names``, ``phase_of``, ``blocks_of`` and ``attribute`` decide
what a name means: this file only says which name to ask about.)

**No name.** An instruction has none where its ``op_name`` is not jax's
name stack (no ``/``: "" for what XLA made itself, or the bare op name
a compiler pass gives, ``ragged-dot-none``) and no instruction inside
it has one: the zero fills, copies, layout changes, async pairs and
compiler kernels XLA made after jax traced. That needs only the map's
``op_name`` and ``called``, so it reads on a program from before
owners too.

**Owner.** Since PR 40 the program's map (``observability.perf.
parse_op_names``) gives each such instruction an ``owner``: the name of
the named instruction it was made for, found by walking the optimised
HLO's def-use graph to the nearest named user (``via`` ``"user"``) or,
failing that, the nearest named operand (``"operand"``). A program
from before that has no ``owner`` field: every instruction then has no
owner, and ``step_owned_share`` reads what ``step_attributed_share``
reads.

**Scope of an owner** (for the log's table): the innermost of the
program's mechanism scopes in it (``SCOPES``), else ``optimizer``, else
the innermost gluon block, else "outside any block".
"""
from __future__ import annotations

from benchmarks import attribution
from benchmarks.harness import layers, xplane

SCOPES = ("moe_experts", "moe_router", "moe", "linear_attention",
          "window_attention", "attention", "optimizer")
NO_OWNER = "(no owner)"


def nameless(entry):
    """No name of the program's, of its own or inside it."""
    return "/" not in entry["op_name"] \
        and not any("/" in c for c in entry["called"])


def has_owners(names):
    """False for a map from before PR 40 (no ``owner`` field)."""
    return any("owner" in entry for entry in names.values())


def owned(names):
    """A copy of the map in which each instruction with no name and an
    owner carries its owner as its ``op_name``."""
    return {k: dict(e, op_name=e["owner"]) if e.get("owner")
            and nameless(e) else e for k, e in names.items()}


def scope_of(op_name):
    parts = op_name.split("/")
    for part in reversed(parts):
        if part in SCOPES:
            return part
    blocks = attribution.blocks_of(op_name)
    return blocks[-1] if blocks else "outside any block"


def step(run):
    """``(ops, names, n_steps, module)`` of chip 0's complete steps,
    computed once a run; None where there is nothing to read (no device
    trace, no complete step, no map of the step's executable)."""
    if "owners.step" in run.facts:
        return run.facts["owners.step"]
    found = None
    dev = layers.chip(run)
    runs = xplane.step_runs(dev) if dev is not None else []
    if runs and attribution.program_names_its_parts():
        lo, hi = runs[0][0], runs[-1][1]
        module = next(name for name, s, e in dev["modules"]
                      if (s, e) == runs[0])
        ops = [op for op in dev["op_selfs"] if lo <= op[0] < hi]
        names = attribution.step_names(module, {op[1] for op in ops})
        if names is not None:
            found = (ops, names, len(runs), module)
    run.facts["owners.step"] = found
    return found


def self_ns(ops):
    """{instruction: self time over the window's complete steps}."""
    out = {}
    for _, instruction, _, ns in ops:
        out[instruction] = out.get(instruction, 0.0) + ns
    return out


def unnamed_rows(ops, names, n_steps):
    """``({(kind, scope, phase): ms a step}, {(kind, scope, phase):
    {owner's last two name parts: ms a step}}, instructions not in the
    map)`` of the instructions with no name; the rows sum to the time
    of all of them. An instruction with no owner is under
    ``NO_OWNER``."""
    rows, tails, unknown = {}, {}, 0
    for instruction, ns in self_ns(ops).items():
        entry = names.get(instruction)
        if entry is None:
            unknown += 1
            continue
        if not nameless(entry):
            continue
        owner = entry.get("owner", "")
        if owner:
            key = (xplane.base_name(instruction), scope_of(owner),
                   attribution.phase_of(instruction, owner))
            tail = "/".join(owner.split("/")[-2:])
        else:
            key = (xplane.base_name(instruction), NO_OWNER,
                   attribution.REST)
            tail = ""
        ms = ns / n_steps / 1e6
        rows[key] = rows.get(key, 0.0) + ms
        seen = tails.setdefault(key, {})
        seen[tail] = seen.get(tail, 0.0) + ms
    return rows, tails, unknown
