"""Device time under the scopes the program opens around its newer
mechanisms -- ``linear_attention``, ``moe``, ``moe_router``,
``moe_experts`` -- by phase: what the readers of ``linear_attention_*``
and ``moe_*`` share. (It sits beside ``attribution.py``, which decides
what a name means and is the benchmark's own: this file calls its
``step_names`` and ``phase_of`` and adds only the question "is this
scope in the name".)

The join is ``attribution.py``'s: the traced window's complete runs of
the step's executable on chip 0, each instruction's self time
(``xplane.reduce``), the program's own map from instruction to
``op_name`` (``observability.perf.op_names``). An instruction counts
under a scope when the scope is one of the parts of its ``op_name``
(its root's; where it has none, the last name inside it), so a fusion
counts once. Forward means the forward pass proper; what a
``contrib.nn.Remat`` layer recomputes runs in the backward pass and
counts there, as everywhere in the benchmark.

One instruction the program cannot name: the TPU compiler turns
``jax.lax.ragged_dot`` into a grouped-matmul kernel of its own and
names it ``ragged-dot-<kind>``, dropping the program's name stack. The
expert layer's grouped products are the program's only ones, so such an
instruction counts under ``moe`` and ``moe_experts``, in the phase of
the named instruction that ran just before it in the step (the gather
or the activation that feeds it).

A program that has no such scope -- the parent of the PR that added
them, or a model without these layers -- has nothing to read: the
readers return None and the line leaves the metric out.
"""
from __future__ import annotations

from benchmarks import attribution
from benchmarks.harness import layers, xplane

SCOPES = ("linear_attention", "moe", "moe_router", "moe_experts")
PHASES = ("forward", "backward")
_GROUPED = "ragged-dot"             # the compiler's name for ragged_dot
_GROUPED_SCOPES = ("moe", "moe_experts")
TOP = 6     # instructions a scope and phase logged, largest first


def by_scope(op_selfs, names, n_steps):
    """({(scope, phase): ms a step}, {(scope, phase): [(the last two
    parts of an op_name, ms a step)] largest first, for the log}) from
    ``[(start, instruction, category, self_ns)]`` of the complete steps
    and the program's map; only scopes that some instruction lies under
    appear."""
    ns, tails = {}, {}
    phase_before = None     # of the last named instruction that ran
    for _, instruction, _, self_ns in sorted(op_selfs):
        entry = names.get(instruction)
        if entry is None:
            continue
        op_name = entry["op_name"] or (entry["called"][-1]
                                       if entry["called"] else "")
        if xplane.base_name(instruction).startswith(_GROUPED):
            phase, under = phase_before, _GROUPED_SCOPES
            tail = xplane.base_name(instruction)
        else:
            phase = attribution.phase_of(instruction, op_name)
            parts = op_name.split("/")
            under = [scope for scope in SCOPES if scope in parts]
            tail = "/".join(parts[-2:])
            if phase in PHASES:
                phase_before = phase
        if phase not in PHASES:
            continue
        for scope in under:
            ns[scope, phase] = ns.get((scope, phase), 0.0) + self_ns
            seen = tails.setdefault((scope, phase), {})
            seen[tail] = seen.get(tail, 0.0) + self_ns
    top = {key: sorted(((tail, v / n_steps / 1e6)
                        for tail, v in seen.items()),
                       key=lambda kv: -kv[1])[:TOP]
           for key, seen in tails.items()}
    return {key: value / n_steps / 1e6 for key, value in ns.items()}, top


def of_run(run):
    """``by_scope`` of chip 0's traced steps, computed once a run; None
    where there is nothing to read (no device trace, no complete step, a
    program without the map)."""
    if "scopes" in run.facts:
        return run.facts["scopes"]
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
            found, top = by_scope(ops, names, len(runs))
            run.log("ms a step under the program's scopes: " + ", ".join(
                f"{scope} {phase} {ms:.3f}"
                for (scope, phase), ms in sorted(found.items())))
            for (scope, phase), rows in sorted(top.items()):
                run.log(f"  {scope} {phase}, largest: " + ", ".join(
                    f"{tail} {ms:.3f}" for tail, ms in rows))
    run.facts["scopes"] = found
    return found


def scope_ms(run, scope, phase):
    """ms a step of ``phase`` ops under ``scope``; None where the
    program has no op under that scope at all."""
    found = of_run(run)
    if not found or not any(key[0] == scope for key in found):
        return None
    return found.get((scope, phase), 0.0)


def expert_tokens(run):
    """The expert layers' token counts of the last step, as the model
    file hands them out; None where the model has no such layers."""
    read = getattr(run.model, "expert_tokens", None)
    return read() if read is not None else None
