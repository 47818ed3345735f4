"""Device time under the scope ``ssm``, which ``contrib.nn.Mamba2Mixer``
opens around everything of a Mamba-2 layer but its two projections (the
short convolution with its SiLU, dt and A, the chunked state-space scan,
the gated norm), by phase: what the three ``ssm_*`` readers share. (It
sits beside ``scopes.py`` and makes the same join as
``window_attention.py`` for one more scope: the traced window's complete
runs of the step's executable on chip 0, each instruction's self time,
the program's own map from instruction to ``op_name``;
``attribution.py``'s ``step_names`` and ``phase_of`` decide what a name
means.)

An instruction counts when ``ssm`` is one of the parts of its
``op_name`` (its root's; where it has none, the last name inside it), so
a fusion counts once. Forward means the forward pass proper; what a
``contrib.nn.Remat`` layer recomputes runs in the backward pass and
counts there, as everywhere in the benchmark.

Also the work of a Mamba-2 layer by shapes, for the roofline: the least
bytes and FLOPs any implementation of the scope's forward moves and
computes, from the configuration's own widths and ``layer_types``.

A program that has no such scope -- the parent of the PR that added it,
or a model without Mamba-2 layers -- has nothing to read: the readers
return None and the line leaves the metric out.
"""
from __future__ import annotations

from benchmarks import attribution
from benchmarks.harness import layers, xplane

SCOPE = "ssm"
PHASES = ("forward", "backward")
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}
TOP = 6     # instructions a phase logged, largest first


def by_phase(op_selfs, names, n_steps):
    """({phase: ms a step}, {phase: [(the last two parts of an op_name,
    ms a step)] largest first, for the log}) of the instructions under
    the scope, from ``[(start, instruction, category, self_ns)]`` of the
    complete steps and the program's map; empty where no instruction
    lies under it."""
    ns, tails = {}, {}
    for _, instruction, _, self_ns in op_selfs:
        entry = names.get(instruction)
        if entry is None:
            continue
        op_name = entry["op_name"] or (entry["called"][-1]
                                       if entry["called"] else "")
        phase = attribution.phase_of(instruction, op_name)
        parts = op_name.split("/")
        if phase in PHASES and SCOPE in parts:
            ns[phase] = ns.get(phase, 0.0) + self_ns
            seen = tails.setdefault(phase, {})
            tail = "/".join(parts[-2:])
            seen[tail] = seen.get(tail, 0.0) + self_ns
    top = {phase: sorted(((tail, v / n_steps / 1e6)
                          for tail, v in seen.items()),
                         key=lambda kv: -kv[1])[:TOP]
           for phase, seen in tails.items()}
    return {phase: value / n_steps / 1e6 for phase, value in ns.items()}, top


def of_run(run):
    """``by_phase`` of chip 0's traced steps, computed once a run; None
    where there is nothing to read (no device trace, no complete step, a
    program without the map)."""
    if SCOPE in run.facts:
        return run.facts[SCOPE]
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
            found, top = by_phase(ops, names, len(runs))
            for phase, ms in sorted(found.items()):
                run.log(f"{SCOPE} {phase}: {ms:.3f} ms a step; largest: "
                        + ", ".join(f"{tail} {v:.3f}"
                                    for tail, v in top[phase]))
    run.facts[SCOPE] = found
    return found


def scope_ms(run, phase):
    """ms a step of ``phase`` ops under the scope; None where the
    program has no op under it at all."""
    found = of_run(run)
    if not found:
        return None
    return found.get(phase, 0.0)


def mamba_layers(config):
    """The layers ``layer_types`` calls ``mamba``."""
    return sum(1 for kind in config.get("layer_types") or ()
               if kind == "mamba")


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's Mamba-2 forward under the scope,
    which bound): max(FLOPs / bf16 peak, bytes / HBM peak) a layer, x
    the Mamba-2 layers. FLOPs: the recurrence at its least, 3 x head_dim
    x state multiply-adds a head a token (decay, write, read). Bytes, in
    the compute dtype, each array once: the convolution reads and writes
    xBC; the scan reads x, B, C and dt and writes y; the gated norm reads
    y and z."""
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    heads, head_dim = config["mamba_num_heads"], config["mamba_head_dim"]
    groups, state = config["n_groups"], config["ssm_state_size"]
    inner = heads * head_dim
    conv = inner + 2 * groups * state
    flops = 2 * 3 * tokens * heads * head_dim * state
    moved = tokens * (2 * conv + (inner + 2 * groups * state + heads + inner)
                      + 2 * inner) * _BYTES[config["train"]["compute_dtype"]]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return (mamba_layers(config) * max(by_flops, by_bytes) * 1e3,
            "compute" if by_flops >= by_bytes else "memory")
