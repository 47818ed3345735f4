"""Device time under the scope ``window_attention``, which
``contrib.nn.GatedAttention`` opens inside ``attention`` around the core
of a layer whose queries see a window of the sequence, by phase: what
the four ``window_attention_*`` readers share. (It sits beside
``scopes.py`` and makes the same join for one more scope: the traced
window's complete runs of the step's executable on chip 0, each
instruction's self time, the program's own map from instruction to
``op_name``; ``attribution.py``'s ``step_names`` and ``phase_of`` decide
what a name means.)

An instruction counts when ``window_attention`` is one of the parts of
its ``op_name`` (its root's; where it has none, the last name inside
it), so a fusion counts once. Forward means the forward pass proper;
what a ``contrib.nn.Remat`` half recomputes runs in the backward pass
and counts there, as everywhere in the benchmark. The attention layers
that see the whole sequence are what ``attention_*`` reads less this.

Also the work of a window layer by shapes, for the two rooflines: the
(query, key) pairs a window really holds, and the layers that have one,
from the configuration's own ``layer_types`` and ``sliding_window``.

A program that has no such scope -- the parent of the PR that added it,
or a model without window layers -- has nothing to read: the readers
return None and the line leaves the metric out.
"""
from __future__ import annotations

from benchmarks import attribution
from benchmarks.harness import layers, xplane

SCOPE = "window_attention"
PHASES = ("forward", "backward")
_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def by_phase(op_selfs, names, n_steps):
    """{phase: ms a step} of the instructions under the scope, from
    ``[(start, instruction, category, self_ns)]`` of the complete steps
    and the program's map; empty where no instruction lies under it."""
    ns = {}
    for _, instruction, _, self_ns in op_selfs:
        entry = names.get(instruction)
        if entry is None:
            continue
        op_name = entry["op_name"] or (entry["called"][-1]
                                       if entry["called"] else "")
        phase = attribution.phase_of(instruction, op_name)
        if phase in PHASES and SCOPE in op_name.split("/"):
            ns[phase] = ns.get(phase, 0.0) + self_ns
    return {phase: value / n_steps / 1e6 for phase, value in ns.items()}


def _kernel_builds():
    """The ``kernel.build`` spans of the program, one a kernel built
    (none from a program that records none)."""
    from mxnet_tpu.observability import trace

    return [s["attrs"] for s in trace.spans(name="kernel.build")]


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
            found = by_phase(ops, names, len(runs))
            for phase, ms in sorted(found.items()):
                whole = attribution.attention_ms(run, phase)
                run.log(f"attention {phase}: {ms:.3f} ms a step under "
                        f"{SCOPE}" + ("" if whole is None else
                                      f", {whole - ms:.3f} in the layers "
                                      "that see the whole sequence"))
            for built in _kernel_builds():
                if built.get("tiles_causal"):
                    run.log(f"  kernel {built['kernel']} (bh {built['bh']}, "
                            f"T {built['t']}, D {built['d']}, tile "
                            f"{built['block_q']} x {built['block_k']}, "
                            f"window {built['window']}): "
                            f"{built['tiles_visited']} tiles visited of "
                            f"{built['tiles_causal']} in the causal half")
    run.facts[SCOPE] = found
    return found


def scope_ms(run, phase):
    """ms a step of ``phase`` ops under the scope; None where the
    program has no op under it at all."""
    found = of_run(run)
    if not found:
        return None
    return found.get(phase, 0.0)


def window_layers(config):
    """(layers whose attention sees a window, the window) of a
    configuration; (0, None) for one without ``layer_types``."""
    kinds = config.get("layer_types") or ()
    return (sum(1 for kind in kinds if kind == "sliding_attention"),
            config.get("sliding_window"))


def seen_pairs(t, window):
    """(query, key) pairs with ``0 <= i - j < window`` in a sequence of
    ``t``: every query sees ``window`` keys but the first ``window - 1``,
    which see one fewer each."""
    window = min(int(window), int(t))
    return window * t - window * (window - 1) / 2


def full_scope_ms(run, phase):
    """ms a step of ``phase`` ops under ``attention`` and not under the
    scope: the layers of a model with window layers that see the whole
    sequence. None where the program has no op under the scope."""
    under = scope_ms(run, phase)
    whole = attribution.attention_ms(run, phase)
    if under is None or whole is None:
        return None
    return whole - under


def least_ms(config, traffic, peaks, products, tensors, full=False):
    """(least time in ms of the step's window layers in one pass, which
    bound): max(FLOPs / bf16 peak, bytes / HBM peak) a layer, x window
    layers. FLOPs: ``products`` matrix products of B x H x D
    multiply-adds a seen (query, key) pair. Bytes: ``tensors`` arrays of
    B x T x H x D read or written once, in the compute dtype. ``full``:
    of the other layers of ``layer_types`` instead, whose window is the
    sequence."""
    b, t = int(traffic["batch"]), int(traffic["seq_len"])
    heads, size = int(config["num_attention_heads"]), int(config["head_dim"])
    n_layers, window = window_layers(config)
    if full:
        n_layers, window = len(config["layer_types"]) - n_layers, t
    flops = 2 * products * b * heads * size * seen_pairs(t, window)
    moved = tensors * b * t * heads * size \
        * _BYTES[config["train"]["compute_dtype"]]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return (n_layers * max(by_flops, by_bytes) * 1e3,
            "compute" if by_flops >= by_bytes else "memory")
