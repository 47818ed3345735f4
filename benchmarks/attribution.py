"""Device time by the names the program gave its own parts, and its
set-up by its own spans: what the readers of ``forward_ms_per_step``,
``backward_ms_per_step``, ``optimizer_ms_per_step``,
``step_attributed_share``, ``attention_*`` and ``setup_*_s`` share.
(It sits outside ``layer_metrics/``, where every file is one metric.)

**How a reader finds the step's map.** A trace's ``XLA Ops`` events are
named after the instructions of the executable that ran
(``%fusion.12 = ...``; ``xplane.reduce`` keeps the short name and the
self time, ``dev["op_selfs"]``). The program keeps, per compiled
executable, what it called each instruction:
``mxnet_tpu.observability.perf.op_names(<ledger key>)`` gives
``{instruction: {"op_name", "kernel", "called"}}`` from the optimised
HLO of the executable that runs. The step's executable is the module
that takes most of chip 0's time (``xplane.step_runs``); its name is
``jit_<label>(<fingerprint>)``, and the ledger's keys are
``<label>@<fingerprint>``: of the entries with that label, the one whose
map knows most of the traced instructions is the step's.

**What a name decides** (here, not in the program, so that a PR that
claims a gain cannot move it). ``op_name`` is jax's name stack,
``jit(sharded_step)/transpose(jvp(net0))/net0_stage1_conv0/conv``:

- under the ``optimizer`` scope: optimizer;
- else with ``transpose(`` (the transposed, i.e. backward, half of
  ``value_and_grad``), or under ``rematted_computation``, or an
  instruction XLA cloned to recompute it (``.remat`` in its name):
  backward -- recomputation counts where it runs;
- else with ``jvp(``: forward;
- else not attributed: no ``op_name`` (copies and layout changes XLA
  added, asynchronous pairs, collectives it rebuilt) or one outside
  ``value_and_grad`` and the optimizer. These are the "rest", printed
  by name.

A fusion is put down once, to its own ``op_name`` (its root's; where it
has none, the last name inside it); the log says how much time sat in
fusions whose inside holds ops of two phases (a weight gradient fused
with its optimizer update), and how much more in fusions of several
blocks (a convolution with the next BatchNorm's statistics). ``attention`` is the scope
``MultiHeadAttention`` opens around scores, softmax and values, whatever
implements them. The block kinds of the log's table are read off the
zoo's block names by pattern: they are for reading, no metric uses them.

**A program from before these names** (no ``perf.op_names``: the parent
commit of the PR that added them) has nothing to read. The harness
fails a chip run whose reader returns None, and cannot leave a metric
out; so there every reader here returns 0 -- no time is named, no
set-up is spanned -- and says so in the log. Where the program has the
names and a reader still finds nothing, it returns None and the run
fails, as for every other metric.
"""
from __future__ import annotations

import re

from benchmarks.harness import layers, xplane

PHASES = ("forward", "backward", "optimizer")
REST = "rest"
_SCOPE_OPTIMIZER = "optimizer"
_SCOPE_ATTENTION = "attention"
_KINDS = (          # first match on the innermost block's name
    ("Conv2D", re.compile(r"conv")),
    ("BatchNorm", re.compile(r"batchnorm|_bn\d*$")),
    ("LayerNorm", re.compile(r"layernorm|_ln\d*$|_norm\d*$")),
    ("embedding", re.compile(r"embed|_pos$")),
    ("Dense", re.compile(r"dense|_qkv$|_out$|_ff\d+$|_head$|_fc\d*$|proj")),
    ("pooling", re.compile(r"pool")),
    ("residual / activation", re.compile(r"relu|gelu|activation|_act\d*$")),
    ("loss", re.compile(r"loss")),
)
_TRANSFORM = re.compile(r"^(?:transpose|jvp|vmap|checkpoint)\((.*)\)$")


def program_names_its_parts():
    """False for a program from before the map, the scopes and the
    set-up spans (they came in one PR; ``perf.op_names`` is the probe)."""
    from mxnet_tpu.observability import perf

    return hasattr(perf, "op_names")


def phase_of(instruction, op_name):
    """One of PHASES, or REST."""
    scopes = op_name.split("/")
    if _SCOPE_OPTIMIZER in scopes:
        return "optimizer"
    if not op_name or "jvp(" not in op_name:
        return REST
    if "transpose(" in op_name or "rematted_computation" in scopes \
            or ".remat" in instruction:
        return "backward"
    return "forward"


def _bare(scope):
    """``transpose(jvp(net0))`` -> ``net0``: the block under jax's
    transform wrappers, which wrap the outermost scope of a stack."""
    m = _TRANSFORM.match(scope)
    while m:
        scope = m.group(1)
        m = _TRANSFORM.match(scope)
    return scope


def blocks_of(op_name):
    """The gluon blocks an ``op_name`` lies under, outermost first. The
    root block is the scope ``value_and_grad`` wrapped (``jvp(net0)``);
    gluon prefixes a child's name with its parent's, so the blocks are
    the root and the scopes that start with ``<root>_`` (a container
    that shares its parent's name counts once). jax's own scopes
    (``while``, ``body``, ``jit(_where)``) and this module's are not
    blocks."""
    scopes = op_name.split("/")
    root = next((_bare(s) for s in scopes if _bare(s) != s
                 and not s.startswith("jit(")), "")
    if not root:
        return []
    out = [root]
    for scope in scopes:
        if scope.startswith(root + "_") and scope != out[-1]:
            out.append(scope)
    return out


def kind_of(op_name, containers=()):
    """The row of the log's table an op belongs in."""
    scopes = op_name.split("/")
    if _SCOPE_OPTIMIZER in scopes:
        return "optimizer"
    if _SCOPE_ATTENTION in scopes:
        return "attention"
    blocks = blocks_of(op_name)
    if not blocks:
        return "outside any block (casts, loss tail)"
    inner = blocks[-1].lower()
    for kind, pattern in _KINDS:
        if pattern.search(inner):
            return kind
    if blocks[-1] in containers:
        return "residual / activation"      # an op of a container itself
    return "other block"


class Attribution:
    """Self time (ns) over the complete steps of the traced window, by
    phase; what could not be attributed; what sat in mixed fusions."""

    def __init__(self, n_steps):
        self.n_steps = n_steps
        self.ns = dict.fromkeys(PHASES + (REST,), 0.0)
        self.attention_ns = {"forward": 0.0, "backward": 0.0}
        self.mixed_phases_ns = 0.0     # in fusions that hold two phases
        self.mixed_blocks_ns = 0.0     # ... one phase, several blocks
        self.rest_by_name = {}          # base instruction name -> ns
        self.table = {}                 # (phase, kind) -> ns
        self.kernels = {}               # pallas kernel name -> ns
        self.unknown = 0                # traced instructions the map lacks

    def ms_per_step(self, phase):
        return self.ns[phase] / self.n_steps / 1e6

    def attention_ms_per_step(self, phase):
        return self.attention_ns[phase] / self.n_steps / 1e6

    def total_ms_per_step(self):
        return sum(self.ns.values()) / self.n_steps / 1e6


def attribute(op_selfs, names, n_steps):
    """Join ``op_selfs`` -- ``[(start, instruction, category, self_ns)]``
    of the complete steps -- with the program's map ``names``."""
    att = Attribution(n_steps)
    every = {n["op_name"] for n in names.values() if n["op_name"]}
    containers = {b for op in every for b in blocks_of(op)[:-1]}
    by_instruction = {}     # an instruction runs every step: classify once
    for _, instruction, _, self_ns in op_selfs:
        by_instruction[instruction] = \
            by_instruction.get(instruction, 0.0) + self_ns
    for instruction, self_ns in by_instruction.items():
        entry = names.get(instruction)
        if entry is None:
            att.unknown += 1
            entry = {"op_name": "", "kernel": "", "called": []}
        op_name = entry["op_name"] or (entry["called"][-1]
                                       if entry["called"] else "")
        phase = phase_of(instruction, op_name)
        att.ns[phase] += self_ns
        if phase == REST:
            base = xplane.base_name(instruction)
            att.rest_by_name[base] = att.rest_by_name.get(base, 0.0) + self_ns
            continue
        kind = kind_of(op_name, containers)
        att.table[phase, kind] = att.table.get((phase, kind), 0.0) + self_ns
        if kind == "attention":
            att.attention_ns[phase] += self_ns
        if entry["kernel"]:
            att.kernels[entry["kernel"]] = \
                att.kernels.get(entry["kernel"], 0.0) + self_ns
        inside = {phase_of(instruction, c) for c in entry["called"]}
        if len(inside - {REST}) > 1:
            att.mixed_phases_ns += self_ns
        elif len({tuple(blocks_of(c)[-1:]) for c in entry["called"]}) > 1:
            att.mixed_blocks_ns += self_ns
    return att


def step_names(module_name, traced):
    """The program's map for the executable behind an ``XLA Modules``
    name, or None: of the ledger's entries under the module's label, the
    one that knows most of the ``traced`` instruction names."""
    from mxnet_tpu.observability import perf

    label = re.sub(r"^jit_", "", module_name.split("(", 1)[0])
    best, known = None, 0
    for key, entry in perf.ledger().items():
        if entry["label"] != label:
            continue
        names = perf.op_names(key)
        hits = sum(1 for n in traced if n in names) if names else 0
        if hits > known:
            best, known = names, hits
    return best


def of_run(run):
    """The ``Attribution`` of chip 0's traced steps, computed once a run;
    None where there is nothing to read (no device trace, no complete
    step, or a program that has the names but whose map is gone). For a
    program from before the names: an empty one, every time 0."""
    if "attribution" in run.facts:
        return run.facts["attribution"]
    att = None
    dev = layers.chip(run)
    runs = xplane.step_runs(dev) if dev is not None else []
    if runs and not program_names_its_parts():
        run.log("the program is older than its names (no "
                "observability.perf.op_names): forward, backward, "
                "optimizer, attention and set-up read 0")
        att = Attribution(len(runs))
    elif runs:
        lo, hi = runs[0][0], runs[-1][1]
        module = next(name for name, s, e in dev["modules"]
                      if (s, e) == runs[0])
        ops = [op for op in dev["op_selfs"] if lo <= op[0] < hi]
        names = step_names(module, {op[1] for op in ops})
        if names is not None:
            att = attribute(ops, names, len(runs))
            log_table(run, att, module)
    run.facts["attribution"] = att
    return att


def log_table(run, att, module):
    total = att.total_ms_per_step()
    run.log(f"{module}: {total:.3f} ms of ops a step over {att.n_steps} "
            "steps = " + " + ".join(
                f"{p} {att.ms_per_step(p):.3f}" for p in PHASES + (REST,))
            + f"; each fusion counted once, under its root: "
            f"{att.mixed_phases_ns / att.n_steps / 1e6:.3f} in fusions "
            "that hold ops of two phases, "
            f"{att.mixed_blocks_ns / att.n_steps / 1e6:.3f} more in fusions "
            f"of several blocks; {att.unknown} traced op(s) not in the "
            "program's map")
    rows = sorted(att.table.items(), key=lambda kv: -kv[1])[:12]
    for (phase, kind), ns in rows:
        ms = ns / att.n_steps / 1e6
        run.log(f"  {phase:9s} {kind:38s} {ms:9.3f} ms "
                f"{100 * ms / total:5.1f} %")
    for kernel, ns in sorted(att.kernels.items(), key=lambda kv: -kv[1]):
        run.log(f"  kernel {kernel}: {ns / att.n_steps / 1e6:.3f} ms a step")
    rest = sorted(att.rest_by_name.items(), key=lambda kv: -kv[1])[:12]
    run.log("  not attributed, by instruction: " + ", ".join(
        f"{name} {ns / att.n_steps / 1e6:.3f}" for name, ns in rest))


def phase_ms(run, phase):
    att = of_run(run)
    return None if att is None else att.ms_per_step(phase)


def attention_ms(run, phase):
    att = of_run(run)
    return None if att is None else att.attention_ms_per_step(phase)


# ------------------------------------------------------------------ set-up

def setup_seconds(run, names, split=None):
    """Seconds covered, before the window, by the program's spans of the
    given names (their union: nested or repeated spans count once).
    ``split(span)`` gives a label to log the parts by. None where the
    program has such spans and none was recorded; 0 for a program from
    before them."""
    lo_ns = run.window[0] * 1e9
    spans = [s for name in names
             for s in run.program_spans(name, in_window=False)
             if s["t0_ns"] + s["dur_ns"] <= lo_ns]
    if not spans:
        if not program_names_its_parts():
            run.log(f"no {'/'.join(names)} span: the program is older "
                    "than its set-up spans; reads 0")
            return 0.0
        return None
    covered = xplane.total(xplane.union(
        [(s["t0_ns"], s["t0_ns"] + s["dur_ns"]) for s in spans])) / 1e9
    parts = {}
    for s in spans:
        label = split(s) if split else s["name"]
        seen = parts.setdefault(label, [0.0, 0])
        seen[0] += s["dur_ns"] / 1e9
        seen[1] += 1
    first = min(s["t0_ns"] for s in spans)
    last = max(s["t0_ns"] + s["dur_ns"] for s in spans)
    run.log(f"set-up spans {'/'.join(names)}: {covered:.3f} s covered, "
            f"from {(first - lo_ns) / 1e9:.3f} to {(last - lo_ns) / 1e9:.3f}"
            " s of the window's start; " + ", ".join(
                f"{label} {secs:.3f} s x{count}"
                for label, (secs, count) in sorted(parts.items())))
    return covered
