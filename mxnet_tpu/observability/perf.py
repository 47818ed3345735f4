"""Performance attribution: the per-executable perf ledger, opt-in
device timing, and MFU / roofline gauges.

PR 10 made the runtime *legible* (span trees, metrics, flight
recorder) but every span still measures host wall-clock around async
dispatch, and nothing attributes cost to the *programs* the runtime
actually runs. This module is the measurement substrate the remaining
ROADMAP items (autotuning, input-stall gates, SLO control loops) stand
on, in three layers:

1. **Static attribution — the perf ledger.** Every compiled executable
   that goes through the sanctioned capture/AOT compile path
   (``capture.aot_compile``: captured trainer steps, ShardedTrainer
   step/grads/apply programs, serving bucket executables in every
   dtype variant) records one ledger entry keyed by its **existing AOT
   fingerprint** (``<label>@<fingerprint16>``): XLA ``cost_analysis()``
   (flops, bytes accessed), ``memory_analysis()`` (argument / output /
   temp / generated-code bytes and the derived peak-HBM estimate) and
   the wall compile time. The ledger is surfaced by
   ``observability.dump()`` / ``tools/obs_dump.py`` and exported as
   per-executable gauges (``mxnet_tpu_executable_peak_hbm_bytes``,
   ``mxnet_tpu_compile_ms``, ...).

2. **Dynamic attribution — device timing.** With
   ``MXNET_TPU_OBS_DEVICE_TIME=1`` (or :func:`set_device_time`), every
   ledgered executable call is wrapped in the dependency-chained
   ``block_until_ready`` timing discipline PERF.md established: the
   span splits into host-dispatch time (the async call returning) and
   device-execute time (until the outputs are ready), recorded as a
   retroactive ``perf.device_execute`` span under the caller's context
   and folded into the ledger entry (``device_ms``, EWMA). OFF by
   default — blocking per call serializes dispatch, so this is a
   diagnosis mode, gated out of the ≤2% obs_bench overhead budget.

3. **Derived gauges — MFU and roofline fraction.** From (1)+(2):
   ``mfu = flops / (device_s · peak_flops)`` and
   ``roofline_fraction = bytes_accessed / (device_s · peak_bw)`` per
   executable, against the published peaks of the device the program
   runs on (:data:`DEVICE_PEAKS`, keyed by jax ``device_kind``; a kind
   that is not in the table leaves both gauges ``None`` — it never
   borrows another device's number; ``MXNET_TPU_PERF_PEAK_FLOPS`` /
   ``MXNET_TPU_PERF_PEAK_GBPS`` supply one). Device time here is the full
   dependency-chained wall (dispatch included) — an upper bound on
   device busy time, so the gauges are conservative.

``tools/perf_gate.py`` turns the ledger + measured step timings into a
continuous regression gate against ``tools/perf_baseline.json``.
Stdlib-only at import (jax loads lazily, and only in the paths that
already hold compiled executables). See docs/observability.md
("Performance attribution") and PERF.md round 6.
"""
from __future__ import annotations

import os
import re
import threading
import time
import weakref
from collections import deque

from . import _STATS
from . import metrics as _metrics

__all__ = ["LEDGER_FIELDS", "note_compile", "note_execution", "timed_call",
           "op_names", "parse_op_names",
           "ledger", "device_timed_entries", "ledger_key",
           "combined_fingerprint", "snapshot", "clear", "update_gauges",
           "device_time_enabled", "set_device_time", "DEVICE_PEAKS",
           "device_peaks", "nominal_peaks", "device_record",
           "require_chip"]

_LOCK = threading.Lock()
_LEDGER: dict = {}
# ledger key -> a callable that gives the compiled object of the entry's
# latest build or None: a weak reference (its owner is the CapturedExec
# or the captured step), a strong one while span tracing is on (a traced
# run asks for the names after the trainer that owned the step is gone);
# and the names parsed out of it the first time someone asked (op_names)
_COMPILED: dict = {}
_OP_NAMES: dict = {}

# THE field registry of one ledger entry. Every entry carries exactly
# these keys (closure-tested), and every field is documented in
# docs/observability.md — graftlint RD005 gates the drift, the same way
# RD001/RD004 pin env knobs and metric names.
LEDGER_FIELDS = (
    "label",                 # compile-site label (trainer_step, serving_bucket8, ...)
    "fingerprint",           # program+signature identity the key derives from
    "backend",               # jax default backend at compile time (cpu/gpu/tpu)
    "compiles",              # times this key compiled this process
    "compile_ms",            # wall time of the latest trace+lower+XLA compile
    "aot_hit",               # latest build deserialized from the AOT disk cache
    "flops",                 # XLA cost_analysis flops (None when unavailable)
    "bytes_accessed",        # XLA cost_analysis bytes accessed (None when unavailable)
    "peak_hbm_bytes",        # argument+output+temp+generated_code-alias estimate
    "argument_bytes",        # memory_analysis argument size
    "output_bytes",          # memory_analysis output size
    "temp_bytes",            # memory_analysis temp size
    "generated_code_bytes",  # memory_analysis generated code size
    "device_calls",          # dependency-chained timed executions (device mode)
    "device_ms",             # EWMA of blocked wall per execution (device mode)
    "dispatch_ms",           # EWMA of the async call returning (device mode)
    "mfu",                   # flops / (device_s * peak flops); None for an unknown device_kind
    "roofline_fraction",     # bytes_accessed / (device_s * peak HBM bandwidth); None likewise
    "t",                     # wall-clock of the latest compile
)

_DEVICE_TIME = os.environ.get("MXNET_TPU_OBS_DEVICE_TIME", "").strip() in (
    "1", "true", "on", "yes")

# EWMA smoothing for per-execution device timings: heavy enough that a
# one-off scheduling hiccup doesn't swing the MFU gauge, light enough
# that a real regression shows within ~10 steps.
_EWMA = 0.3

# THE peaks table: published per-chip peaks keyed by jax ``device_kind``
# (``jax.devices()[0].device_kind``). bench.py, chip_smoke.py and the
# MFU / roofline gauges all read it; there is no second copy.
# Source: Google Cloud TPU documentation, "TPU v5e" (system
# architecture): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
# 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197.0e12,
                    "int8_ops_per_s": 393.0e12,
                    "hbm_bytes_per_s": 819.0e9},
}


def device_time_enabled():
    return _DEVICE_TIME


def set_device_time(flag):
    """Toggle dependency-chained device timing at runtime (the
    post-import counterpart of ``MXNET_TPU_OBS_DEVICE_TIME``); returns
    the previous state."""
    global _DEVICE_TIME
    prev = _DEVICE_TIME
    _DEVICE_TIME = bool(flag)
    return prev


def device_record():
    """The device record every measurement prints: ``{"platform",
    "kind", "count"}`` exactly as jax reports them."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chip():
    """:func:`device_record`, or ``MXNetError`` when jax's default
    backend is the CPU. A measurement path that finds no chip fails; it
    never falls back to the CPU."""
    from ..base import MXNetError

    dev = device_record()
    if dev["platform"] == "cpu":
        raise MXNetError(
            f"no accelerator: jax.devices() reports only {dev['count']} "
            f"cpu device(s) (JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}); device metrics come "
            "from a chip run, never from XLA-CPU")
    return dev


def device_peaks(device_kind=None):
    """The :data:`DEVICE_PEAKS` row for ``device_kind`` (default: the
    first device of jax's default backend). An unknown kind raises
    ``KeyError`` naming it — a measurement never borrows another
    device's peak."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)}); add a sourced row to "
            "observability.perf.DEVICE_PEAKS") from None


def nominal_peaks(device_kind=None):
    """(peak_flops_per_s, peak_hbm_bytes_per_s) the MFU / roofline gauges
    divide by: the env overrides where set, else the table's bf16 and
    HBM peaks for ``device_kind``, else ``None`` (gauge undefined)."""
    try:
        row = device_peaks(device_kind)
        flops, bw = row["bf16_flops_per_s"], row["hbm_bytes_per_s"]
    except KeyError:
        flops = bw = None
    try:
        flops = float(os.environ.get("MXNET_TPU_PERF_PEAK_FLOPS") or 0) \
            or flops
    except ValueError:
        pass
    try:
        bw = float(os.environ.get("MXNET_TPU_PERF_PEAK_GBPS") or 0) * 1e9 \
            or bw
    except ValueError:
        pass
    return flops, bw


def ledger_key(label, fingerprint):
    """The ledger key: the compile-site label + the first 16 hex chars
    of the site's program+signature identity (see
    :func:`combined_fingerprint` — the same structural identity the
    persistent compile cache is keyed by, so a shape/dtype/code change
    re-keys the entry instead of silently merging two programs)."""
    fp = (fingerprint or "").strip()
    return f"{label}@{fp[:16] if fp else 'none'}"


def combined_fingerprint(fingerprint, sig):
    """Fold a per-call aval/sharding signature into a compile site's
    structural fingerprint — the ledger identity. The AOT disk cache
    keys by (label, fingerprint, sig); a ledger keyed by fingerprint
    alone would merge the distinct programs one CapturedExec compiles
    for different batch shapes (elastic resize, partial tail batch)
    into one entry with last-writer-wins numbers. Both the compile site
    (``capture.aot_compile``) and the execution sites compute this from
    the same inputs, so compile and execution attribution agree."""
    import hashlib

    base = (fingerprint or "").strip()
    if not sig:
        return base
    return hashlib.sha256(f"{base}|{sig}".encode()).hexdigest()[:32]


# ------------------------------------------------------------ cost analysis

def _cost_numbers(compiled):
    """(flops, bytes_accessed) from a compiled executable's XLA cost
    analysis — one flat dict on this jax; (None, None) for an object
    that is not a compiled executable (tests seed entries with one)."""
    analyze = getattr(compiled, "cost_analysis", None)
    if analyze is None:
        return None, None
    ca = analyze()
    flops = ca.get("flops")
    acc = ca.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(acc) if acc is not None else None)


def _memory_numbers(compiled):
    """Memory footprint dict from ``memory_analysis()``; zeros for an
    object that is not a compiled executable. ``peak_hbm_bytes`` is the
    standard estimate argument + output + temp + generated_code − alias
    (donated buffers alias their inputs and must not be double-counted),
    clamped at 0."""
    out = {"argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
           "generated_code_bytes": 0, "peak_hbm_bytes": 0}
    analyze = getattr(compiled, "memory_analysis", None)
    if analyze is None:
        return out
    ma = analyze()
    arg = int(ma.argument_size_in_bytes)
    outp = int(ma.output_size_in_bytes)
    tmp = int(ma.temp_size_in_bytes)
    gen = int(ma.generated_code_size_in_bytes)
    alias = int(ma.alias_size_in_bytes)
    out.update(argument_bytes=arg, output_bytes=outp, temp_bytes=tmp,
               generated_code_bytes=gen,
               peak_hbm_bytes=max(0, arg + outp + tmp + gen - alias))
    return out


def note_compile(label, fingerprint, compiled, compile_s, aot_hit=False):
    """Record one compile into the ledger (called from
    ``capture.aot_compile`` for every captured/serving executable).
    Returns the ledger key."""
    import jax

    key = ledger_key(label, fingerprint)
    flops, acc = _cost_numbers(compiled)
    mem = _memory_numbers(compiled)
    backend = jax.default_backend()
    from . import trace as _trace

    if _trace.enabled():
        ref = lambda: compiled  # noqa: E731  (held for a traced run)
    else:
        try:
            ref = weakref.ref(compiled)
        except TypeError:   # not a compiled executable (tests seed one)
            ref = None
    with _LOCK:
        _COMPILED[key] = ref
        _OP_NAMES.pop(key, None)
        entry = _LEDGER.get(key)
        if entry is None:
            entry = dict.fromkeys(LEDGER_FIELDS)
            entry.update(label=label, fingerprint=fingerprint or "",
                         compiles=0, device_calls=0)
            _LEDGER[key] = entry
            _STATS["perf_ledger_entries"] += 1
        entry.update(mem)
        entry.update(backend=backend, compile_ms=compile_s * 1e3,
                     aot_hit=bool(aot_hit), flops=flops,
                     bytes_accessed=acc, t=time.time())
        entry["compiles"] += 1
    return key


def note_execution(label, fingerprint, blocked_s, dispatch_s=0.0):
    """Fold one dependency-chained timed execution into the ledger
    entry and refresh its derived MFU / roofline numbers. ``blocked_s``
    is the full wall from launch until the outputs were ready (the
    PERF.md discipline); ``dispatch_s`` the async call returning."""
    key = ledger_key(label, fingerprint)
    with _LOCK:
        entry = _LEDGER.get(key)
        if entry is None:
            # executions can only follow a compile through aot_compile,
            # but a cleared ledger (tests, gate runs) must not lose the
            # timing — re-seed a minimal entry
            entry = dict.fromkeys(LEDGER_FIELDS)
            entry.update(label=label, fingerprint=fingerprint or "",
                         compiles=0, device_calls=0)
            _LEDGER[key] = entry
            _STATS["perf_ledger_entries"] += 1
        n = entry["device_calls"]
        ms, disp = blocked_s * 1e3, dispatch_s * 1e3
        if n == 0 or entry["device_ms"] is None:
            entry["device_ms"], entry["dispatch_ms"] = ms, disp
        else:
            entry["device_ms"] += _EWMA * (ms - entry["device_ms"])
            entry["dispatch_ms"] += _EWMA * (disp - entry["dispatch_ms"])
        entry["device_calls"] = n + 1
        dev_s = entry["device_ms"] / 1e3
        if dev_s > 0:
            peak_flops, peak_bw = nominal_peaks()
            if entry["flops"] and peak_flops:
                entry["mfu"] = entry["flops"] / (dev_s * peak_flops)
            if entry["bytes_accessed"] and peak_bw:
                entry["roofline_fraction"] = \
                    entry["bytes_accessed"] / (dev_s * peak_bw)
    _STATS["perf_device_timings"] += 1
    return key


def timed_call(fn, args, label, fingerprint):
    """Execute ``fn(*args)`` under the device-timing discipline when
    enabled; a bare call otherwise (one global check — cheap enough for
    every executable hot path). When timing: measure the async dispatch
    returning, block until every output leaf is ready, record a
    retroactive ``perf.device_execute`` span (host-dispatch vs
    device-execute split in its attrs) under the caller's current trace
    context, and fold the numbers into the ledger."""
    if not _DEVICE_TIME:
        return fn(*args)
    t0 = time.perf_counter_ns()
    out = fn(*args)
    t_disp = time.perf_counter_ns()
    try:
        import jax

        jax.block_until_ready(out)
    except Exception:
        pass  # non-array outputs (already-host values) are already ready
    t_ready = time.perf_counter_ns()
    key = note_execution(label, fingerprint, (t_ready - t0) / 1e9,
                         (t_disp - t0) / 1e9)
    from . import trace as _trace

    _trace.record("perf.device_execute", t0, t_ready - t0,
                  executable=key, host_dispatch_ns=t_disp - t0,
                  device_ns=t_ready - t_disp)
    return out


# -------------------------------------------------------------- introspection

def ledger():
    """Snapshot of every entry, keyed by ``<label>@<fingerprint16>``."""
    with _LOCK:
        return {k: dict(v) for k, v in _LEDGER.items()}


def device_timed_entries(min_calls=1):
    """Entries with at least ``min_calls`` dependency-chained timed
    executions and a live ``device_ms`` EWMA — the subscription surface
    for consumers of the dynamic series (the alert engine's
    ``perf_device_regression`` rule watches exactly this view)."""
    with _LOCK:
        return {k: dict(v) for k, v in _LEDGER.items()
                if (v["device_calls"] or 0) >= int(min_calls)
                and v["device_ms"] is not None}


def snapshot():
    """The ``observability.dump()`` section: entries + the roofline
    constants they were judged against + the timing-mode flag."""
    peak_flops, peak_bw = nominal_peaks()
    return {"entries": ledger(),
            # None for a device_kind outside DEVICE_PEAKS (XLA-CPU)
            "peaks": {"flops_per_s": peak_flops, "hbm_bytes_per_s": peak_bw},
            "device_time": _DEVICE_TIME}


def clear():
    with _LOCK:
        _LEDGER.clear()
        _COMPILED.clear()
        _OP_NAMES.clear()


# ------------------------------------------------- the program's own names

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
# the opcode and its operand list: the first `` word(`` after `` = `` (a
# shape's layout ``{1,0:T(8,128)}`` and a tuple shape's ``, f32[`` hold
# no space before a parenthesis)
_HLO_OPERANDS = re.compile(r" ([a-z][\w\-]*)\(([^)]*)\)")
_HLO_REF = re.compile(r"%?([\w.\-]+)")
# every computation an instruction runs
_HLO_CALLED = re.compile(
    r"\b(calls|to_apply|body|condition|true_computation|false_computation|"
    r"select|scatter|branch_computations|called_computations)="
    r"(?:\{([^}]*)\}|%?([\w.\-]+))")
# where a computation's parameter 0 sits among its caller's operands: a
# conditional's branch k takes operand k + 1, every other caller hands
# operand i to parameter i
_HLO_FIRST_OPERAND = {"true_computation": 1, "false_computation": 2}
_PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


def _is_name(op_name):
    """A name the program gave: jax's name stack, ``jit(<label>)/...``.
    A bare ``op_name`` -- what a compiler pass gives the op it makes
    (``ragged-dot-none``, ``reduce_sum``), an argument's (``x``) -- is
    not one."""
    return "/" in op_name


def parse_op_names(hlo_text):
    """Optimised HLO text -> ``{instruction name: {"op_name", "kernel",
    "called", "owner", "via"}}`` for every instruction of every
    computation.

    ``op_name`` is the instruction's ``metadata={op_name=...}``: jax's
    name stack at the point the op was traced, e.g.
    ``jit(sharded_step)/transpose(jvp(net0))/net0_dense1/dot_general``
    -- the executable's label, ``jvp(`` (forward) or ``transpose(jvp(``
    (backward) from ``value_and_grad``, the ``optimizer`` / ``attention``
    scopes, and the gluon blocks' names (``jit.scope``). "" where XLA
    made the instruction itself (copies, layout changes, async pairs).
    ``kernel`` is the Pallas kernel's ``name=`` for a ``tpu_custom_call``
    (the scope the call sits in), else "". ``called`` lists the distinct
    ``op_name`` values of the instructions inside a fusion's computation,
    in program order, so a reader can tell a fusion that mixes blocks or
    directions from one that does not.

    ``owner`` is, for an instruction with no name of the program's (no
    name stack of its own -- "" or a compiler pass's bare op name such
    as ``ragged-dot-none`` -- and none inside it), the name of the part
    it was made for; "" elsewhere. ``via`` says how it was found:
    ``"user"`` -- the nearest named instruction that reads the result,
    walking forward through instructions with no name (tuples,
    get-tuple-elements, bitcasts, copies, an async pair's ``-start`` to
    its ``-done``, a computation's ROOT to the instruction that calls
    it): a fill is made for what reads it, a layout copy for the
    consumer that wants the layout; else ``"operand"`` -- the nearest
    named instruction it reads, walking backward by the same moves (a
    computation's parameter to its caller's operand); "" where neither
    finds one (the entry computation's parameters, a constant nothing
    named reads). A fusion with no name of its own and names inside is
    named by the last of them. One walk each way over one index of
    users: linear in the text. Facts, not verdicts: which phase or
    scope an owner means is the reader's to decide."""
    inside, current, out = {}, None, {}
    # the def-use graph, by index in program order
    names, named, operands, runs = [], [], [], []
    roots, params, entry_params = {}, {}, set()
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _HLO_COMPUTATION.match(line)
            current = inside.setdefault(m.group(1), {}) if m else None
            if m:
                comp, is_entry = m.group(1), line.startswith("ENTRY")
            continue
        m = _HLO_INSTRUCTION.match(line)
        if m is None or current is None:
            continue
        # operands and attributes come first, then the metadata, then a
        # Pallas call's body (megabytes of base64 on the same line)
        at = line.find("metadata={")
        head = line[:at] if at >= 0 else line
        found = _HLO_OP_NAME.search(line, at) if at >= 0 else None
        op_name = found.group(1).replace("\\'", "'") if found else ""
        name = m.group(2)
        kernel = ""
        if _PALLAS_TARGET in head:
            scopes = op_name.split("/")
            kernel = scopes[-2] if len(scopes) > 1 \
                and scopes[-1] == "pallas_call" else name
        i = len(names)
        runs.append([])
        called = []
        for kind, many, one in _HLO_CALLED.findall(head):
            targets = _HLO_REF.findall(many) if many else [one]
            for k, target in enumerate(targets):
                runs[i].append((target, k + 1 if kind == "branch_computations"
                                else _HLO_FIRST_OPERAND.get(kind, 0)))
            if kind == "calls" and not called:
                called = list(inside.get(targets[0], ()))
        if op_name:
            current[op_name] = None     # a dict keeps them in order, once
        out[name] = {"op_name": op_name, "kernel": kernel, "called": called,
                     "owner": "", "via": ""}
        names.append(name)
        inner = [c for c in called if _is_name(c)]
        named.append(op_name if _is_name(op_name)
                     else inner[-1] if inner else "")
        ops = _HLO_OPERANDS.search(head, m.end() - 1)
        opcode, listed = ops.groups() if ops else ("", "")
        operands.append([] if opcode in ("parameter", "constant")
                        else _HLO_REF.findall(listed))
        if opcode == "parameter":
            params.setdefault(comp, {})[int(listed)] = i
            if is_entry:
                entry_params.add(i)
        if m.group(1):
            roots[comp] = i
    _find_owners(out, names, named, operands, runs, roots, params,
                 entry_params)
    return out


def _find_owners(out, names, named, operands, runs, roots, params,
                 entry_params):
    """The two walks of :func:`parse_op_names`, each breadth-first from
    every named instruction at once, in program order: the nearest name
    wins, the earlier one on a tie. ``runs[i]`` lists the computations
    instruction ``i`` runs, each with the operand its parameter 0 takes."""
    index = {name: i for i, name in enumerate(names)}
    n = len(names)
    args = [[index[a] for a in ops if a in index] for ops in operands]
    users = [[] for _ in range(n)]      # (user, operand position)
    callers = {}                         # computation -> [caller]
    root_of = {i: comp for comp, i in roots.items()}
    for i in range(n):
        for pos, a in enumerate(args[i]):
            users[a].append((i, pos))
        for comp, _ in runs[i]:
            callers.setdefault(comp, []).append(i)
    open_ = [not named[i] and i not in entry_params for i in range(n)]

    def toward_operands(i):
        # i reads its operands, and the ROOT of each computation it runs
        yield from args[i]
        for comp, _ in runs[i]:
            if comp in roots:
                yield roots[comp]

    def toward_users(i):
        # i is read by its users, by the parameter of a computation its
        # user runs, and, as a ROOT, by the callers of its computation
        for user, pos in users[i]:
            yield user
            for comp, first in runs[user]:
                param = params.get(comp, {}).get(pos - first)
                if param is not None:
                    yield param
        if i in root_of:
            yield from callers.get(root_of[i], ())

    for steps, via in ((toward_operands, "user"), (toward_users, "operand")):
        label = list(named)
        queue = deque(i for i in range(n) if named[i])
        while queue:
            i = queue.popleft()
            for j in steps(i):
                if label[j] or not open_[j]:
                    continue
                label[j] = label[i]
                queue.append(j)
                entry = out[names[j]]
                if not entry["owner"]:
                    entry["owner"], entry["via"] = label[i], via


def op_names(key):
    """The names the program gave the instructions of one ledgered
    executable (:func:`parse_op_names` of the optimised HLO of the
    executable that runs, ``compiled.as_text()``), or None when the key
    is unknown or its executable is gone: the ledger holds the compiled
    object weakly, and strongly only for a compile made while span
    tracing was on (a diagnosis run may ask after the owner is gone; an
    untraced process never keeps a dead step's executable loaded).
    Parsed when first asked for and kept until the key compiles again:
    nothing is read or parsed at compile time, so a process that never
    asks pays nothing. A trace's
    ``XLA Ops`` events are named after these instructions, which is how
    a reader puts device time down to forward, backward, optimizer, a
    block or a kernel (benchmarks/attribution.py)."""
    with _LOCK:
        names = _OP_NAMES.get(key)
        ref = _COMPILED.get(key)
    if names is not None:
        return names
    compiled = ref() if ref is not None else None
    if compiled is None:
        return None
    names = parse_op_names(compiled.as_text())
    with _LOCK:
        if _COMPILED.get(key) is ref:
            _OP_NAMES[key] = names
    return names


# ------------------------------------------------------------ derived gauges

_PEAK_HBM = _metrics.gauge(
    "mxnet_tpu_executable_peak_hbm_bytes",
    "estimated peak HBM of one compiled executable "
    "(argument+output+temp+generated code bytes)", labels=("executable",))
_COMPILE_MS = _metrics.gauge(
    "mxnet_tpu_compile_ms",
    "wall compile time of the executable's latest build",
    labels=("executable",))
_EXEC_FLOPS = _metrics.gauge(
    "mxnet_tpu_executable_flops",
    "XLA cost-analysis flops per execution", labels=("executable",))
_EXEC_BYTES = _metrics.gauge(
    "mxnet_tpu_executable_bytes_accessed",
    "XLA cost-analysis bytes accessed per execution",
    labels=("executable",))
_DEVICE_MS = _metrics.gauge(
    "mxnet_tpu_device_ms",
    "EWMA dependency-chained device time per execution "
    "(MXNET_TPU_OBS_DEVICE_TIME)", labels=("executable",))
_MFU = _metrics.gauge(
    "mxnet_tpu_mfu",
    "model flops utilization vs the device's published peak",
    labels=("executable",))
_ROOFLINE = _metrics.gauge(
    "mxnet_tpu_roofline_fraction",
    "achieved HBM bandwidth fraction vs the device's published peak",
    labels=("executable",))


_PERF_GAUGES = (_PEAK_HBM, _COMPILE_MS, _EXEC_FLOPS, _EXEC_BYTES,
                _DEVICE_MS, _MFU, _ROOFLINE)


def update_gauges():
    """Refresh the per-executable gauges from the ledger — called by
    every exporter via ``metrics.update_derived()``, so the ledger
    exports without any caller wiring (the ``update_slo`` pattern).
    Labelsets whose key left the ledger (re-fingerprinted program,
    ``clear()``) are pruned, so a retrace-churny workload can't accrete
    unbounded label cardinality or export dead executables' frozen
    numbers forever."""
    entries = ledger()
    for g in _PERF_GAUGES:
        for labelset in g.labelsets():
            key = dict(labelset).get("executable")
            if key not in entries:
                g.remove(executable=key)
    for key, e in entries.items():
        _PEAK_HBM.set(e["peak_hbm_bytes"] or 0, executable=key)
        if e["compile_ms"] is not None:
            _COMPILE_MS.set(e["compile_ms"], executable=key)
        if e["flops"] is not None:
            _EXEC_FLOPS.set(e["flops"], executable=key)
        if e["bytes_accessed"] is not None:
            _EXEC_BYTES.set(e["bytes_accessed"], executable=key)
        if e["device_calls"]:
            _DEVICE_MS.set(e["device_ms"], executable=key)
        if e["mfu"] is not None:
            _MFU.set(e["mfu"], executable=key)
        if e["roofline_fraction"] is not None:
            _ROOFLINE.set(e["roofline_fraction"], executable=key)
