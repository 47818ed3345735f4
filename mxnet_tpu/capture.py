"""Whole-program step capture + persistent AOT compile cache.

ROADMAP item 3 (the Julia-to-TPU full-compilation argument, PAPERS.md
[1810.09868], and TensorFlow's whole-graph compilation [1605.08695]):
instead of eager dispatch with bulked segments, compile the *entire*
training step — forward, backward, optimizer update sweep, and the
HealthSentinel/loss-scaler finite check — into ONE donated XLA
executable, and serialize compiled programs to disk so a new process
(serving cold-start, multi-host restart) skips XLA compilation.

Three layers, all routed through the single sanctioned compile site
``_compile_jit`` (graftlint TS002):

1. **Capture** — :func:`capture` turns a gluon ``Trainer`` step (the
   eager fwd/bwd + bulked-update hot loop) or a parallel
   ``ShardedTrainer`` into a captured step object. The gluon capture
   re-runs the user's imperative step under trace via the
   mutation->functional bridge (``jit.TraceSession``), with three
   properties the plain ``mx.jit.trace`` path lacks:

   - **dynamic scalar operands**: every hyperparameter an optimizer op
     declares ``dynamic_params`` for (lr, wd, rescale_grad — including
     schedule- and bias-correction-drifted values) is a runtime operand,
     refreshed each step by a *scalar replay* of the update sweep's
     Python (array math skipped), so an Adam bias correction or lr
     schedule neither retraces nor goes stale;
   - **fused sentinel check**: with a HealthSentinel attached, one
     ``multi_all_finite`` reduction over the gradients runs *inside*
     the program and gates every weight/state write with a select, so
     an unhealthy batch never touches the weights — policies
     (raise/skip_batch/rollback) apply on the host from the returned
     flag exactly as on the eager path;
   - **retrace forensics**: a signature change (shape, dtype, scalar
     slots, rebound trainer state) bumps ``capture_retraces``, records
     a structured reason in the dispatch ring (crash reports embed it)
     and in :func:`retrace_log` — never a silent recompile.

2. **CapturedExec** — the keyed executable wrapper the
   ``ShardedTrainer`` fused/elastic steps and the serving ``Predictor``
   bucket executables compile through: per-signature executable cache,
   the same forensics, and the AOT layer below.

3. **AOT compile cache** — with ``MXNET_TPU_COMPILE_CACHE=<dir>``,
   compiled programs are persisted as ``jax.export`` artifacts keyed by
   (program fingerprint, avals/sharding/donation signature, backend
   topology) with the jax/jaxlib versions in the header. A warm process
   deserializes the traced program (skipping Python tracing + lowering)
   and re-links the XLA executable from jax's persistent compilation
   cache (skipping XLA compilation) — that cache's one directory is
   resolved at import (``mxnet_tpu._configure_jax``), never here. Stale
   (version-mismatched) and corrupt artifacts fall back to a fresh
   compile — never a crash.

Env knobs (docs/env_vars.md): ``MXNET_TPU_CAPTURE``,
``MXNET_TPU_COMPILE_CACHE``, ``MXNET_TPU_COMPILE_CACHE_MAX_MB``,
``MXNET_TPU_COMPILE_CACHE_SALT``. Counters surface in
``profiler.dispatch_stats()``. See docs/capture.md.
"""
from __future__ import annotations

import functools
import hashlib
import json
import os
import threading
import time

from . import profiler as _profiler
from .base import compile_cache_limit_bytes, evict_oldest
from .observability import flight as _obs_flight
from .observability import numerics as _obs_numerics
from .observability import perf as _obs_perf
from .observability import trace as _obs_trace

__all__ = ["capture", "CapturedTrainerStep", "CapturedShardedStep",
           "CapturedExec", "CaptureError", "enabled", "aot_enabled",
           "cache_dir", "compile_cache", "aot_compile", "note_recapture",
           "retrace_log", "clear_retrace_log", "stats", "reset_stats",
           "fingerprint", "code_sig", "net_sig"]

_LOCK = threading.Lock()

# Flat counters, merged into profiler.dispatch_stats() (docs/capture.md).
_STATS = {
    "capture_steps": 0,           # captured trainer-step invocations
    "capture_hits": 0,            # signature-cache hits on captured execs
    "capture_misses": 0,          # first compile per signature
    "capture_retraces": 0,        # signature changes after first compile
    "capture_fallback_eager": 0,  # kill-switch / capture-failure eager runs
    "aot_cache_hits": 0,          # artifacts loaded from disk
    "aot_cache_misses": 0,        # artifacts absent: fresh trace + store
    "aot_cache_stale": 0,         # version/platform mismatch: recompiled
    "aot_cache_corrupt": 0,       # unreadable artifact: recompiled
    "aot_cache_writes": 0,        # artifacts written
    "aot_cache_evictions": 0,     # files removed by the size-cap GC
}


def stats():
    return dict(_STATS)


def reset_stats():
    for k in _STATS:
        _STATS[k] = 0


class CaptureError(RuntimeError):
    """Capture could not (re)build a step program (scalar-slot drift,
    unsupported trainer config). The caller falls back to eager."""


# ------------------------------------------------------------------ env knobs

def enabled():
    """Master kill switch: ``MXNET_TPU_CAPTURE=0`` makes :func:`capture`
    return an eager-fallback step (identical semantics, no compile)."""
    return os.environ.get("MXNET_TPU_CAPTURE", "1").strip().lower() \
        not in ("0", "false", "off")


def cache_dir():
    """AOT artifact directory (``MXNET_TPU_COMPILE_CACHE``), or None when
    persistence is disabled."""
    d = os.environ.get("MXNET_TPU_COMPILE_CACHE", "").strip()
    return d or None


def aot_enabled():
    return enabled() and cache_dir() is not None


def _integrity_enabled():
    """Is the in-graph step fingerprint armed (resilience.integrity)?
    Late import: capture loads before the resilience package in some
    entry orders."""
    from .resilience import integrity as _integrity

    return _integrity.fingerprint_enabled()


def _cache_salt():
    return os.environ.get("MXNET_TPU_COMPILE_CACHE_SALT", "")


def _schedule_token():
    """The kernel schedule-table identity folded into every AOT cache
    key (mxnet_tpu/tune/, docs/autotune.md): kernel builders resolve
    Pallas block sizes / int8 arrangements from the table at trace
    time, so a table change is a program change — a tuned program
    warm-loads fleet-wide, and a schedule edit can never false-hit an
    artifact compiled under the old schedule. '' when autotuning is
    disabled or the table is empty (both compile the default-schedule
    programs)."""
    try:
        from .tune import schedule as _tune_schedule

        return _tune_schedule.fingerprint_token()
    except Exception:
        return ""


# -------------------------------------------------------- retrace forensics

# Structured reasons for every captured-program recompile, newest last.
# Bounded; guarded by _LOCK (read by tests and crash-report consumers).
_RETRACE_LOG: list = []
_RETRACE_LOG_CAP = 64


def retrace_log():
    """Structured reasons for every captured-step recompile after its
    first build: ``{"label", "reason", "prev", "new", "t"}`` dicts,
    oldest first. The same reasons land in the dispatch ring (and so in
    watchdog crash reports) as ``capture_retrace:<label>:<reason>``."""
    with _LOCK:
        return [dict(e) for e in _RETRACE_LOG]


def clear_retrace_log():
    with _LOCK:
        del _RETRACE_LOG[:]


def _sig_reason(prev, new):
    """Human-readable diff of two capture signatures."""
    if prev is None:
        return "first capture"
    try:
        if len(prev) != len(new):
            return f"operand count changed {len(prev)} -> {len(new)}"
        for i, (p, n) in enumerate(zip(prev, new)):
            if p != n:
                return f"operand {i} changed {p} -> {n}"
    except TypeError:
        pass
    return f"signature changed {prev!r} -> {new!r}"


def _note_retrace(label, prev_sig, new_sig, reason=None):
    """Record one captured-program recompile: counter + structured log +
    dispatch-ring entry, so a watchdog crash report written later names
    the retrace cause instead of showing a silent compile stall."""
    reason = reason or _sig_reason(prev_sig, new_sig)
    _STATS["capture_retraces"] += 1
    entry = {"label": label, "reason": reason, "prev": repr(prev_sig),
             "new": repr(new_sig), "t": time.time()}
    with _LOCK:
        _RETRACE_LOG.append(entry)
        if len(_RETRACE_LOG) > _RETRACE_LOG_CAP:
            del _RETRACE_LOG[:-_RETRACE_LOG_CAP]
    _profiler.record_dispatch(f"capture_retrace:{label}:{reason}")
    _obs_flight.record("retrace", label=label, reason=reason)
    return entry


def note_recapture(label, prev, new, reason=None):
    """Public forensics entry for compile-site owners (the parallel
    ``ShardedTrainer``, serving): a program that must be REBUILT — mesh
    shrink, ``set_learning_rate``, elastic re-capture — records why,
    exactly like an in-place signature retrace."""
    return _note_retrace(label, prev, new, reason=reason)


# -------------------------------------------------------- fingerprinting

def fingerprint(parts):
    """THE shared key-schema digest for every capture/AOT compile site
    (gluon trainer steps, sharded step programs, serving buckets): a
    stable 32-hex hash of a structural-identity dict. One helper so a
    schema change (new field, version bump) cannot fork the cache-key
    format across sites."""
    return hashlib.sha256(json.dumps(
        parts, sort_keys=True, default=repr).encode()).hexdigest()[:32]


def code_sig(fn):
    """Structural signature of a callable's *computation*: its bytecode
    + consts, recursing into nested code objects (comprehensions, inner
    defs). Param shapes alone cannot distinguish ``relu`` from ``tanh``
    or one lambda loss body from another — without this in the program
    fingerprint a warm AOT cache would silently serve the wrong compiled
    program."""
    import types

    code = getattr(fn, "__code__", None)
    if code is None:  # callable object: sign its class's call path
        for name in ("hybrid_forward", "forward", "__call__"):
            meth = getattr(type(fn), name, None)
            code = getattr(meth, "__code__", None)
            if code is not None:
                break
    if code is None:
        return repr(fn)
    out = []
    stack = [code]
    while stack:
        c = stack.pop()
        out.append(c.co_code.hex())
        for const in c.co_consts:
            if isinstance(const, types.CodeType):
                stack.append(const)
            else:
                out.append(repr(const))
    return hashlib.sha256("|".join(out).encode()).hexdigest()[:16]


def net_sig(net):
    """Structural signature of a gluon block tree: the repr (layer
    types, activations, unit counts) + every distinct block class's
    forward bytecode, so architecture changes that keep the param
    shapes identical still change the program fingerprint."""
    parts = [repr(net)]
    seen = set()
    stack = [net]
    while stack:
        b = stack.pop()
        cls = type(b)
        key = f"{cls.__module__}.{cls.__qualname__}"
        if key not in seen:
            seen.add(key)
            fwd = getattr(b, "hybrid_forward", None) \
                or getattr(b, "forward", None)
            parts.append(f"{key}:{code_sig(fwd) if fwd else ''}")
        stack.extend(getattr(b, "_children", {}).values())
    return hashlib.sha256("|".join(sorted(parts)).encode()).hexdigest()[:16]


# ------------------------------------------------------- sanctioned compile

def _compile_jit(fn, jit_kwargs, name):
    """THE sanctioned ``jax.jit`` site for captured programs (graftlint
    TS002): every capture/AOT executable — trainer steps, elastic
    grad/apply programs, serving bucket forwards, deserialized AOT
    artifacts — compiles here, so donation/sharding conventions and the
    capture counters cannot be bypassed by a stray raw jit. ``name``
    (the compile site's label) names the jitted function, and so the XLA
    module: a device trace reads ``jit_sharded_step``,
    ``jit_decode_prefill64``, not ``jit_step`` / ``jit_fn`` for all."""
    import jax

    @functools.wraps(fn)        # keeps the argument names jax reads
    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, **{k: v for k, v in jit_kwargs.items()
                             if v is not None})


# ----------------------------------------------------------- scalar sessions

_TLS = threading.local()


def _session():
    return getattr(_TLS, "session", None)


class _ScalarSession:
    """Dispatch-hook session threading dynamic scalar params through a
    captured program. Modes:

    - ``discover``: eager discovery pass — ops run normally; every
      dispatch of an op with declared ``dynamic_params`` records an
      operand slot (op name + keys + current values), fixing the slot
      order the compiled program consumes operands in.
    - ``record``: the jit trace — the same dispatches consume operand
      *tracers* (the program's trailing inputs) instead of baking the
      Python float of the moment into the executable.
    - ``replay``: per-step refresh — the update sweep's *Python* re-runs
      (schedules, bias corrections, ``num_update`` bookkeeping advance
      exactly as eagerly) while ops with ``mutate`` slots are skipped
      via identity outputs, collecting fresh operand values with no
      device work.
    """

    __slots__ = ("mode", "slots", "values", "operands", "pos", "off")

    def __init__(self, mode, slots=None, operands=None):
        self.mode = mode
        self.slots = slots if slots is not None else []
        self.values = []
        self.operands = operands
        self.pos = 0
        self.off = 0

    def __enter__(self):
        if getattr(_TLS, "session", None) is not None:
            raise CaptureError("nested capture sessions are not supported")
        _TLS.session = self
        _install_hook()
        return self

    def __exit__(self, *exc):
        _TLS.session = None
        return False

    # ---- dispatch hook body (see registry._CAPTURE_HOOK)
    def on_dispatch(self, op, params, arrays, is_traced):
        mode = self.mode
        dyn_keys, dyn_vals, static = op.split_dynamic(params)
        if mode == "record":
            if not dyn_keys or not is_traced:
                return NotImplemented
            if self.pos >= len(self.slots) or \
                    self.slots[self.pos] != (op.name, dyn_keys):
                raise CaptureError(
                    f"scalar slot drift at #{self.pos}: traced "
                    f"{(op.name, dyn_keys)}, discovered "
                    f"{self.slots[self.pos] if self.pos < len(self.slots) else None}")
            ops_in = self.operands[self.off:self.off + len(dyn_keys)]
            self.pos += 1
            self.off += len(dyn_keys)
            return op.closed(static)(*arrays, **dict(zip(dyn_keys, ops_in)))
        if dyn_keys:
            self.slots.append((op.name, dyn_keys))
            self.values.extend(dyn_vals)
        if mode == "discover":
            return NotImplemented  # run normally; slots now known
        # replay: skip the array math of mutating update ops — their
        # results are discarded; only the scalar Python above matters
        slots_m = op.mutate_slots(params)
        if not slots_m:
            return NotImplemented
        n_primary = op.n_out(params)
        prim = arrays[slots_m[0]]
        outs = tuple([prim] * n_primary) + tuple(arrays[s] for s in slots_m)
        return outs if len(outs) > 1 else outs[0]


def _capture_dispatch_hook(op, params, arrays, device, is_traced):
    sess = getattr(_TLS, "session", None)
    if sess is None:
        return NotImplemented
    return sess.on_dispatch(op, params, arrays, is_traced)


_HOOK_INSTALLED = False


def _install_hook():
    global _HOOK_INSTALLED
    with _LOCK:
        if _HOOK_INSTALLED:
            return
        from .ops import registry

        registry._set_capture_hook(_capture_dispatch_hook)
        _HOOK_INSTALLED = True


# ------------------------------------------------------------ AOT artifacts

_MAGIC = b"MXTPUAOT1\n"


def _backend_sig():
    import jax

    devs = jax.devices()
    return f"{devs[0].platform}:{len(devs)}"


def _versions():
    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


class CompileCache:
    """On-disk store of compiled-program artifacts.

    Layout under the root: ``programs/<key>.aotx`` — a header (schema,
    jax/jaxlib versions, backend, payload SHA-256) followed by the
    ``jax.export`` serialization of the traced program. A warm load
    skips Python tracing/lowering; the XLA executable itself comes from
    jax's persistent compilation cache (``mxnet_tpu._configure_jax``).

    Invalidation (docs/capture.md): the key hashes the caller's
    structural fingerprint + avals/sharding/donation signature + backend
    topology + ``MXNET_TPU_COMPILE_CACHE_SALT``; the header carries the
    jax/jaxlib versions, so a version bump is detected as *stale* and
    recompiled in place. Corrupt artifacts (bad magic, truncated, hash
    mismatch, undeserializable) are treated identically — fresh compile,
    never a crash.
    """

    def __init__(self, root):
        self.root = root
        self.programs = os.path.join(root, "programs")
        os.makedirs(self.programs, exist_ok=True)

    # ------------------------------------------------------------------ keys
    def key(self, label, fingerprint, sig):
        blob = json.dumps({
            "label": label, "fingerprint": fingerprint, "sig": repr(sig),
            "backend": _backend_sig(), "salt": _cache_salt(),
            "schedule": _schedule_token(),
        }, sort_keys=True)
        return hashlib.sha256(blob.encode()).hexdigest()[:40]

    def _path(self, key):
        return os.path.join(self.programs, f"{key}.aotx")

    # ------------------------------------------------------------------- load
    def load(self, key):
        """Deserialize the artifact under ``key``; None on miss/stale/
        corrupt (counting each), never an exception."""
        path = self._path(key)
        if not os.path.isfile(path):
            _STATS["aot_cache_misses"] += 1  # absent: fresh trace+store
            return None
        try:
            with open(path, "rb") as f:
                blob = f.read()
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            off = len(_MAGIC)
            hlen = int.from_bytes(blob[off:off + 4], "big")
            header = json.loads(blob[off + 4:off + 4 + hlen])
            payload = blob[off + 4 + hlen:]
        except Exception:
            _STATS["aot_cache_corrupt"] += 1
            return None
        vers = _versions()
        if header.get("jax") != vers["jax"] \
                or header.get("jaxlib") != vers["jaxlib"] \
                or header.get("backend") != _backend_sig():
            _STATS["aot_cache_stale"] += 1
            try:  # never serveable again under this key: free it now
                os.remove(path)
            except OSError:
                pass
            return None
        if hashlib.sha256(payload).hexdigest() != header.get("sha256"):
            _STATS["aot_cache_corrupt"] += 1
            return None
        try:
            from jax import export as _export

            exported = _export.deserialize(bytearray(payload))
        except Exception:
            _STATS["aot_cache_corrupt"] += 1
            return None
        try:  # freshen mtime so the size-cap GC evicts cold artifacts,
            os.utime(path)  # not the most-reloaded ones
        except OSError:
            pass
        return exported

    # ------------------------------------------------------------------ store
    def store(self, key, exported, label=""):
        """Atomically persist one exported program; best-effort (a full
        disk must never fail the compile that produced the program)."""
        try:
            payload = bytes(exported.serialize())
            header = dict(_versions())
            header.update({
                "schema": 1, "backend": _backend_sig(), "label": label,
                "sha256": hashlib.sha256(payload).hexdigest(),
                "created": time.time(),
            })
            hbytes = json.dumps(header, sort_keys=True).encode()
            path = self._path(key)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(_MAGIC)
                f.write(len(hbytes).to_bytes(4, "big"))
                f.write(hbytes)
                f.write(payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            _STATS["aot_cache_writes"] += 1
            self.gc()
            return path
        except Exception:
            return None

    # --------------------------------------------------------------------- gc
    def gc(self, limit_bytes=None):
        """Size-cap eviction: while the cache exceeds
        ``MXNET_TPU_COMPILE_CACHE_MAX_MB``, delete the oldest-mtime
        program artifacts."""
        limit = (compile_cache_limit_bytes() if limit_bytes is None
                 else limit_bytes)
        evicted = evict_oldest(self.programs, limit)
        _STATS["aot_cache_evictions"] += evicted
        return evicted


_CACHES: dict = {}


def compile_cache():
    """The process CompileCache for ``MXNET_TPU_COMPILE_CACHE``, or None
    when persistence is off (read per call: tests flip the env var)."""
    d = cache_dir()
    if d is None:
        return None
    with _LOCK:
        cache = _CACHES.get(d)
        if cache is None:
            try:
                cache = CompileCache(d)
            except OSError:
                return None
            _CACHES[d] = cache
    return cache


_PERSISTENT_HITS = 0       # jax's cache_hits events, once listening
_LISTENING = False


def _on_jax_event(event, **_):
    global _PERSISTENT_HITS
    if event == "/jax/compilation_cache/cache_hits":
        _PERSISTENT_HITS += 1


def _listen_for_cache_hits():
    """Count jax's persistent-cache hits from now on. Only a traced
    process asks: an untraced one registers no listener."""
    global _LISTENING
    if not _LISTENING:
        import jax

        jax.monitoring.register_event_listener(_on_jax_event)
        _LISTENING = True


def _precompile(jitted, example_args, label, t0_ns, aot_hit=False):
    """Force trace + XLA compile now (build time), so first-step latency
    never lands inside an armed watchdog guard and a compile failure
    (a kernel Mosaic refuses, an OOM at link) surfaces here, named.
    Returns ``(compiled, seconds since t0_ns)``: the readings that time
    the ledger's ``compile_ms`` are the ones the ``capture.trace_lower``
    (from ``t0_ns``: tracing, lowering, an AOT export or load) and
    ``capture.compile`` (XLA, or the persistent cache's answer) spans
    are recorded from."""
    if _obs_trace.enabled():
        _listen_for_cache_hits()
    lowered = jitted.lower(*example_args)
    t1_ns = time.perf_counter_ns()
    hits = _PERSISTENT_HITS
    compiled = lowered.compile()
    t2_ns = time.perf_counter_ns()
    _obs_trace.record("capture.trace_lower", t0_ns, t1_ns - t0_ns,
                      label=label, aot_hit=aot_hit)
    _obs_trace.record("capture.compile", t1_ns, t2_ns - t1_ns, label=label,
                      cache_hit=_PERSISTENT_HITS > hits)
    return compiled, (t2_ns - t0_ns) / 1e9


def aot_compile(fn, *, label, fingerprint, example_args, sig=None,
                in_shardings=None, out_shardings=None, donate_argnums=()):
    """Compile ``fn`` through the sanctioned site, persisting/loading the
    traced program via the AOT cache when enabled.

    Warm path: deserialize the artifact (skips Python tracing and
    lowering) and compile its ``call`` — which jax's persistent
    compilation cache resolves to a stored executable (skips XLA
    compilation).
    Cold path: jit ``fn``, export with ``example_args``, store. Both
    paths execute the exported program form when a cache is configured,
    so cold and warm runs are bitwise-identical by construction.
    """
    jit_kwargs = {"in_shardings": in_shardings,
                  "out_shardings": out_shardings,
                  "donate_argnums": donate_argnums or None}
    t0_ns = time.perf_counter_ns()
    perf_fp = _perf_identity(fingerprint, example_args, sig)

    def _build(jitted, aot_hit=False):
        # static perf attribution (observability.perf): every compile
        # through this site — captured steps, sharded programs, serving
        # buckets — lands one ledger entry (cost/memory analysis + wall
        # compile time) under the SAME (fingerprint, signature)
        # identity that keys the AOT artifact, so the perf gate and the
        # compile cache agree on identity by construction and two
        # programs can never merge into one entry
        compiled, seconds = _precompile(jitted, example_args, label, t0_ns,
                                        aot_hit=aot_hit)
        _obs_perf.note_compile(label, perf_fp, compiled, seconds,
                               aot_hit=aot_hit)
        return compiled

    cache = compile_cache()
    if cache is None or not enabled():
        return _build(_compile_jit(fn, jit_kwargs, name=label))
    key = cache.key(label, fingerprint, sig if sig is not None
                    else _avals_sig(example_args))
    # load() counts the outcome: absent -> misses, version/backend
    # mismatch -> stale, unreadable -> corrupt (each a distinct series,
    # so cold-cache misses never masquerade as invalidation churn)
    exported = cache.load(key)
    aot_hit = exported is not None
    if exported is None:
        jitted = _compile_jit(fn, jit_kwargs, name=label)
        try:
            from jax import export as _export

            exported = _export.export(jitted)(*example_args)
            cache.store(key, exported, label=label)
        except Exception:
            # program not exportable (callbacks, unsupported primitive):
            # serve the plain executable; persistence is best-effort
            return _build(jitted)
    else:
        _STATS["aot_cache_hits"] += 1
    wrapped = _compile_jit(exported.call,
                           {"donate_argnums": donate_argnums or None},
                           name=label)
    return _build(wrapped, aot_hit=aot_hit)


def _avals_sig(args):
    """Flat (shape, dtype, sharding) signature of a pytree of arrays."""
    import jax

    out = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        sh = getattr(leaf, "sharding", None)
        out.append((shape, dtype, repr(sh) if sh is not None else None))
    return tuple(out)


def _perf_identity(fingerprint, example_args, sig=None):
    """The perf-ledger identity of one compiled program: the caller's
    structural fingerprint folded with its aval signature — exactly the
    pair the AOT cache key hashes. Execution sites recompute this from
    the same inputs so their timings land on the entry their compile
    created."""
    full_sig = sig if sig is not None else _avals_sig(example_args)
    return _obs_perf.combined_fingerprint(fingerprint, repr(full_sig))


# ------------------------------------------------------------- CapturedExec

class CapturedExec:
    """A keyed captured executable: per-signature compile cache with
    retrace forensics and AOT persistence.

    The compile path for ``parallel.ShardedTrainer`` fused/elastic steps
    and serving ``Predictor`` bucket forwards. ``sig_argnums`` selects
    which positional args key the per-call signature (the batch operands;
    state avals are fixed per instance and belong in ``fingerprint``), so
    the steady-state hot path costs one small tuple build + dict hit.
    """

    def __init__(self, fn, *, label, fingerprint="", in_shardings=None,
                 out_shardings=None, donate_argnums=(), sig_argnums=()):
        self._fn = fn
        self.label = label
        self.fingerprint = fingerprint
        self._in_shardings = in_shardings
        self._out_shardings = out_shardings
        self._donate = tuple(donate_argnums or ())
        self._sig_argnums = tuple(sig_argnums)
        self._entries = {}
        self._entry_fps = {}  # sig -> perf-ledger identity (fp ⊕ avals)
        self._last_sig = None
        self._lock = threading.Lock()

    def _sig_of(self, args):
        return tuple((tuple(args[i].shape), str(args[i].dtype))
                     for i in self._sig_argnums)

    def __call__(self, *args):
        sig = self._sig_of(args)
        entry = self._entries.get(sig)
        if entry is None:
            with self._lock:
                entry = self._entries.get(sig)
                if entry is None:
                    if self._last_sig is not None or self._entries:
                        _note_retrace(self.label, self._last_sig, sig)
                    _STATS["capture_misses"] += 1
                    avals = _avals_sig(args)
                    entry = aot_compile(
                        self._fn, label=self.label,
                        fingerprint=self.fingerprint,
                        example_args=args, sig=avals,
                        in_shardings=self._in_shardings,
                        out_shardings=self._out_shardings,
                        donate_argnums=self._donate)
                    self._entry_fps[sig] = _perf_identity(
                        self.fingerprint, args, avals)
                    self._entries[sig] = entry
                    self._last_sig = sig
        else:
            _STATS["capture_hits"] += 1
        # dynamic perf attribution: with MXNET_TPU_OBS_DEVICE_TIME on,
        # every call blocks on its outputs (dependency-chained timing,
        # PERF.md) and feeds THIS signature's ledger entry (the same
        # fp ⊕ avals identity its compile registered); off, this is one
        # global check around a plain call
        return _obs_perf.timed_call(entry, args, self.label,
                                    self._entry_fps[sig])

    @property
    def compiled_signatures(self):
        return sorted(self._entries)


# ------------------------------------------------- gluon Trainer capture

def _absorb_session(outer, inner):
    """Merge a nested TraceSession's reads/mutations into ``outer`` —
    used when the captured step wraps its update sweep in its own
    session (to learn pre/post values for the sentinel select) while the
    enclosing discovery/trace session still needs every state cell."""
    if outer is None:
        return
    for nd_ in inner.captured:
        if id(nd_) in outer.created:
            continue
        outer.orig.setdefault(id(nd_), inner.orig[id(nd_)])
        if id(nd_) not in outer._captured_ids:
            outer._captured_ids.add(id(nd_))
            outer.captured.append(nd_)
    for nd_ in inner.mutated:
        if id(nd_) in outer.created:
            continue
        if id(nd_) not in outer._mutated_ids:
            outer._mutated_ids.add(id(nd_))
            outer.mutated.append(nd_)


class CapturedTrainerStep:
    """One gluon training step — forward, backward, gradient allreduce,
    optimizer sweep, sentinel finite-check — as a single donated XLA
    executable with dynamic scalar operands.

    Bitwise-equal to the eager path (eager fwd/bwd + ``Trainer.step``
    with or without ``engine.bulk``-ed updates), including optimizers
    whose per-step scalars drift (Adam bias correction, lr schedules):
    those enter as runtime operands refreshed by a per-step scalar
    replay, not baked constants (docs/capture.md).

    Parameters
    ----------
    net : initialized gluon Block
    loss_fn : callable(pred_nd, label_nd) -> NDArray (head grad = ones,
        exactly like calling ``loss.backward()`` eagerly)
    trainer : gluon.Trainer (``update_on_kvstore`` unsupported)
    batch_size : rescale denominator for ``Trainer.step``; default = the
        batch's row count
    sentinel : HealthSentinel; default = the one attached to ``trainer``
        (which is bypassed on the captured path — the check runs fused,
        the policy applies on the host from the returned flag)
    loss_scaler : amp.LossScaler — its scale becomes a runtime operand:
        the loss is scaled before backward, gradients unscale before the
        finite check and update, and the scaler's host schedule advances
        from the program's overflow flag (``note_finite``, so
        ``has_overflow`` never host-syncs under capture).
    numerics : observability.numerics.NumericsTap — in-graph numerics
        telemetry: per-layer/per-param stats computed on-device as one
        extra side output, with sampling cadence and stat selection as
        runtime operands (docs/observability.md "Numerics telemetry").
        Default: armed from ``MXNET_TPU_NUMERICS``; None keeps the
        program identical to the pre-telemetry build.
    """

    def __init__(self, net, loss_fn, trainer, batch_size=None,
                 sentinel=None, loss_scaler=None, numerics=None,
                 label="trainer_step"):
        self.net = net
        self.loss_fn = loss_fn
        self.trainer = trainer
        self.label = label
        self._batch_size = batch_size
        if not trainer._kv_initialized:
            trainer._init_kvstore()
        if trainer._update_on_kvstore:
            raise CaptureError(
                "capture() does not support update_on_kvstore trainers "
                "(the update runs outside the step program)")
        self.sentinel = sentinel if sentinel is not None \
            else getattr(trainer, "_sentinel", None)
        self.loss_scaler = loss_scaler
        self.numerics = numerics if numerics is not None \
            else _obs_numerics.default_tap()
        if self.numerics is not None:
            self.numerics.bind(net, trainer)
        self._entries = {}
        self._last_sig = None
        self._step_count = 0
        # last step's in-graph fingerprint output (resilience.integrity;
        # lazy — host-read only on last_fingerprint access)
        self._last_fp_out = None

    @property
    def last_fingerprint(self):
        """uint32 fingerprint of the last executed step, or None when
        fingerprinting is off (resilience.integrity). Identical across
        the captured, eager-fallback, and bulk paths by construction."""
        if self._last_fp_out is None:
            return None
        import numpy as np

        return int(np.asarray(self._last_fp_out))

    def _note_eager_fp(self):
        """Host-side fingerprint of the step that just ran eagerly (the
        kill-switch / capture-failure path) — folds the same operand set
        as the in-graph output, so eager and captured agree bitwise."""
        from .resilience import integrity as _integrity

        if not _integrity.fingerprint_enabled():
            self._last_fp_out = None
            return
        import numpy as np

        named_p, named_g = _integrity.net_named_state(self.net)
        self._last_fp_out = np.uint32(
            _integrity.step_fold_host(named_p, named_g))
        _integrity.note_fingerprint_step()

    # ------------------------------------------------------------ step python
    def _opt_host_snapshot(self):
        opt = self.trainer._optimizer
        return (opt.num_update, dict(opt._index_update_count),
                opt.rescale_grad)

    def _opt_host_restore(self, snap):
        opt = self.trainer._optimizer
        opt.num_update, count, opt.rescale_grad = snap
        opt._index_update_count = dict(count)

    def _grad_list(self):
        out = []
        for p in self.trainer._params:
            if p.grad_req != "null":
                out.extend(p.list_grad())
        return out

    def _health_flags(self, grads):
        """Fused health check over the gradients, as traced values:
        ``(finite, norm_ok_or_None)`` — ``multi_all_finite`` plus the
        grad-norm bound when the sentinel sets one, mirroring
        ``HealthSentinel._grads_healthy`` (two separate flags so the
        host attributes a trip to the same counter eager would:
        ``sentinel_nonfinite`` vs ``sentinel_grad_norm_trips``)."""
        from .ndarray import ndarray as _nd

        finite = _nd.imperative_invoke(
            "multi_all_finite", *grads, num_arrays=len(grads))[0]
        flag = finite.data_.reshape(())
        thr = (self.sentinel.grad_norm_threshold
               if self.sentinel is not None else None)
        if thr is None:
            return flag, None
        import jax.numpy as jnp

        sq = _nd.imperative_invoke(
            "multi_sum_sq", *grads, num_arrays=len(grads))
        total = sum(s.data_.reshape(()) for s in sq)
        # same comparison shape as eager (norm vs threshold, not the
        # squared form) so threshold-boundary rounding agrees
        norm_ok = jnp.sqrt(total) <= jnp.float32(thr)
        return flag, norm_ok

    def _run_step_python(self, x_nd, y_nd, batch_size, scale_val=None,
                         check_gate=None, tap_ops=None):
        """The step body re-run by discovery and by the jit trace. The
        update sweep runs in a nested TraceSession so the sentinel
        select knows each cell's pre-update value. ``check_gate`` is the
        sentinel's cadence operand (1.0 = this step is a check step):
        on off-cadence steps the eager ``before_update`` never looks at
        the gradients, so the select must let even an unhealthy batch
        through — except the loss-scaler's finiteness gate, which eager
        AMP applies every step. ``tap_ops`` is the numerics tap's
        column-selection-mask operand and marks the SAMPLED program
        variant: when present, the per-layer stats matrix computes and
        rides out as one extra side output; when None with a tap armed,
        this body builds the base (off-cadence) variant — no hooks, no
        stats, only the finite gate for halt/skip policies."""
        import jax.numpy as jnp

        from . import autograd
        from .jit import TraceSession, _active
        from .ndarray.ndarray import NDArray
        from .resilience import integrity as _integrity

        trainer = self.trainer
        tap = self.numerics
        # "full" = the sampled-step program variant (stats side output);
        # with tap_ops=None and a tap armed this body builds the BASE
        # variant: for a record-policy tap literally the untapped
        # program, for halt/skip the untapped program + the fused
        # finite flag and its weight-write select (the protection that
        # must run every step regardless of sampling)
        full = tap is not None and tap_ops is not None
        hooks = acts = None
        if full:
            hooks, acts = tap.install_hooks(self.net)
        try:
            with autograd.record():
                out = self.net(x_nd)
                loss = self.loss_fn(out, y_nd)
                if scale_val is not None:
                    scale_nd = NDArray(jnp.asarray(scale_val, jnp.float32))
                    sess = _active()
                    if sess is not None:
                        sess.note_created(scale_nd)
                    loss_b = loss * scale_nd
                else:
                    loss_b = loss
        finally:
            if full:
                tap.remove_hooks(hooks)
        loss_b.backward()
        grads = self._grad_list()
        if scale_val is not None:
            inv = 1.0 / scale_nd
            for g in grads:
                g._set_data((g * inv)._data)
        # a record-policy tap adds NO per-step device work: its finite
        # signal rides the sampled stats matrix's nonfinite column, so
        # the fused every-step finite reduction is only built when
        # something gates on it (sentinel, AMP scaler, halt/skip tap)
        flags = self._health_flags(grads) if (
            self.sentinel is not None or scale_val is not None
            or (tap is not None and tap.gates_updates)) else None
        tap_params = tap_pre = None
        if full:
            tap_params = tap.tapped_params(trainer)
            tap_pre = [p.data()._data for p in tap_params]
        outer = _active()
        trainer._optimizer.rescale_grad = trainer._scale / batch_size
        with TraceSession() as upd:
            trainer._allreduce_grads()
            trainer._update()
        _absorb_session(outer, upd)
        tap_out = None
        if full:
            # stats see the RAW computed update (post - pre), before the
            # health select below decides whether it lands
            named_grads = []
            for p in tap_params:
                for g in p.list_grad():
                    named_grads.append((p.name, g.data_))
            named_pre = [(p.name, d) for p, d in zip(tap_params, tap_pre)]
            named_post = [(p.name, p.data()._data) for p in tap_params]
            tap_out = tap.graph_stats(named_grads, named_pre, named_post,
                                      acts, tap_ops)
        if flags is not None:
            finite, norm_ok = flags
            ok = finite if norm_ok is None \
                else jnp.logical_and(finite, norm_ok)
            passed = None
            if self.sentinel is not None or scale_val is not None:
                if check_gate is not None:
                    passed = jnp.logical_or(ok, check_gate == 0)
                    if scale_val is not None:
                        # AMP overflow skips are never sampled
                        passed = jnp.logical_and(passed, finite)
                else:
                    passed = ok
            if tap is not None and tap.gates_updates:
                # halt/skip numerics policies: a non-finite batch never
                # touches the weights, sampled or not (the AMP rule); a
                # record-only tap leaves the program bitwise-transparent
                passed = finite if passed is None \
                    else jnp.logical_and(passed, finite)
            if passed is not None:
                for cell in upd.mutated:
                    cell._data = jnp.where(passed, cell._data,
                                           upd.orig[id(cell)])
        # in-graph step fingerprint (resilience.integrity): folded AFTER
        # the sentinel select so it digests the values that actually
        # landed — rides out as one extra scalar of the SAME program
        fp = None
        if _integrity.fingerprint_enabled():
            fp = _integrity.step_fold(*_integrity.net_named_state(self.net))
        return loss, flags, tap_out, fp

    # ------------------------------------------------------------------ build
    def _build(self, x_nd, y_nd, batch_size, sig):
        import jax.numpy as jnp

        from .jit import TraceSession
        from .ndarray.ndarray import NDArray

        import numpy as np

        host_snap = self._opt_host_snapshot()
        scale0 = (self.loss_scaler.loss_scale
                  if self.loss_scaler is not None else None)
        has_gate = self.sentinel is not None
        has_tap = self.numerics is not None
        tap0 = self.numerics.sel_values() if has_tap else None
        with _ScalarSession("discover") as scal, TraceSession() as sess:
            sess.note_created(x_nd)
            sess.note_created(y_nd)
            try:
                self._run_step_python(x_nd, y_nd, batch_size, scale0,
                                      1.0 if has_gate else None, tap0)
            finally:
                for m in sess.mutated:
                    m._data = sess.orig[id(m)]
                self._opt_host_restore(host_snap)
        slots = list(scal.slots)
        n_dyn = len(scal.values)
        state_cells = list(sess.captured)
        has_flag = self.sentinel is not None \
            or self.loss_scaler is not None \
            or (has_tap and self.numerics.gates_updates)
        has_scale = self.loss_scaler is not None
        has_norm = self.sentinel is not None \
            and self.sentinel.grad_norm_threshold is not None
        from .resilience import integrity as _integrity

        has_fp = _integrity.fingerprint_enabled()
        tap_rows = self.numerics.rows if has_tap else ()
        step = self

        def make_pure(with_tap):
            """One program variant: ``with_tap`` is the SAMPLED-step
            form (stats side output + one trailing mask operand); the
            base form is the off-cadence hot path — identical to the
            pre-telemetry program for a record-policy tap, plus only
            the fused finite gate for halt/skip policies."""

            def pure(arg_datas, state_datas, dyn_vals):
                saved = [c._data for c in state_cells]
                snap = step._opt_host_snapshot()
                try:
                    for c, d in zip(state_cells, state_datas):
                        c._data = d
                    x2, y2 = NDArray(arg_datas[0]), NDArray(arg_datas[1])
                    idx = n_dyn
                    scale_t = dyn_vals[idx] if has_scale else None
                    idx += int(has_scale)
                    gate_t = dyn_vals[idx] if has_gate else None
                    idx += int(has_gate)
                    tap_t = dyn_vals[idx] if with_tap else None
                    with _ScalarSession("record", slots, dyn_vals), \
                            TraceSession() as inner:
                        inner.note_created(x2)
                        inner.note_created(y2)
                        loss, flags, tap_out, fp = step._run_step_python(
                            x2, y2, batch_size, scale_t, gate_t, tap_t)
                    if with_tap and \
                            tuple(step.numerics.rows) != tuple(tap_rows):
                        raise CaptureError(
                            "numerics tap row plan drifted between "
                            f"discovery and trace ({len(tap_rows)} -> "
                            f"{len(step.numerics.rows)} rows); recapture "
                            "with a fresh CapturedTrainerStep")
                    outs = [loss.data_]
                    if flags is not None:
                        outs.append(flags[0])
                        if flags[1] is not None:
                            outs.append(flags[1])
                    if fp is not None:
                        outs.append(fp)
                    if tap_out is not None:
                        outs.append(tap_out)
                    new_state = [c._data for c in state_cells]
                finally:
                    for c, d in zip(state_cells, saved):
                        c._data = d
                    step._opt_host_restore(snap)
                return outs, new_state

            return pure

        fingerprint = self._fingerprint(sig, slots, state_cells)
        # numpy f32 scalars: the per-step refresh passes np.float32 too,
        # so the example avals match the steady-state call exactly (a
        # Python float would trace a weak-typed operand and the compiled
        # program would reject the refreshed values)
        base_dyn = ([np.float32(v) for v in scal.values]
                    + ([np.float32(scale0)] if has_scale else [])
                    + ([np.float32(1.0)] if has_gate else []))
        example = ([x_nd.data_, y_nd.data_],
                   [c._data for c in state_cells], list(base_dyn))
        fn = aot_compile(make_pure(False), label=self.label,
                         fingerprint=fingerprint, example_args=example,
                         donate_argnums=(1,))
        fn_tap = None
        fp_tap = None
        if has_tap:
            # the sampled-step variant is its own program (extra output
            # + trailing mask operand) under a variant-tagged identity;
            # cadence picks between the two PREBUILT executables, so an
            # interval change can never retrace
            fingerprint_tap = self._fingerprint(sig, slots, state_cells,
                                                variant="tap_sample")
            example_tap = ([x_nd.data_, y_nd.data_],
                           [c._data for c in state_cells],
                           list(base_dyn) + [self.numerics.sel_values()])
            fn_tap = aot_compile(make_pure(True),
                                 label=f"{self.label}:tap_sample",
                                 fingerprint=fingerprint_tap,
                                 example_args=example_tap,
                                 donate_argnums=(1,))
            fp_tap = _perf_identity(fingerprint_tap, example_tap)
        entry = {
            "fn": fn, "fn_tap": fn_tap, "cells": state_cells,
            "slots": slots,
            "has_flag": has_flag, "has_scale": has_scale,
            "has_gate": has_gate, "has_norm": has_norm,
            "has_tap": has_tap, "tap_rows": tap_rows,
            "tap_gates": has_tap and self.numerics.gates_updates,
            "has_fp": has_fp,
            "fp_idx": 1 + int(has_flag) + int(has_norm),
            "tap_idx": 1 + int(has_flag) + int(has_norm) + int(has_fp),
            "states_ref": self.trainer._updaters[0].states,
            "ctx": x_nd.context,
            # the same fp ⊕ avals identity aot_compile just ledgered,
            # so the per-step device timings land on this program's entry
            "fingerprint": _perf_identity(fingerprint, example),
            "fingerprint_tap": fp_tap,
        }
        self._entries[sig] = entry
        self._last_sig = sig
        return entry

    def _fingerprint(self, sig, slots, state_cells, variant=None):
        trainer = self.trainer
        opt = trainer._optimizer
        parts = {
            # base vs tap_sample program variant of one captured step
            "variant": variant,
            "net": [(n, tuple(c.shape), str(c.dtype))
                    for n, c in sorted(
                        self.net._collect_params_with_prefix().items())],
            # param avals can't distinguish relu from tanh or one lambda
            # loss from another — the computation structure must key too
            "net_struct": net_sig(self.net),
            "loss_code": code_sig(self.loss_fn),
            "optimizer": type(opt).__name__,
            "loss": getattr(self.loss_fn, "__qualname__",
                            type(self.loss_fn).__name__),
            "sig": repr(sig),
            "slots": repr(slots),
            "n_state": len(state_cells),
            "sentinel": None if self.sentinel is None else
                (self.sentinel.policy, self.sentinel.grad_norm_threshold),
            "scaler": self.loss_scaler is not None,
            # row plan + column schema + gating semantics; cadence and
            # stat selection are runtime operands and must NOT key here
            "numerics": None if self.numerics is None
                else self.numerics.plan_signature(),
            # the in-graph step fingerprint adds an output to the traced
            # program (resilience.integrity) — an AOT artifact compiled
            # with the other setting must never false-hit
            "integrity": _integrity_enabled(),
        }
        return fingerprint(parts)

    # ------------------------------------------------------------------- call
    def _sig_of(self, x_nd, y_nd, batch_size):
        return ((tuple(x_nd.shape), str(x_nd.data_.dtype)),
                (tuple(y_nd.shape), str(y_nd.data_.dtype)),
                float(batch_size))

    def _entry_valid(self, entry):
        """A checkpoint restore (``set_states_bytes``) rebinds the
        updater's state dict to fresh cells; the captured program must
        then re-discover its state list instead of silently reading the
        orphaned ones."""
        return entry["states_ref"] is self.trainer._updaters[0].states

    def __call__(self, x, y, batch_size=None):
        import numpy as np

        from .ndarray.ndarray import NDArray
        from .resilience import faults as _faults
        from .resilience import watchdog as _watchdog

        _STATS["capture_steps"] += 1
        x_nd = x if isinstance(x, NDArray) else NDArray(x)
        y_nd = y if isinstance(y, NDArray) else NDArray(y)
        if not enabled():
            _STATS["capture_fallback_eager"] += 1
            return self._eager_step(x_nd, y_nd, batch_size)
        # the nan_grad drill: a captured program cannot be poisoned from
        # the outside per-step, so the fault poisons the batch instead —
        # NaN flows through the real compiled fwd/bwd into the fused
        # sentinel check, same detection surface as the eager hook
        if _faults.active("nan_grad"):
            f = _faults.get("nan_grad")
            if f is not None and f.should_fire():
                x_nd = NDArray(x_nd.data_ * np.float32("nan"), x_nd.context)
        # the nonfinite_grad drill's captured form: poison the TARGET
        # layer's weight so the NaN flows through the real compiled
        # fwd/bwd into that layer's activations and gradients — the
        # detection surface (fused finite flag + per-layer tap rows)
        # and the bisect tool then localize it, never the injection
        _faults.maybe_nonfinite_grad(self.trainer._params, where="param")
        bs = batch_size if batch_size is not None else (
            self._batch_size if self._batch_size is not None
            else int(x_nd.shape[0]))
        sig = self._sig_of(x_nd, y_nd, bs)
        entry = self._entries.get(sig)
        if entry is not None and not self._entry_valid(entry):
            _note_retrace(self.label, sig, sig,
                          reason="trainer state rebound "
                                 "(checkpoint restore)")
            del self._entries[sig]
            entry = None
        if entry is None:
            if self._last_sig is not None and self._last_sig != sig:
                _note_retrace(self.label, self._last_sig, sig)
            _STATS["capture_misses"] += 1
            try:
                entry = self._build(x_nd, y_nd, bs, sig)
            except CaptureError:
                _STATS["capture_fallback_eager"] += 1
                return self._eager_step(x_nd, y_nd, batch_size)
        else:
            _STATS["capture_hits"] += 1
        # scalar replay: re-run the update sweep's Python (schedules,
        # bias corrections, num_update) with array math skipped, giving
        # this step's fresh operand values. Snapshot the host bookkeeping
        # first: a batch the fused health check rejects never reaches the
        # update sweep on the eager path, so its replay must un-advance
        # (Adam's t, num_update) to stay bitwise with eager skip_batch.
        host_snap = self._opt_host_snapshot()
        self.trainer._optimizer.rescale_grad = \
            self.trainer._scale / bs
        with _ScalarSession("replay") as rep:
            self.trainer._update()
        if [s for s in rep.slots] != entry["slots"]:
            self._opt_host_restore(host_snap)  # undo the replay advance
            raise CaptureError(
                f"scalar replay diverged from the captured program "
                f"(captured {len(entry['slots'])} slots, replayed "
                f"{len(rep.slots)}); recapture with a fresh "
                "CapturedTrainerStep")
        dyn = [np.float32(v) for v in rep.values]
        if entry["has_scale"]:
            dyn.append(np.float32(self.loss_scaler.loss_scale))
        # sentinel cadence (HealthSentinel.check_every): same counter
        # and sampling rule as the eager before_update — an off-cadence
        # step's gate operand disables the in-program select, so even an
        # unhealthy batch updates the weights, exactly like eager
        checking = False
        if self.sentinel is not None:
            self.sentinel._step += 1
            checking = (self.sentinel._step - 1) \
                % self.sentinel.check_every == 0
        if entry["has_gate"]:
            dyn.append(np.float32(1.0 if checking else 0.0))
        tap_sampled = False
        if entry["has_tap"]:
            # the cadence picks between the two PREBUILT program
            # variants and the column selection is a trailing operand
            # of the sampled one: changing either at runtime never
            # retraces (tested by the compile-count probe)
            tap_sampled = self.numerics.tick()
            if tap_sampled:
                dyn.append(self.numerics.sel_values())
        self._step_count += 1
        _watchdog.note_step(self._step_count)
        try:
            # numerics_sampled marks the tap's cadence steps: they pay
            # the stats variant + host pull by design, so the step-time
            # drift detector excludes them (a configured sampling
            # cadence is not an anomaly)
            span_attrs = {"step": self._step_count}
            if tap_sampled:
                span_attrs["numerics_sampled"] = True
            with _obs_trace.span("train.captured_step", **span_attrs), \
                    _watchdog.guard("step",
                                    detail="capture.CapturedTrainerStep",
                                    step=self._step_count):
                _faults.maybe_hang("hang_step")
                with _obs_trace.span("captured.execute"):
                    outs, new_state = _obs_perf.timed_call(
                        entry["fn_tap"] if tap_sampled else entry["fn"],
                        ([x_nd.data_, y_nd.data_],
                         [c._data for c in entry["cells"]], dyn),
                        self.label,
                        entry["fingerprint_tap"] if tap_sampled
                        else entry["fingerprint"])
        except _watchdog.StallError as e:
            if not self._stall_rollback(e):
                # the stalled step never applied: un-advance the replay's
                # host bookkeeping (Adam's t, num_update) so a caller that
                # catches the stall and keeps training stays bitwise with
                # eager (a successful rollback restores it from the ckpt)
                self._opt_host_restore(host_snap)
                raise
            return None
        for c, v in zip(entry["cells"], new_state):
            c._data = v
        loss = NDArray(outs[0], entry["ctx"])
        if entry.get("has_fp"):
            from .resilience import integrity as _integrity

            self._last_fp_out = outs[entry["fp_idx"]]
            _integrity.note_fingerprint_step()
        else:
            self._last_fp_out = None
        # reading the flag is a host sync that breaks async dispatch
        # pipelining. Anything that GATES on it — sentinel, AMP scaler,
        # a halt/skip tap — reads it every step: the in-program select
        # and the host bookkeeping (the un-advance below, Adam's t /
        # num_update) must stay in lockstep, or the replayed scalar
        # operands would drift from the reverted device state. Only a
        # record-policy tap (pure telemetry, nothing gated) defers to
        # the sampling cadence, deriving its finite signal from the
        # sampled matrix's nonfinite column.
        need_flag = entry["has_flag"] and (
            self.sentinel is not None or entry["has_scale"]
            or entry["tap_gates"] or tap_sampled)
        if entry["has_tap"] and not need_flag:
            # record-policy tap (or gating tap off-cadence): the finite
            # signal derives from the sampled matrix's nonfinite column
            stats_np = np.asarray(outs[entry["tap_idx"]]) \
                if tap_sampled else None
            self.numerics.on_step(self._step_count, None, stats_np,
                                  (x_nd, y_nd))
        if need_flag:
            finite_ok = bool(np.asarray(outs[1]).reshape(-1)[0])
            norm_ok = (bool(np.asarray(outs[2]).reshape(-1)[0])
                       if entry["has_norm"] else None)
            if entry["has_scale"]:
                # the in-graph flag IS the AMP all-finite check: note it
                # so LossScaler.has_overflow never host-syncs under
                # capture (amp.unscale consumes the noted flag)
                self.loss_scaler.note_finite(finite_ok)
            tap_gated = entry["tap_gates"] and not finite_ok
            gated = (not finite_ok) if not checking \
                else not (finite_ok and norm_ok is not False)
            if (gated and (checking or entry["has_scale"])) or tap_gated:
                # the gated update never applied: un-advance the
                # replay's host bookkeeping (Adam's t, num_update)
                self._opt_host_restore(host_snap)
            self._apply_flag(finite_ok, norm_ok, checking)
            if entry["has_tap"]:
                stats_np = np.asarray(outs[entry["tap_idx"]]) \
                    if tap_sampled else None
                # emission + divergence detectors + non-finite policy;
                # off-cadence steps never pull the stats matrix (the
                # finite flag above is the only per-step host read)
                self.numerics.on_step(self._step_count, finite_ok,
                                      stats_np, (x_nd, y_nd))
        return loss

    def _apply_flag(self, finite_ok, norm_ok, checking):
        """Host-side policy application from the program's fused health
        flags — the captured counterpart of ``HealthSentinel
        .before_update`` (weights were already gated by the in-program
        select, so every policy only does bookkeeping/restore here).
        ``checking`` follows the sentinel's ``check_every`` cadence:
        off-cadence steps do no sentinel bookkeeping at all (eager
        ``before_update`` returns before looking at the gradients); a
        loss-scaler overflow is still recorded every step."""
        from .resilience import sentinel as _sentinel

        scaler = self.loss_scaler
        if scaler is not None:
            scaler.update_scale(not finite_ok)
        s = self.sentinel
        if s is None or not checking:
            if scaler is not None and not finite_ok:
                _sentinel.note_skip("amp_overflow")
            return
        ok = finite_ok and norm_ok is not False
        _sentinel.note_check(
            ok, kind="nonfinite" if not finite_ok else "grad_norm")
        if ok:
            return
        s.last_reason = (
            "non-finite gradient (NaN/Inf) (captured step)"
            if not finite_ok else
            f"global grad norm exceeds threshold "
            f"{s.grad_norm_threshold:.3e} (captured step)")
        if s.policy == "raise":
            raise _sentinel.NumericHealthError(
                f"numeric health check failed at captured step "
                f"{self._step_count}: {s.last_reason}")
        if s.policy == "skip_batch" or s.manager is None:
            _sentinel.note_skip("sentinel")
            return
        restored = s.manager.restore_latest(net=s._net or self.net,
                                            trainer=self.trainer)
        if restored is None:
            raise _sentinel.NumericHealthError(
                "rollback requested (captured step) but no valid "
                f"checkpoint exists under {s.manager.directory}")
        _sentinel.note_skip("sentinel")
        _sentinel.note_rollback()

    def _stall_rollback(self, err):
        """Mirror ``Trainer._stall_rollback`` for the captured call."""
        from .resilience import watchdog as _watchdog

        s = self.sentinel
        if s is None or s.policy != "rollback" or s.manager is None:
            return False
        manifest = s.manager.restore_latest(net=s._net or self.net,
                                            trainer=self.trainer)
        if manifest is None:
            return False
        _watchdog.note_rollback(err, manifest)
        import warnings

        warnings.warn(
            f"captured step stalled ({err}); rolled back to checkpoint "
            f"step {manifest.get('step')} and skipped the step")
        return True

    def _eager_step(self, x_nd, y_nd, batch_size):
        """The identical step semantics, eagerly (kill switch and
        capture-failure fallback): plain fwd/bwd + ``Trainer.step`` with
        the sentinel attached, exactly the pre-capture hot loop. With a
        loss scaler the captured data flow is replicated by hand (scale
        loss, unscale grads, fused finite check gating the update)."""
        import numpy as np

        from . import autograd

        trainer = self.trainer
        bs = batch_size if batch_size is not None else (
            self._batch_size if self._batch_size is not None
            else int(x_nd.shape[0]))
        scaler = self.loss_scaler
        if scaler is None:
            reattach = self.sentinel is not None \
                and trainer._sentinel is None
            if reattach:
                trainer._sentinel = self.sentinel
            try:
                with autograd.record():
                    loss = self.loss_fn(self.net(x_nd), y_nd)
                loss.backward()
                trainer.step(bs)
            finally:
                if reattach:
                    trainer._sentinel = None
            self._note_eager_fp()
            return loss
        from .resilience import faults as _faults
        from .resilience import watchdog as _watchdog

        scale = float(scaler.loss_scale)
        scaler.clear_note()  # stale captured-step flag never answers
        with autograd.record():  # this eager step's has_overflow
            loss = self.loss_fn(self.net(x_nd), y_nd)
            loss_b = loss * scale
        loss_b.backward()
        s = self.sentinel
        checking = False
        if s is not None:
            s._step += 1
            checking = (s._step - 1) % s.check_every == 0
        trainer._optimizer.rescale_grad = trainer._scale / bs
        # mirror gluon.Trainer.step's guard/fault points: the kill-switch
        # path must keep the watchdog deadline, hang/NaN drills, and
        # stall rollback the resilience stack promises for every step
        try:
            with _watchdog.guard("step", detail="capture._eager_step",
                                 step=getattr(s, "_step", None)):
                _faults.maybe_hang("hang_step")
                grads = self._grad_list()
                inv = 1.0 / scale
                for g in grads:
                    g._set_data((g * inv)._data)
                _faults.maybe_nan_grads(self.trainer._params)
                _faults.maybe_nonfinite_grad(self.trainer._params)
                finite_t, norm_t = self._health_flags(grads)
                finite_ok = bool(np.asarray(finite_t).reshape(-1)[0])
                norm_ok = (bool(np.asarray(norm_t).reshape(-1)[0])
                           if norm_t is not None else None)
                scaler.note_finite(finite_ok)
                ok = finite_ok and norm_ok is not False
                if finite_ok and (ok or not checking):
                    trainer._allreduce_grads()
                    trainer._update()
        except _watchdog.StallError as e:
            if not self._stall_rollback(e):
                raise
            return None
        self._apply_flag(finite_ok, norm_ok, checking)
        self._note_eager_fp()
        return loss


    def attach_monitor(self, monitor):
        """``Monitor.install`` entry point for the compiled-tap path:
        ensures this step has a :class:`~.observability.numerics
        .NumericsTap` (creating a ``record``-policy, request-driven one
        when none is armed — ``Monitor.tic`` forces the sample, so the
        tap's own cadence stays off) and returns it. Attaching a tap to
        an already-built step is a program change: the built entries
        are dropped with a structured retrace reason, never silently."""
        tap = self.numerics
        if tap is None:
            tap = _obs_numerics.NumericsTap(interval=0, policy="record")
            tap.bind(self.net, self.trainer)
            self.numerics = tap
            if self._entries:
                _note_retrace(self.label, self._last_sig, self._last_sig,
                              reason="numerics tap attached "
                                     "(Monitor install)")
                self._entries.clear()
        return tap


class CapturedShardedStep:
    """Captured view of a ``parallel.ShardedTrainer``: the trainer's
    fused step is already one donated pjit program, and every one of its
    step/grads/apply programs compiles through the capture path — AOT
    persistence, retrace forensics, capture counters — so this wrapper
    just counts steps and delegates (watchdog, elastic microbatching,
    mesh-shrink recovery all apply unchanged; an elastic or mesh
    re-capture shows up in :func:`retrace_log` instead of recompiling
    silently)."""

    def __init__(self, trainer, label="sharded_step"):
        self.trainer = trainer
        self.label = label
        # no executable invalidation needed: every ShardedTrainer step/
        # grads/apply program already compiles through _capture_exec, so
        # a pre-built (possibly minutes-of-XLA) executable is kept

    def __call__(self, x, y, microbatches=None, length=None):
        _STATS["capture_steps"] += 1
        return self.trainer.step(x, y, microbatches=microbatches,
                                 length=length)

    @property
    def mesh(self):
        return self.trainer.mesh

    @property
    def batch_sharding(self):
        """The trainer's batch placement, passed through so the
        streaming layer's ``DevicePrefetcher.for_trainer`` accepts a
        captured step wherever it accepts the trainer itself."""
        return self.trainer.batch_sharding


def capture(trainer, net=None, loss_fn=None, **kwargs):
    """Capture a whole training step as one donated XLA executable.

    ``capture(sharded_trainer)`` returns a :class:`CapturedShardedStep`;
    ``capture(trainer, net=net, loss_fn=loss)`` (gluon) returns a
    :class:`CapturedTrainerStep`. With ``MXNET_TPU_CAPTURE=0`` the gluon
    wrapper executes the identical step eagerly (kill switch).
    """
    from .parallel.trainer import ShardedTrainer

    if isinstance(trainer, ShardedTrainer):
        return CapturedShardedStep(trainer, **kwargs)
    if net is None or loss_fn is None:
        raise CaptureError(
            "capture(gluon_trainer) needs net= and loss_fn= (the step "
            "program is fwd+bwd+update, not just the update sweep)")
    return CapturedTrainerStep(net, loss_fn, trainer, **kwargs)
