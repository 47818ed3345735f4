"""Tracing bridge: imperative MXNet-style code -> one XLA executable.

This is the TPU-native replacement for the reference's CachedOp
(src/imperative/cached_op.cc) and GraphExecutor bulking: instead of
replaying per-op engine pushes, we re-run the user's *imperative Python*
under `jax.jit` so the whole step (forward, backward tape, optimizer
updates, collectives) compiles into a single TPU executable.

Mechanics — the mutation->functional bridge (SURVEY.md §7 hard part 2):

1. Discovery pass: run the function eagerly inside a TraceSession. Every op
   dispatch reports its input/output cells; cells that are read but were
   created *before* the session are captured state (parameters, optimizer
   state, RNG key, BatchNorm stats). Cells mutated during the run are state
   outputs.
2. Compile: `jax.jit` a pure wrapper (args, state_in) -> (outs, state_out)
   that temporarily rebinds each captured cell to its tracer and re-runs the
   Python. Donated state buffers make updates in-place in HBM.
3. Execute: call the executable, write state outputs back into the cells.

Shape-keyed cache = the reference's per-shape CachedOp executables.
Requires the traced Python to be shape-deterministic (same discipline
hybridize imposes in the reference).
"""
from __future__ import annotations

import contextlib
import threading

from .base import MXNetError

__all__ = ["trace", "TracedFunction", "TraceSession"]

_TLS = threading.local()


def _sessions():
    if not hasattr(_TLS, "stack"):
        _TLS.stack = []
    return _TLS.stack


class TraceSession:
    """Records cell reads/mutations during a discovery run.

    ``name_blocks``: the session is a jax trace whose ops should carry
    the name of the gluon block that asked for them (:func:`scope`);
    ``parallel.functional_call`` sets it, the eager discovery passes do
    not."""

    def __init__(self, name_blocks=False):
        self.name_blocks = name_blocks
        self.created = set()      # id() of cells born inside the session
        self.captured = []        # pre-existing cells read by ops (ordered)
        self._captured_ids = set()
        self.mutated = []         # pre-existing cells mutated (ordered)
        self._mutated_ids = set()
        self.orig = {}            # id(cell) -> pre-session value (for rollback)
        self._keep = []           # strong refs so ids stay valid

    def __enter__(self):
        _sessions().append(self)
        return self

    def __exit__(self, *a):
        _sessions().pop()

    def note_created(self, nd):
        self.created.add(id(nd))
        self._keep.append(nd)

    def note_read(self, nd):
        if id(nd) in self.created or id(nd) in self._captured_ids:
            return
        self._captured_ids.add(id(nd))
        self.captured.append(nd)
        self.orig.setdefault(id(nd), nd._data)

    def note_mutated(self, nd):
        if id(nd) in self.created:
            return
        self.orig.setdefault(id(nd), nd._data)  # pre-mutation value
        if id(nd) not in self._captured_ids:
            self._captured_ids.add(id(nd))
            self.captured.append(nd)
        if id(nd) not in self._mutated_ids:
            self._mutated_ids.add(id(nd))
            self.mutated.append(nd)


def _active():
    s = _sessions()
    return s[-1] if s else None


def _notify_mutation(nd):
    s = _active()
    if s is not None:
        s.note_mutated(nd)


def _notify_io(inputs, outputs):
    s = _active()
    if s is not None:
        for x in inputs:
            s.note_read(x)
        for o in outputs:
            s.note_created(o)


_NO_SCOPE = contextlib.nullcontext()


def scope(name):
    """``jax.named_scope(name)`` while the innermost session names its
    blocks, else a shared no-op: the name lands in the ``op_name`` of
    every instruction traced inside (``observability.perf.op_names``
    hands the compiled program's names out). The eager path pays one
    call and one check, opens no scope and so retraces nothing."""
    sess = _active()
    if sess is None or not sess.name_blocks:
        return _NO_SCOPE
    import jax

    return jax.named_scope(name)


class no_trace:
    """Suspend trace-session capture. One-time side effects that happen to
    fire during a discovery pass (deferred parameter init, lazy state
    creation) must survive the discovery rollback and not become traced
    state, so they run with the session stack parked."""

    def __enter__(self):
        self._saved = list(_sessions())
        _TLS.stack.clear()
        return self

    def __exit__(self, *a):
        _TLS.stack.extend(self._saved)
        return False


class TracedFunction:
    """Shape-keyed jit cache over an imperative function of NDArrays."""

    def __init__(self, fn, static_argnums=(), donate_state=True, name=None):
        self.fn = fn
        self.static_argnums = tuple(static_argnums)
        self.donate_state = donate_state
        self.name = name or getattr(fn, "__name__", "traced")
        self._cache = {}

    def _key(self, args):
        from . import autograd

        parts = [autograd.is_training(), autograd.is_recording()]
        for i, a in enumerate(args):
            if i in self.static_argnums:
                parts.append(("static", a))
            else:
                parts.append((tuple(a.shape), str(a._data.dtype)))
        return tuple(parts)

    def __call__(self, *args):
        from .ndarray.ndarray import NDArray
        from . import autograd

        key = self._key(args)
        entry = self._cache.get(key)
        dyn = [a for i, a in enumerate(args) if i not in self.static_argnums]
        if entry is None:
            entry = self._build(args, key)
        jitted, pure, state_cells, n_out, single = entry
        # _force(): cells left lazy by an engine.bulk segment must resolve
        # to concrete buffers before they cross into the jitted call
        state_vals = [c._force() for c in state_cells]
        outs, new_state = jitted([a._force() for a in dyn], state_vals)
        ctx = next((a.context for a in args if isinstance(a, NDArray)), None)
        out_nds = [NDArray(o, ctx) for o in outs]
        if autograd.is_recording():
            # the whole traced program is ONE tape node, exactly like the
            # reference's CachedOp recording itself (cached_op.cc:1026);
            # recorded before state write-back so the node captures entry
            # values of params/stats.
            self._record_tape_node(pure, n_out, dyn, state_cells, out_nds)
        for c, v in zip(state_cells, new_state):
            c._data = v  # direct rebind: no re-notify, views not supported here
        return out_nds[0] if single else out_nds

    def _record_tape_node(self, pure, n_out, dyn, state_cells, out_nds):
        from . import autograd
        from .ops.registry import OpDef

        n_args = len(dyn)
        # freeze the train-mode flag at record time: the vjp replay re-runs
        # the user's Python later (possibly outside the record scope), and
        # Dropout/BatchNorm read autograd.is_training() live — without the
        # freeze the backward would differentiate the eval-mode graph
        train_flag = autograd.is_training()

        def tape_fn(*datas):
            with autograd._Scope(recording=False, training=train_flag):
                outs, _ = pure(list(datas[:n_args]), list(datas[n_args:]))
            return tuple(outs)

        op = OpDef(f"_traced_{self.name}", tape_fn, num_outputs=n_out)
        autograd.record_op(op, {}, list(dyn) + list(state_cells), out_nds)

    def _build(self, args, key):
        import jax

        from .ndarray.ndarray import NDArray

        # ---- pass 1: eager discovery
        with TraceSession() as sess:
            for a in args:
                sess.note_created(a)
            try:
                result = self.fn(*args)
            finally:
                # Roll back discovery side-effects even when fn raises
                # mid-discovery; the jitted execution (below, in __call__)
                # applies each mutation exactly once.
                for m in sess.mutated:
                    m._data = sess.orig[id(m)]
        single = not isinstance(result, (list, tuple))
        res_list = [result] if single else list(result)
        n_out = len(res_list)
        state_cells = list(sess.captured)
        fn = self.fn
        statics = {i: a for i, a in enumerate(args) if i in self.static_argnums}

        # ---- pass 2: pure wrapper for jit
        def pure(arg_datas, state_datas):
            # rebind captured cells to tracers, run, collect, restore
            saved = [c._data for c in state_cells]
            call_args = []
            di = 0
            for i in range(len(args)):
                if i in statics:
                    call_args.append(statics[i])
                else:
                    call_args.append(NDArray(arg_datas[di]))
                    di += 1
            try:
                for c, d in zip(state_cells, state_datas):
                    c._data = d
                with TraceSession() as inner:
                    for a in call_args:
                        if isinstance(a, NDArray):
                            inner.note_created(a)
                    r = fn(*call_args)
                r_list = [r] if not isinstance(r, (list, tuple)) else list(r)
                out_data = [x._data for x in r_list]
                new_state = [c._data for c in state_cells]
            finally:
                for c, d in zip(state_cells, saved):
                    c._data = d
            return out_data, new_state

        from . import autograd

        # when recording, entry state buffers feed the tape's vjp replay —
        # they must not be donated to the forward executable
        donate = (1,) if self.donate_state and not autograd.is_recording() else ()
        jitted = jax.jit(pure, donate_argnums=donate)
        entry = (jitted, pure, state_cells, n_out, single)
        self._cache[key] = entry
        return entry


def trace(fn=None, *, static_argnums=(), donate_state=True):
    """Decorator: compile an imperative training/inference step to one XLA
    executable. The TPU-idiomatic stand-in for hybridize/CachedOp."""
    if fn is None:
        return lambda f: TracedFunction(f, static_argnums, donate_state)
    return TracedFunction(fn, static_argnums, donate_state)
