"""Executor — symbolic graph execution as jitted XLA executables.

Parity: include/mxnet/executor.h + src/executor/graph_executor.cc. The
reference binds once (nnvm passes, memory pool, pre-created engine ops) and
replays per batch; here bind builds a pure graph interpreter and jits it —
one executable for inference forward, one fused forward+backward for
training. Memory planning (plan_memory.cc), inplace detection and pointwise
fusion are XLA buffer assignment/fusion. The training hot path runs ONE
executable per batch: `forward(is_train=True)` is lazy and `backward()`
executes the fused fwd+bwd program (outputs + gradients + aux updates).
"""
from __future__ import annotations

import contextlib as _contextlib
import inspect as _inspect

import numpy as _np

from .base import MXNetError
from .context import current_context
from .ndarray.ndarray import NDArray, zeros as nd_zeros
from .ops import registry as _registry

__all__ = ["Executor"]


def _graph_program(symbol, placement=None, default_device=None):
    """Build (pure_fn, arg_names, aux_names, out_count). pure_fn maps
    (list arg_vals, list aux_vals, bool is_train) -> (outs, new_aux_vals).

    placement: optional {node_name: jax.Device} from bind(group2ctx=...) —
    the reference's manual model parallelism (symbol.py:1551,
    graph_executor.cc:1961 cross_device_copy insertion). Each node's
    inputs are device_put to its device — unplaced nodes count as placed
    on `default_device` (the bind ctx), like the reference's default
    group — and placed programs run eagerly, like the reference's per-op
    engine dispatch."""
    import jax

    nodes = symbol._topo_nodes()
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    arg_pos = {n: i for i, n in enumerate(arg_names)}
    aux_pos = {n: i for i, n in enumerate(aux_names)}
    ops_meta = []
    for n in nodes:
        if n.is_var:
            continue
        op = _registry.get_op(n.op)
        params = op.normalize(n.params)
        has_train = "_train" in _inspect.signature(op.fn).parameters
        ops_meta.append((n, op, params, has_train))

    def pure_fn(arg_vals, aux_vals, is_train, tap=None):
        # tap: optional callback(node_name, out_index, raw_array) — the
        # monitor hook (reference GraphExecutor::SetMonitorCallback,
        # graph_executor.cc:187); only used on eager (non-jitted) passes
        env = {}
        aux_out = list(aux_vals)
        for n in nodes:
            if n.is_var:
                if n.aux_mark:
                    env[(id(n), 0)] = aux_out[aux_pos[n.name]]
                else:
                    env[(id(n), 0)] = arg_vals[arg_pos[n.name]]
        for (n, op, params, has_train) in ops_meta:
            ins = [env[(id(i), s)] for i, s in n.inputs]
            if placement:
                dev = placement.get(n.name, default_device)
                if dev is not None:
                    ins = [jax.device_put(x, dev) for x in ins]
            p = dict(params)
            if has_train:
                p["_train"] = is_train
            raw = op.closed(p)(*ins)
            raw = raw if isinstance(raw, tuple) else (raw,)
            n_primary = op.n_out(params)
            for i in range(n_primary):
                env[(id(n), i)] = raw[i]
                if tap is not None:
                    tap(n.name, i, raw[i])
            for slot, val in zip(op.mutate_slots(params), raw[n_primary:]):
                tgt_node, tgt_slot = n.inputs[slot]
                env[(id(tgt_node), tgt_slot)] = val
                if tgt_node.is_var and tgt_node.aux_mark:
                    aux_out[aux_pos[tgt_node.name]] = val
        outs = [env[(id(n), i)] for n, i in symbol._outputs]
        return outs, aux_out

    return pure_fn, arg_names, aux_names, len(symbol._outputs)


def _alloc_for_name(name, shape, ctx, dtype=_np.float32):
    import jax

    if name.endswith("rng_key"):
        return NDArray(jax.random.PRNGKey(abs(hash(name)) % (2 ** 31)), ctx)
    if name.endswith("moving_var") or name.endswith("running_var"):
        from .ndarray.ndarray import ones

        return ones(shape, ctx, dtype)
    return nd_zeros(shape, ctx, dtype)


class Executor:
    # When set (serving Predictor), a live-rollout param swap flips every
    # shared arg/aux cell under this lock; forward_batch gathers under it
    # too, so one forward sees all-old or all-new params, never a torn mix.
    _param_read_lock = None

    def __init__(self, symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                 group2ctx=None):
        import jax

        self._symbol = symbol
        self._ctx = ctx
        self.arg_dict = arg_dict
        self.grad_dict = grad_dict
        self.aux_dict = aux_dict
        # group2ctx (reference symbol.py:1551-1654 + graph_executor.cc
        # cross_device_copy): resolve each node's __ctx_group__ attr to a
        # jax device; placed graphs run eagerly with per-node transfers —
        # the same per-op dispatch model the reference's engine used
        placement = None
        if group2ctx:
            placement = {}
            for n in symbol._topo_nodes():
                g = (n.attrs or {}).get("__ctx_group__")
                if not n.is_var and g in group2ctx:
                    placement[n.name] = group2ctx[g].jax_device()
            placement = placement or None
        self._placement = placement
        self._group2ctx = dict(group2ctx) if group2ctx else None
        pure_fn, self._arg_names, self._aux_names, self._n_out = \
            _graph_program(symbol, placement,
                           ctx.jax_device() if placement else None)
        self._pure = pure_fn
        if isinstance(grad_req, str):
            grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            grad_req = dict(zip(self._arg_names, grad_req))
        self.grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}
        self._diff_names = [n for n in self._arg_names
                            if self.grad_req[n] != "null" and n in grad_dict]

        def fwd(arg_vals, aux_vals, is_train):
            return pure_fn(arg_vals, aux_vals, is_train)

        # placed graphs cannot be one single-device XLA program
        self._jit_fwd = (fwd if placement
                         else jax.jit(fwd, static_argnums=(2,)))

        diff_idx = [self._arg_names.index(n) for n in self._diff_names]

        def fwd_bwd(arg_vals, aux_vals, head_grads):
            def of_diff(*diff_vals):
                full = list(arg_vals)
                for i, v in zip(diff_idx, diff_vals):
                    full[i] = v
                outs, new_aux = pure_fn(full, aux_vals, True)
                return tuple(outs), new_aux

            # MXNET_BACKWARD_DO_MIRROR: recompute activations in backward
            # instead of keeping them (reference graph_executor.cc:357)
            from .remat import mirror_enabled, resolve_policy

            if mirror_enabled():
                of_diff = jax.checkpoint(of_diff,
                                         policy=resolve_policy(True))
            diff_vals = tuple(arg_vals[i] for i in diff_idx)
            outs, vjp_fn, new_aux = jax.vjp(of_diff, *diff_vals, has_aux=True)
            grads = vjp_fn(tuple(head_grads))
            return outs, list(grads), new_aux

        self._jit_fwd_bwd = fwd_bwd if placement else jax.jit(fwd_bwd)
        self._infer_capture = None
        self._outputs = None
        self._pending_train = False
        self.monitor_callback = None

    def enable_capture(self, label, fingerprint):
        """Route the stateless inference fast path (``forward_batch``)
        through the capture/AOT compile path (mxnet_tpu.capture): the
        executable compiles via the sanctioned capture site, gets
        capture/AOT counters and retrace forensics, and — with
        ``MXNET_TPU_COMPILE_CACHE`` set — persists to/loads from the
        on-disk artifact keyed by ``fingerprint``, so a serving
        cold-start skips tracing and XLA compilation. Placed
        (``group2ctx``) graphs run eagerly per node and are left alone.
        Returns self for chaining."""
        if self._placement is not None:
            return self
        from . import capture as _capture

        if not _capture.enabled():
            return self
        pure = self._pure

        def infer(arg_vals, aux_vals):
            outs, _new_aux = pure(arg_vals, aux_vals, False)
            return outs

        self._infer_capture = _capture.CapturedExec(
            infer, label=label, fingerprint=fingerprint)
        return self

    # ------------------------------------------------------------------ api
    @property
    def outputs(self):
        if self._outputs is None and self._pending_train:
            self._run_forward(True)
        return self._outputs or []

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-op output tap (reference
        GraphExecutor::SetMonitorCallback, graph_executor.cc:187). While a
        callback is installed, forward runs the graph eagerly op-by-op so
        every intermediate can be observed — the NaiveEngine-style debug
        mode; clear the callback to return to the fused executable."""
        self.monitor_callback = callback
        self._monitor_all = monitor_all

    def forward(self, is_train=False, **kwargs):
        for k, v in kwargs.items():
            if k in self.arg_dict:
                tgt = self.arg_dict[k]
                tgt._set_data(v._data if isinstance(v, NDArray) else v)
        if is_train and self._diff_names:
            # lazy: the fused fwd+bwd in backward() will produce outputs;
            # materialize on .outputs access if backward never comes.
            self._pending_train = True
            self._outputs = None
            return _LazyOutputs(self)
        self._run_forward(is_train)
        return self._outputs

    def _run_forward(self, is_train):
        arg_vals = [self.arg_dict[n]._data for n in self._arg_names]
        aux_vals = [self.aux_dict[n]._data for n in self._aux_names]
        if self.monitor_callback is not None:
            cb = self.monitor_callback

            def tap(name, i, arr):
                out_name = f"{name}_output" if i == 0 else f"{name}_output{i}"
                cb(out_name, NDArray(arr, self._ctx))

            outs, new_aux = self._pure(arg_vals, aux_vals, bool(is_train),
                                       tap=tap)
        else:
            outs, new_aux = self._jit_fwd(arg_vals, aux_vals, bool(is_train))
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._data = v
        self._pending_train = False
        return self._outputs

    def forward_batch(self, feeds, raw=False):
        """Inference fast path (mxnet_tpu.serving): run the jitted forward
        with ``feeds`` (name -> NDArray or raw/numpy array) overriding the
        bound arguments, WITHOUT writing into this executor's arg/aux
        cells. Stateless per call, so concurrent callers never race —
        the property the serving BatchServer relies on. Aux states are
        read, not written (is_train=False inference: moving stats are
        consumed, never updated). Returns raw jax arrays when ``raw``,
        else NDArrays."""
        lock = self._param_read_lock
        if lock is None:
            lock = _contextlib.nullcontext()
        with lock:
            arg_vals = []
            for n in self._arg_names:
                v = feeds.get(n)
                if v is None:
                    v = self.arg_dict[n]._data
                elif isinstance(v, NDArray):
                    v = v._data
                arg_vals.append(v)
            aux_vals = [self.aux_dict[n]._data for n in self._aux_names]
        cap = self._infer_capture
        if cap is not None:
            outs = cap(arg_vals, aux_vals)
        else:
            outs, _ = self._jit_fwd(arg_vals, aux_vals, False)
        if raw:
            return outs
        return [NDArray(o, self._ctx) for o in outs]

    def backward(self, out_grads=None, is_train=True):
        import jax.numpy as jnp

        if not self._diff_names:
            self._pending_train = False
            return
        arg_vals = [self.arg_dict[n]._data for n in self._arg_names]
        aux_vals = [self.aux_dict[n]._data for n in self._aux_names]
        if out_grads is None:
            import jax

            out_shapes = jax.eval_shape(
                lambda a, x: self._pure(a, x, True)[0], arg_vals, aux_vals)
            heads = [jnp.ones(o.shape, o.dtype) for o in out_shapes]
        else:
            out_grads = [out_grads] if isinstance(out_grads, NDArray) else list(out_grads)
            heads = [g._data for g in out_grads]
        if self._placement:
            # head gradients must start on their output's placed device —
            # jax transpose rules don't insert cross-device transfers
            import jax

            heads = [jax.device_put(g, self._placement[n.name])
                     if n.name in self._placement else g
                     for g, (n, _) in zip(heads, self._symbol._outputs)]
        outs, grads, new_aux = self._jit_fwd_bwd(arg_vals, aux_vals, heads)
        self._outputs = [NDArray(o, self._ctx) for o in outs]
        for n, v in zip(self._aux_names, new_aux):
            self.aux_dict[n]._data = v
        for n, g in zip(self._diff_names, grads):
            tgt = self.grad_dict[n]
            if self.grad_req[n] == "add":
                tgt._data = tgt._data + g
            else:
                tgt._data = g
        self._pending_train = False

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in arg_params.items():
            if k in self.arg_dict:
                self.arg_dict[k]._set_data(v._data)
            elif not allow_extra_params:
                raise MXNetError(f"unknown argument {k}")
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._set_data(v._data)

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind for new input shapes (shape-keyed recompile under jit)."""
        arg_shapes, _, aux_shapes = self._symbol._infer_shape_impl(
            partial=True, **{**{k: tuple(v.shape) for k, v in self.arg_dict.items()},
                             **kwargs})
        new_args = {}
        for name, shape in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[name]
            if shape is not None and tuple(cur.shape) != tuple(shape):
                new_args[name] = nd_zeros(shape, self._ctx)
            else:
                new_args[name] = cur
        grad_dict = {n: nd_zeros(new_args[n].shape, self._ctx)
                     for n in self._diff_names}
        return Executor(self._symbol, self._ctx, new_args, grad_dict,
                        self.grad_req, self.aux_dict,
                        group2ctx=self._group2ctx)

    # ------------------------------------------------------------- builders
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req="write", group2ctx=None,
                     **shape_kwargs):
        ctx = ctx or current_context()
        arg_shapes, _, aux_shapes = symbol._infer_shape_impl(partial=False,
                                                             **shape_kwargs)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        # group2ctx: allocate each variable on its consumer's group device
        # (reference simple_bind ctx resolution) so placed stages don't
        # re-transfer weights every iteration
        var_ctx = {}
        if group2ctx:
            for node in symbol._topo_nodes():
                g = (node.attrs or {}).get("__ctx_group__")
                if node.is_var or g not in group2ctx:
                    continue
                for (inp, _) in node.inputs:
                    if inp.is_var and inp.name not in var_ctx:
                        var_ctx[inp.name] = group2ctx[g]
        arg_dict = {n: _alloc_for_name(n, s, var_ctx.get(n, ctx))
                    for n, s in zip(arg_names, arg_shapes)}
        if isinstance(grad_req, str):
            req = {n: grad_req for n in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            req = dict(zip(arg_names, grad_req))
        else:
            req = dict(grad_req)
        grad_dict = {n: nd_zeros(s, var_ctx.get(n, ctx))
                     for n, s in zip(arg_names, arg_shapes)
                     if req.get(n, "write") != "null"}
        # aux shapes may be underdetermined (rng keys): infer or allocate
        aux_dict = {}
        for n, s in zip(aux_names, aux_shapes):
            aux_dict[n] = _alloc_for_name(n, s or (2,), ctx)
        return Executor(symbol, ctx, arg_dict, grad_dict, req, aux_dict,
                        group2ctx=group2ctx)

    @staticmethod
    def _bind(symbol, ctx, args, args_grad=None, grad_req="write",
              aux_states=None, group2ctx=None):
        ctx = ctx or current_context()
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        if isinstance(args, (list, tuple)):
            arg_dict = dict(zip(arg_names, args))
        else:
            arg_dict = dict(args)
        missing = [n for n in arg_names if n not in arg_dict]
        if missing:
            raise MXNetError(f"bind: missing arguments {missing}")
        if args_grad is None:
            grad_dict = {}
            if grad_req != "null":
                grad_dict = {n: nd_zeros(arg_dict[n].shape, ctx) for n in arg_names}
        elif isinstance(args_grad, (list, tuple)):
            grad_dict = dict(zip(arg_names, args_grad))
        else:
            grad_dict = dict(args_grad)
        if aux_states is None:
            aux_dict = {}
        elif isinstance(aux_states, (list, tuple)):
            aux_dict = dict(zip(aux_names, aux_states))
        else:
            aux_dict = dict(aux_states)
        missing_aux = [n for n in aux_names if n not in aux_dict]
        if missing_aux:
            # partial aux dicts are common (e.g. ONNX-imported graphs have
            # BN stats but not auto-created Dropout rng keys): allocate the
            # rest like the aux_states=None path does
            shapes = {k: tuple(v.shape) for k, v in arg_dict.items()}
            _, _, aux_shapes = symbol._infer_shape_impl(partial=True,
                                                        **shapes)
            for an, s in zip(aux_names, aux_shapes):
                if an in missing_aux:
                    aux_dict[an] = _alloc_for_name(an, s or (2,), ctx)
        return Executor(symbol, ctx, arg_dict, grad_dict, grad_req, aux_dict,
                        group2ctx=group2ctx)


class _LazyOutputs(list):
    """Sequence proxy so `exec.forward(is_train=True)` callers can still index
    outputs — materializes the forward program on first access."""

    def __init__(self, executor):
        super().__init__()
        self._ex = executor

    def _mat(self):
        return self._ex.outputs

    def __getitem__(self, i):
        return self._mat()[i]

    def __iter__(self):
        return iter(self._mat())

    def __len__(self):
        return len(self._mat())
