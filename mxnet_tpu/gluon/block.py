"""Gluon Block / HybridBlock / SymbolBlock.

Parity: python/mxnet/gluon/block.py:229,839,1194 in the reference. TPU
redesign of hybridization: the reference's ``_build_cache`` traces
``hybrid_forward`` into a Symbol graph and wraps it in a C++ ``CachedOp``
(block.py:933,970); here ``hybridize()`` routes ``__call__`` through
``mxnet_tpu.jit.trace``, which re-runs the imperative code under ``jax.jit``
so the whole forward (and, when recording, the backward tape) compiles into
one XLA executable per input-shape signature — the same "compile once,
replay" contract with XLA doing memory planning and fusion.
"""
from __future__ import annotations

import copy
import re
import warnings
from collections import OrderedDict

from ..base import MXNetError
from ..context import Context, cpu, current_context
from .. import ndarray as nd
from .parameter import (Parameter, ParameterDict, DeferredInitializationError,
                        tensor_types)
from .. import initializer
from .. import jit as _jit
from ..observability import trace as _obs_trace

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope:
    """Scope for collecting child Blocks (gluon/block.py:34)."""

    _current = None
    _global_counter = {}  # top-level naming (reference: NameManager current)

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None
        self._name_scope = None

    @staticmethod
    def create(prefix, params, hint):
        current = _BlockScope._current
        if current is None:
            if prefix is None:
                prefix = _name_with_count(_BlockScope._global_counter,
                                          hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            prefix = _name_with_count(current._counter, hint) + "_"
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old_scope = _BlockScope._current
        _BlockScope._current = self
        return self

    def __exit__(self, ptype, value, trace):
        if self._block._empty_prefix:
            return
        _BlockScope._current = self._old_scope


def _name_with_count(counter, hint):
    count = counter.get(hint, 0)
    counter[hint] = count + 1
    return f"{hint}{count}"


def _flatten(args, fmt_name):
    flat, fmts = [], []
    for a in args:
        if isinstance(a, tensor_types):
            flat.append(a)
            fmts.append(0)
        elif isinstance(a, (list, tuple)):
            f, fmt = _flatten(a, fmt_name)
            flat.extend(f)
            fmts.append(fmt)
        else:
            flat.append(a)
            fmts.append(-1)
    return flat, fmts


def _regroup(flat, fmt):
    if isinstance(fmt, int):
        if fmt == 0 or fmt == -1:
            return flat[0], flat[1:]
        return flat[:fmt], flat[fmt:]
    out = []
    for f in fmt:
        res, flat = _regroup(flat, f)
        out.append(res)
    return out, flat


class Block:
    """Base class for all neural network layers and models
    (gluon/block.py:229)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = OrderedDict()
        self._forward_pre_hooks = OrderedDict()

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self.__dict__.items()
            if isinstance(block, Block))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        """Registers parameters and child blocks on assignment."""
        if hasattr(self, name):
            existing = getattr(self, name)
            if isinstance(existing, (Parameter, Block)) and \
                    not isinstance(value, type(existing)):
                raise TypeError(
                    f"Changing attribute type for {self.name} from "
                    f"{type(existing)} to {type(value)} is not allowed.")
        if isinstance(value, Block):
            self.register_child(value, name)
        elif isinstance(value, Parameter):
            assert name not in self._reg_params, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value
        super().__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        """Returns a name-space scope object managing child block and
        parameter names."""
        return self._scope

    @property
    def params(self):
        """Returns this Block's parameter dictionary (not including children)."""
        return self._params

    def collect_params(self, select=None):
        """Returns a ParameterDict of this Block and all children
        (gluon/block.py:504)."""
        ret = ParameterDict(self._params.prefix)
        if not select:
            ret.update(self.params)
        else:
            pattern = re.compile(select)
            ret.update({name: value for name, value in self.params.items()
                        if pattern.match(name)})
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_pre_hook(self, hook):
        handle = _HookHandle(self._forward_pre_hooks)
        self._forward_pre_hooks[handle.id] = hook
        return handle

    def register_forward_hook(self, hook):
        handle = _HookHandle(self._forward_hooks)
        self._forward_hooks[handle.id] = hook
        return handle

    def apply(self, fn):
        """Applies fn recursively to every child block and self."""
        for cld in self._children.values():
            cld.apply(fn)
        fn(self)
        return self

    def initialize(self, init=initializer.Uniform(), ctx=None, verbose=False,
                   force_reinit=False):
        with _obs_trace.span("setup.initialize", block=self.name):
            self.collect_params().initialize(init, ctx, verbose,
                                             force_reinit)

    def hybridize(self, active=True, **kwargs):
        for cld in self._children.values():
            cld.hybridize(active, **kwargs)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def save_parameters(self, filename, deduplicate=False):
        """Save parameters to file (gluon/block.py:417). Format: the repo's
        NDArray dict container (see mxnet_tpu.ndarray.save)."""
        params = self._collect_params_with_prefix()
        nd.save(filename, {key: val._data if isinstance(val, Parameter) else val
                           for key, val in params.items()})

    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val.data() for key, val in self._reg_params.items()
               if val._data is not None or val._deferred_init}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def _params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._params_with_prefix(prefix + name))
        return ret

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False, dtype_source="current"):
        """Load parameters from file (gluon/block.py:473)."""
        loaded = nd.load(filename)
        params = self._params_with_prefix()
        if not loaded and not params:
            return
        if not any("." in k for k in loaded.keys()):
            # legacy full-name format, fall back to ParameterDict.load
            del loaded
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra, self.prefix)
            return
        if not allow_missing:
            for name in params.keys():
                assert name in loaded, \
                    f"Parameter '{name}' is missing in file '{filename}'"
        for name in loaded:
            if not ignore_extra and name not in params:
                raise ValueError(
                    f"Parameter '{name}' loaded from file '{filename}' is not "
                    "present in this block")
            if name in params:
                params[name].set_data(loaded[name])

    def __call__(self, *args):
        for hook in self._forward_pre_hooks.values():
            hook(self, args)
        # inside a functional_call trace the ops carry this block's name
        # (jit.scope); an eager call gets the shared no-op
        with _jit.scope(self._name):
            out = self.forward(*args)
        for hook in self._forward_hooks.values():
            hook(self, args, out)
        return out

    def forward(self, *args):
        """Override to implement forward computation using NDArray."""
        raise NotImplementedError

    def summary(self, *inputs):
        """Print a summary of the network (gluon/block.py:601)."""
        summary = OrderedDict()
        hooks = []

        def _make_hook(name, blk):
            def hook(block, inputs, outputs):
                cname = name or block.__class__.__name__
                entry = summary.setdefault(cname, {"params": 0})
                entry["params"] = sum(
                    p.data().size for p in block.params.values()
                    if p._data is not None)
            return hook

        def _register(blk, name=""):
            hooks.append(blk.register_forward_hook(_make_hook(name, blk)))
            for cname, child in blk._children.items():
                _register(child, name + "." + cname if name else cname)

        _register(self)
        try:
            self(*inputs)
            print(f"{'Layer':<40}{'Params':<15}")
            print("=" * 55)
            total = 0
            for name, entry in summary.items():
                print(f"{name:<40}{entry['params']:<15}")
                total += entry["params"]
            print("=" * 55)
            print(f"Total params: {total}")
        finally:
            for h in hooks:
                h.detach()


class _HookHandle:
    _next_id = 0

    def __init__(self, hooks_dict):
        self.id = _HookHandle._next_id
        _HookHandle._next_id += 1
        self._hooks = hooks_dict

    def detach(self):
        self._hooks.pop(self.id, None)


def _indent(s, num_spaces):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line for line in lines)


class HybridBlock(Block):
    """A Block that can be compiled into one XLA executable
    (gluon/block.py:839).

    Subclasses implement ``hybrid_forward(F, x, *args, **params)`` where
    ``F`` is ``mxnet_tpu.nd`` (imperative) or ``mxnet_tpu.sym`` (symbolic
    export path) and registered parameters arrive as keyword arguments.
    """

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._traced = {}       # shape/dtype signature -> TracedFunction
        self._flags = {}
        self._v2 = type(self).hybrid_forward is HybridBlock.hybrid_forward

    def hybridize(self, active=True, **kwargs):
        """Activates XLA whole-graph compilation for this block and all
        children. The flags of the reference CachedOp (static_alloc,
        static_shape — cached_op.h:32) are accepted and ignored: XLA's
        buffer assignment is always static."""
        self._active = active
        self._flags.update(kwargs)
        self._traced = {}
        super().hybridize(active, **kwargs)

    def cast(self, dtype):
        self._traced = {}
        super().cast(dtype)

    def _all_params(self):
        ret = dict(self._reg_params)
        for child in self._children.values():
            ret.update(child._all_params() if isinstance(child, HybridBlock)
                       else child._reg_params)
        return ret

    def _deferred_infer_shape(self, *args):
        """Finish deferred parameter initialization by tracing the whole
        block symbolically and running shape inference — the analogue of
        _deferred_infer_shape (reference gluon/block.py:791)."""
        from .. import symbol as sym
        try:
            with _obs_trace.span("setup.infer_shape", block=self.name):
                inputs = [sym.var(f"data{i}") for i in range(len(args))]
                out = self(*inputs)
                if isinstance(out, (list, tuple)):
                    out = sym.Group(list(out))
                shapes = {f"data{i}": a.shape for i, a in enumerate(args)
                          if isinstance(a, tensor_types)}
                arg_shapes, _, aux_shapes = out.infer_shape_partial(**shapes)
                sdict = dict(zip(out.list_arguments(), arg_shapes))
                sdict.update(zip(out.list_auxiliary_states(), aux_shapes))
                for p in self._all_params().values():
                    if p.name in sdict and sdict[p.name] is not None and \
                            p._deferred_init:
                        p.shape = sdict[p.name]
                        p._finish_deferred_init()
        except Exception as e:
            raise ValueError(
                "Deferred initialization failed because shape cannot be "
                "inferred: " + str(e)) from e

    def infer_shape(self, *args):
        self._deferred_infer_shape(*args)

    def _call_with_params(self, *args):
        params = {name: p.data() for name, p in self._reg_params.items()}
        return self.hybrid_forward(nd, *args, **params)

    def forward(self, x, *args):
        """Defines the forward computation; wires params and jit. Symbol
        inputs route through hybrid_forward(sym, ...) — the export /
        shape-inference path."""
        from ..symbol import Symbol
        if isinstance(x, Symbol):
            params = {name: p.var() for name, p in self._reg_params.items()}
            return self.hybrid_forward(_sym_ns(), x, *args, **params)
        try:
            if self._active:
                return self._traced_call(x, *args)
            return self._call_with_params(x, *args)
        except DeferredInitializationError:
            self._deferred_infer_shape(x, *args)
        if self._active:
            return self._traced_call(x, *args)
        return self._call_with_params(x, *args)

    def _traced_call(self, *args):
        from .. import jit as _jit

        # inside an enclosing trace (a hybridized parent, or a user-level
        # mxnet_tpu.jit.trace step) run eagerly so everything fuses into the
        # one outer executable instead of nesting jits
        import jax.core as _jcore
        if _jit._sessions() or any(
                isinstance(a.data_, _jcore.Tracer)
                for a in args if isinstance(a, tensor_types)):
            return self._call_with_params(*args)
        key = tuple((a.shape, str(a.dtype)) if isinstance(a, tensor_types)
                    else a for a in args)
        fn = self._traced.get(key)
        if fn is None:
            # non-tensor extras (scalars, None, flags) become static args so
            # TracedFunction never asks them for .shape
            statics = tuple(i for i, a in enumerate(args)
                            if not isinstance(a, tensor_types))
            fn = _jit.trace(lambda *xs: self._call_with_params(*xs),
                            static_argnums=statics)
            self._traced[key] = fn
        return fn(*args)

    def hybrid_forward(self, F, x, *args, **kwargs):
        """Override to implement forward computation over namespace F."""
        raise NotImplementedError

    def export(self, path, epoch=0):
        """Export symbol graph + params for deployment
        (gluon/block.py:1081): ``path-symbol.json`` + ``path-%04d.params``."""
        from .. import symbol as sym
        out = self(sym.var("data"))
        if isinstance(out, (list, tuple)):
            out = sym.Group(list(out))
        out.save(f"{path}-symbol.json")
        arg_dict = {}
        for name, param in self.collect_params().items():
            if param._data is not None:
                arg_dict[name] = param.data()
        nd.save(f"{path}-{epoch:04d}.params", arg_dict)
        return f"{path}-symbol.json", f"{path}-{epoch:04d}.params"



def _sym_ns():
    from .. import symbol as sym
    return sym


class SymbolBlock(HybridBlock):
    """Construct a block from a Symbol (gluon/block.py:1194) — the import
    path for models exported with HybridBlock.export."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix=None, params=None)
        # graph arguments keep their exported names — unprefixed dict
        # (reference block.py:1250 uses ParameterDict with empty prefix)
        self._params = ParameterDict("", None)
        from .. import symbol as sym
        if isinstance(inputs, sym.Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)):
            outputs = sym.Group(list(outputs))
        self._cached_graph = (inputs, outputs)
        input_names = {i.name for i in inputs}
        # every non-input argument becomes a Parameter
        for name in outputs.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in outputs.list_auxiliary_states():
            if name not in input_names:
                self.params.get(name, grad_req="null", allow_deferred_init=True)
        if params is not None:
            for name, value in params.items():
                if name in self.params:
                    self.params[name].shape = value.shape
                    self.params[name].set_data(value)

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        """Load a model exported by HybridBlock.export."""
        from .. import symbol as sym
        if isinstance(input_names, str):
            input_names = [input_names]
        outputs = sym.load(symbol_file)
        inputs = [sym.var(n) for n in input_names]
        ret = SymbolBlock(outputs, inputs)
        if param_file is not None:
            arrays = nd.load(param_file)
            for name, value in arrays.items():
                if name in ret.params:
                    ret.params[name].shape = value.shape
                    ret.params[name].set_data(value)
        if ctx is not None:
            ret.collect_params().reset_ctx(ctx)
        return ret

    def forward(self, x, *args):
        inputs, outputs = self._cached_graph
        feed = {}
        for i, a in zip(inputs, (x,) + args):
            feed[i.name] = a
        for name, p in self.params.items():
            feed[name] = p.data()
        res = outputs.eval(ctx=x.ctx, **feed)
        return res[0] if len(res) == 1 else res

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
