"""Gluon Parameter / ParameterDict / Constant.

Parity: python/mxnet/gluon/parameter.py:47,706 in the reference — deferred
initialization, grad_req handling, per-context data, save/load. TPU redesign:
a Parameter owns ONE NDArray (a jax.Array committed to a Context); replication
across devices is not done by keeping N copies (the reference's per-GPU
`_data` list) but by sharding annotations applied when the training step is
pjit-ed over a Mesh (see mxnet_tpu/parallel). `list_data()` therefore returns
a single-element list in the single-logical-device model.
"""
from __future__ import annotations

import re
import warnings
from collections import OrderedDict

import numpy as _np

from ..base import MXNetError
from ..context import Context, current_context
from .. import ndarray as nd
from .. import initializer

__all__ = ["DeferredInitializationError", "Parameter", "Constant",
           "ParameterDict", "tensor_types"]

tensor_types = (nd.NDArray,)


class DeferredInitializationError(MXNetError):
    """Error for unfinished deferred initialization
    (gluon/parameter.py:36)."""


class Parameter:
    """A Container holding parameters (weights) of Blocks.

    Parity: python/mxnet/gluon/parameter.py:47. ``shape`` entries of 0 are
    unknown and resolved at first forward (deferred init).
    """

    def __init__(self, name, grad_req="write", shape=None, dtype=_np.float32,
                 lr_mult=1.0, wd_mult=1.0, init=None, allow_deferred_init=False,
                 differentiable=True, stype="default", grad_stype="default"):
        self._var = None
        self._data = None
        self._grad = None
        self._ctx = None
        self._deferred_init = ()
        self._differentiable = differentiable
        self._allow_deferred_init = allow_deferred_init
        self.name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        if stype not in ("default",) or grad_stype not in ("default",):
            # sparse storage is out of scope on TPU (SURVEY.md §7 hard part 4)
            warnings.warn("sparse parameter storage is not supported on TPU; "
                          "using dense", stacklevel=2)
        self.grad_req = grad_req

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"

    # ------------------------------------------------------------------ props
    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        assert req in ("write", "add", "null"), \
            f"grad_req must be one of write, add, null, but got {req}"
        if not self._differentiable:
            req = "null"
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._grad = None
                self._data.grad_req = "null"
            elif self._grad is None:
                self._init_grad()

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        assert len(self._shape) == len(new_shape) and \
            all(j in (0, i) for i, j in zip(new_shape, self._shape)), \
            f"Expected shape {new_shape} is incompatible with given shape {self._shape}."
        self._shape = tuple(new_shape)

    # ----------------------------------------------------------------- init
    def _check_initialized(self):
        if self._data is not None:
            return
        if self._deferred_init:
            raise DeferredInitializationError(
                f"Parameter '{self.name}' has not been initialized yet because "
                "initialization was deferred. Actual initialization happens "
                "during the first forward pass. Please pass one batch of data "
                "through the network before accessing Parameters.")
        raise RuntimeError(
            f"Parameter '{self.name}' has not been initialized. Note that you "
            "should initialize parameters and create Trainer with "
            "Block.collect_params() instead of Block.params")

    def initialize(self, init=None, ctx=None, default_init=initializer.Uniform(),
                   force_reinit=False):
        """Initialize parameter and gradient arrays
        (gluon/parameter.py:361)."""
        if self._data is not None and not force_reinit:
            warnings.warn(f"Parameter '{self.name}' is already initialized, "
                          "ignoring. Set force_reinit=True to re-initialize.",
                          stacklevel=2)
            return
        if ctx is None:
            ctx = current_context()
        if isinstance(ctx, Context):
            ctx = [ctx]
        if init is None:
            init = default_init if self.init is None else self.init
        if self._shape is None or any(s == 0 for s in self._shape):
            if self._allow_deferred_init:
                self._deferred_init = (init, ctx, default_init, None)
                return
            raise ValueError(f"Cannot initialize Parameter '{self.name}' "
                             "because it has invalid shape: "
                             f"{self._shape}.")
        self._deferred_init = (init, ctx, default_init, None)
        self._finish_deferred_init()

    def _finish_deferred_init(self):
        if not self._deferred_init:
            return
        init, ctx, default_init, data = self._deferred_init
        self._deferred_init = ()
        assert self._shape is not None and all(s > 0 for s in self._shape), \
            f"Cannot initialize Parameter '{self.name}' because it has " \
            f"invalid shape: {self._shape}."
        from .. import autograd
        from ..jit import no_trace
        with autograd.pause(), no_trace():
            if data is None:
                # initialized where it will live: staging on the host
                # would need a cpu backend the process may not have
                # (JAX_PLATFORMS=tpu) and a transfer per parameter
                data = nd.zeros(self._shape, dtype=self.dtype, ctx=ctx[0])
                if isinstance(init, str):
                    init = initializer.create(init)
                init(initializer.InitDesc(self.name), data)
            self._init_impl(data, ctx)

    def _init_impl(self, data, ctx_list):
        self._ctx = list(ctx_list)
        self._data = data.copyto(self._ctx[0]) if data.ctx != self._ctx[0] else data
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self):
        self._data.attach_grad(grad_req=self._grad_req)
        self._grad = self._data.grad

    # ----------------------------------------------------------------- data
    def data(self, ctx=None):
        """Returns the parameter on one context (gluon/parameter.py:549)."""
        self._check_initialized()
        return self._data

    def list_data(self):
        self._check_initialized()
        return [self._data]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init:
                return self._deferred_init[1]
            raise RuntimeError(f"Parameter '{self.name}' has not been initialized")
        return list(self._ctx)

    def grad(self, ctx=None):
        if self._data is not None and self._grad is None:
            raise RuntimeError(
                f"Cannot get gradient array for Parameter '{self.name}' "
                "because grad_req='null'")
        self._check_initialized()
        return self._data.grad

    def list_grad(self):
        return [self.grad()]

    def zero_grad(self):
        if self._grad is None:
            return
        g = self._data.grad
        g._set_data(nd.zeros(g.shape, dtype=g.dtype, ctx=g.ctx).data_)

    def set_data(self, data):
        """Sets this parameter's value on all contexts
        (gluon/parameter.py:589)."""
        self.shape = data.shape
        if not isinstance(data, nd.NDArray):
            data = nd.array(data, dtype=self.dtype)
        if self._data is None:
            # loading weights IS initialization (reference _load_init,
            # gluon/parameter.py:274) — works on never-initialized params too
            if self._deferred_init:
                init, ctx, default_init, _ = self._deferred_init
            else:
                init, ctx, default_init = self.init, [current_context()], \
                    initializer.Uniform()
            self._deferred_init = (init, ctx, default_init, data)
            self._finish_deferred_init()
            return
        if not isinstance(data, nd.NDArray):
            data = nd.array(data, dtype=self.dtype)
        self._data._set_data(data.copyto(self._ctx[0]).astype(self.dtype).data_)

    def reset_ctx(self, ctx):
        if isinstance(ctx, Context):
            ctx = [ctx]
        if self._data is not None:
            data = self._data.copyto(ctx[0])
            grad_req = self._grad_req
            self._grad = None
            self._init_impl(data, ctx)
            self.grad_req = grad_req
        elif self._deferred_init:
            init, _, default_init, data = self._deferred_init
            self._deferred_init = (init, ctx, default_init, data)
        else:
            raise ValueError(f"Cannot reset context for Parameter '{self.name}' "
                             "because it has not been initialized.")

    def cast(self, dtype):
        """Cast data and gradient of this Parameter to a new data type."""
        self.dtype = dtype
        if self._data is None:
            return
        from .. import autograd
        with autograd.pause():
            data = self._data.astype(dtype)
            grad_req = self._grad_req
            self._grad = None
            self._init_impl(data, self._ctx)
            self.grad_req = grad_req

    # --------------------------------------------------------------- symbol
    def var(self):
        """Returns a symbol representing this parameter."""
        if self._var is None:
            from .. import symbol
            self._var = symbol.var(self.name, shape=self.shape, dtype=self.dtype,
                                   lr_mult=self.lr_mult, wd_mult=self.wd_mult,
                                   init=self.init)
        return self._var


class Constant(Parameter):
    """A constant parameter for holding non-differentiable values
    (gluon/parameter.py:652)."""

    def __init__(self, name, value):
        if not isinstance(value, nd.NDArray):
            value = nd.array(value)
        self.value = value

        class Init(initializer.Initializer):
            def _init_weight(self, _, arr):
                value.copyto(arr)

        init_name = f"Constant_{name}"
        initializer.register(Init)
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=Init(), differentiable=False)


class ParameterDict:
    """A dictionary managing a set of Parameters (gluon/parameter.py:706)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    def __repr__(self):
        s = "\n".join(f"  {v}" for v in self.values())
        return f"{type(self).__name__}({self._prefix}\n{s}\n)"

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __contains__(self, key):
        return key in self._params

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    @property
    def prefix(self):
        return self._prefix

    def _get_impl(self, name):
        if name in self._params:
            return self._params[name]
        if self._shared is not None and name in self._shared._params:
            self._params[name] = self._shared._params[name]
            return self._params[name]
        return None

    def get(self, name, **kwargs):
        """Retrieves or creates a Parameter named prefix+name
        (gluon/parameter.py:817)."""
        name = self.prefix + name
        param = self._get_impl(name)
        if param is None:
            param = Parameter(name, **kwargs)
            self._params[name] = param
            return param
        for k, v in kwargs.items():
            if hasattr(param, k) and getattr(param, k) is not None:
                existing = getattr(param, k)
                if k == "shape" and v is not None and len(v) == len(existing):
                    inferred = tuple(
                        max(i, j) if 0 in (i, j) else i
                        for i, j in zip(v, existing))
                    if all(i in (0, j) or j in (0, i)
                           for i, j in zip(v, existing)):
                        param._shape = inferred
                        continue
                if k == "dtype" and _np.dtype(v) == _np.dtype(existing):
                    continue
                assert v is None or str(v) == str(existing), \
                    f"Cannot retrieve Parameter '{name}' because desired " \
                    f"attribute does not match with stored for attribute " \
                    f"'{k}': desired '{v}' vs stored '{getattr(param, k)}'."
            else:
                setattr(param, k, v)
        return param

    def get_constant(self, name, value=None):
        name = self.prefix + name
        param = self._get_impl(name)
        if param is None:
            if value is None:
                raise KeyError(f"No constant named '{name}'.")
            param = Constant(name, value)
            self._params[name] = param
        return param

    def update(self, other):
        for k, v in other.items():
            if k in self._params:
                assert self._params[k] is v, \
                    f"Cannot update self with other because they have " \
                    f"different Parameters with the same name '{k}'"
            else:
                self._params[k] = v

    def initialize(self, init=initializer.Uniform(), ctx=None, verbose=False,
                   force_reinit=False):
        for _, v in self.items():
            v.initialize(None, ctx, init, force_reinit=force_reinit)

    def zero_grad(self):
        for v in self.values():
            v.zero_grad()

    def reset_ctx(self, ctx):
        for v in self.values():
            v.reset_ctx(ctx)

    def setattr(self, name, value):
        for v in self.values():
            setattr(v, name, value)

    def save(self, filename, strip_prefix=""):
        arg_dict = {}
        for param in self.values():
            weight = param.data()
            if not param.name.startswith(strip_prefix):
                raise ValueError(
                    f"Prefix '{strip_prefix}' is to be stripped before saving, "
                    f"but Parameter's name '{param.name}' does not start with it.")
            arg_dict[param.name[len(strip_prefix):]] = weight
        nd.save(filename, arg_dict)

    def load(self, filename, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        arg_dict = nd.load(filename)
        if restore_prefix:
            arg_dict = {restore_prefix + k: v for k, v in arg_dict.items()}
        if not allow_missing:
            for name in self.keys():
                assert name in arg_dict, \
                    f"Parameter '{name}' is missing in file '{filename}'."
        for name in arg_dict:
            if name not in self._params:
                assert ignore_extra, \
                    f"Parameter '{name}' loaded from file '{filename}' is " \
                    "not present in ParameterDict"
                continue
            self[name].set_data(arg_dict[name].copyto(ctx) if ctx else arg_dict[name])
