"""Gluon basic neural network layers.

Parity: python/mxnet/gluon/nn/basic_layers.py in the reference (Dense :144,
BatchNorm :282, Embedding :379, LayerNorm :546, etc.). Every layer's
hybrid_forward dispatches through the op registry, so under hybridize the
whole network lowers to one XLA program with bf16-friendly matmuls on the MXU.
"""
from __future__ import annotations

import numpy as _np

from ..block import Block, HybridBlock
from ..parameter import Parameter
from ... import initializer

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "InstanceNorm", "LayerNorm", "RMSNorm", "GroupNorm", "Embedding",
           "Flatten",
           "Lambda", "HybridLambda", "Activation", "LeakyReLU", "PReLU",
           "ELU", "SELU", "Swish", "GELU"]


class Sequential(Block):
    """Stacks Blocks sequentially (gluon/nn/basic_layers.py:29)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())

    def hybridize(self, active=True, **kwargs):
        if self._children and all(isinstance(c, HybridBlock)
                                  for c in self._children.values()):
            import warnings
            warnings.warn(
                "All children of this Sequential layer are HybridBlocks. "
                "Consider using HybridSequential for the best performance.",
                stacklevel=2)
        super().hybridize(active, **kwargs)


class HybridSequential(HybridBlock):
    """Stacks HybridBlocks sequentially (gluon/nn/basic_layers.py:98)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def _call_with_params(self, *args):
        x = args[0]
        for block in self._children.values():
            x = block(x)
        return x

    def forward(self, x, *args):
        from ...symbol import Symbol
        if isinstance(x, Symbol):
            for block in self._children.values():
                x = block(x)
            return x
        return super().forward(x, *args)

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())[key]
        if isinstance(layers, list):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                net.add(*layers)
            return net
        return layers

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully-connected layer: ``out = act(dot(x, w.T) + b)``
    (gluon/nn/basic_layers.py:144). Lowered to one MXU dot_general."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype=_np.float32, weight_initializer=None,
                 bias_initializer="zeros", in_units=0, **kwargs):
        super().__init__(**kwargs)
        self._flatten = flatten
        self._units = units
        self._in_units = in_units
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), init=weight_initializer,
                dtype=dtype, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), init=bias_initializer,
                    dtype=dtype, allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def hybrid_forward(self, F, x, weight, bias=None):
        out = F.FullyConnected(x, weight, bias, no_bias=bias is None,
                               num_hidden=self._units, flatten=self._flatten)
        if self.act is not None:
            out = self.act(out)
        return out

    def __repr__(self):
        shape = self.weight.shape
        return "{name}({layout}, {act})".format(
            name=self.__class__.__name__,
            act=self.act if self.act else "linear",
            layout=f"{shape[1] if len(shape) > 1 and shape[1] else None} -> {shape[0]}")


class Dropout(HybridBlock):
    """Dropout regularization (gluon/nn/basic_layers.py:235)."""

    def __init__(self, rate, axes=(), **kwargs):
        super().__init__(**kwargs)
        self._rate = rate
        self._axes = axes

    def hybrid_forward(self, F, x):
        if self._rate == 0:
            return x
        return F.Dropout(x, p=self._rate, axes=self._axes)

    def __repr__(self):
        return f"{self.__class__.__name__}(p = {self._rate}, axes={self._axes})"


class BatchNorm(HybridBlock):
    """Batch normalization (gluon/nn/basic_layers.py:282). Running stats are
    auxiliary params mutated by the op (mutate slots), matching the
    reference's aux-state semantics."""

    def __init__(self, axis=1, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones", running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"axis": axis, "eps": epsilon, "momentum": momentum,
                        "fix_gamma": not scale,
                        "use_global_stats": use_global_stats}
        self.in_channels = in_channels
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True, differentiable=scale)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True, differentiable=center)
            self.running_mean = self.params.get(
                "running_mean", grad_req="null", shape=(in_channels,),
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False)
            self.running_var = self.params.get(
                "running_var", grad_req="null", shape=(in_channels,),
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False)

    def cast(self, dtype):
        if _np.dtype(dtype).name == "float16":
            dtype = "float32"
        super().cast(dtype)

    def hybrid_forward(self, F, x, gamma, beta, running_mean, running_var):
        return F.BatchNorm(x, gamma, beta, running_mean, running_var,
                           name="fwd", **self._kwargs)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join(f"{k}={v}" for k, v in self._kwargs.items()))


class Embedding(HybridBlock):
    """Turns non-negative integers into dense vectors
    (gluon/nn/basic_layers.py:379). Lowered to XLA gather; gradient is a
    dense scatter-add (the TPU replacement for row_sparse grads —
    SURVEY.md §7 hard part 4)."""

    def __init__(self, input_dim, output_dim, dtype=_np.float32,
                 weight_initializer=None, sparse_grad=False, **kwargs):
        super().__init__(**kwargs)
        if sparse_grad:
            import warnings
            warnings.warn("sparse_grad is not supported on TPU; using dense "
                          "gradients", stacklevel=2)
        self._kwargs = {"input_dim": input_dim, "output_dim": output_dim,
                        "dtype": _np.dtype(dtype).name}
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim),
                init=weight_initializer, dtype=dtype, allow_deferred_init=True)

    def hybrid_forward(self, F, x, weight):
        return F.Embedding(x, weight, name="fwd", **self._kwargs)

    def __repr__(self):
        return "{name}({input_dim} -> {output_dim}, {dtype})".format(
            name=self.__class__.__name__, **self._kwargs)


class Flatten(HybridBlock):
    """Flattens the input to (batch, -1) (gluon/nn/basic_layers.py:435)."""

    def hybrid_forward(self, F, x):
        return F.Flatten(x)

    def __repr__(self):
        return self.__class__.__name__


class InstanceNorm(HybridBlock):
    """Instance normalization (gluon/nn/basic_layers.py:457)."""

    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"eps": epsilon, "axis": axis, "center": center,
                        "scale": scale}
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True)

    def hybrid_forward(self, F, x, gamma, beta):
        if self._axis == 1:
            return F.InstanceNorm(x, gamma, beta, name="fwd", eps=self._epsilon)
        x = x.swapaxes(1, self._axis) if hasattr(x, "swapaxes") else x
        return F.InstanceNorm(x, gamma, beta, name="fwd",
                              eps=self._epsilon).swapaxes(1, self._axis)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join(f"{k}={v}" for k, v in self._kwargs.items()))


class LayerNorm(HybridBlock):
    """Layer normalization (gluon/nn/basic_layers.py:546)."""

    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._kwargs = {"eps": epsilon, "axis": axis, "center": center,
                        "scale": scale}
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True)

    def hybrid_forward(self, F, data, gamma, beta):
        return F.LayerNorm(data, gamma, beta, axis=self._axis,
                           eps=self._epsilon)

    def __repr__(self):
        in_channels = self.gamma.shape[0]
        return "{name}({content}, in_channels={in_channels})".format(
            name=self.__class__.__name__, in_channels=in_channels,
            content=", ".join(f"{k}={v}" for k, v in self._kwargs.items()))


class RMSNorm(HybridBlock):
    """Root-mean-square normalisation over the last axis, in float32:
    ``x * rsqrt(mean(x^2) + eps) * w``. ``zero_centered`` takes
    ``w = 1 + weight`` with the weight trained from zero (else from
    one); called with a second input, ``norm(x, gate)``, it is the gated
    form ``.. * silu(gate)``."""

    def __init__(self, in_channels, epsilon=1e-6, zero_centered=False,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._kwargs = {"eps": epsilon, "zero_centered": zero_centered}
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(in_channels,),
                init="zeros" if zero_centered else "ones")

    def hybrid_forward(self, F, data, gate=None, weight=None):
        if gate is None:
            return F.RMSNorm(data, weight, **self._kwargs)
        return F.RMSNorm(data, weight, gate, **self._kwargs)

    def __repr__(self):
        return "{name}({content}, in_channels={n})".format(
            name=self.__class__.__name__, n=self.weight.shape[0],
            content=", ".join(f"{k}={v}" for k, v in self._kwargs.items()))


class GroupNorm(HybridBlock):
    """Group normalization (gluon/nn/basic_layers.py:625)."""

    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._kwargs = {"eps": epsilon, "num_groups": num_groups,
                        "center": center, "scale": scale}
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", grad_req="write" if scale else "null",
                shape=(num_groups,), init=gamma_initializer,
                allow_deferred_init=True)
            self.beta = self.params.get(
                "beta", grad_req="write" if center else "null",
                shape=(num_groups,), init=beta_initializer,
                allow_deferred_init=True)

    def hybrid_forward(self, F, data, gamma, beta):
        return F.GroupNorm(data, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)

    def __repr__(self):
        return "{name}({content})".format(
            name=self.__class__.__name__,
            content=", ".join(f"{k}={v}" for k, v in self._kwargs.items()))


class Lambda(Block):
    """Wraps an operator or expression as a Block
    (gluon/nn/basic_layers.py:701)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            assert hasattr(nd, function), \
                f"Function name {function} is not found in ndarray."
            self._func_impl = getattr(nd, function)
            self._func_name = function
        elif callable(function):
            self._func_impl = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: "
                             f"{function} of type {type(function)}")

    def forward(self, *args):
        return self._func_impl(*args)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._func_name})"


class HybridLambda(HybridBlock):
    """Wraps an operator or expression as a HybridBlock
    (gluon/nn/basic_layers.py:746)."""

    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as nd
            from ... import symbol as sym
            assert hasattr(nd, function) and hasattr(sym, function), \
                f"Function name {function} is not found in ndarray/symbol."
            self._func = lambda F, *args: getattr(F, function)(*args)
            self._func_name = function
        elif callable(function):
            self._func = function
            self._func_name = function.__name__
        else:
            raise ValueError("Unrecognized function in lambda: "
                             f"{function} of type {type(function)}")

    def hybrid_forward(self, F, x, *args):
        return self._func(F, x, *args)

    def __repr__(self):
        return f"{self.__class__.__name__}({self._func_name})"


# ------------------------------------------------------------- activations
# Parity: python/mxnet/gluon/nn/activations.py

class Activation(HybridBlock):
    """Applies an activation function (relu/sigmoid/tanh/softrelu/softsign)."""

    def __init__(self, activation, **kwargs):
        self._act_type = activation
        super().__init__(**kwargs)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type, name="fwd")

    def __repr__(self):
        return f"{self.__class__.__name__}({self._act_type})"


class LeakyReLU(HybridBlock):
    """Leaky ReLU (gluon/nn/activations.py:77)."""

    def __init__(self, alpha, **kwargs):
        assert alpha >= 0, "Slope coefficient for LeakyReLU must be no less than 0."
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="leaky", slope=self._alpha, name="fwd")

    def __repr__(self):
        return f"{self.__class__.__name__}({self._alpha})"


class PReLU(HybridBlock):
    """Parametric leaky ReLU (gluon/nn/activations.py:114)."""

    def __init__(self, alpha_initializer=initializer.Constant(0.25),
                 in_channels=1, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.alpha = self.params.get("alpha", shape=(in_channels,),
                                         init=alpha_initializer)

    def hybrid_forward(self, F, x, alpha):
        return F.LeakyReLU(x, gamma=alpha, act_type="prelu", name="fwd")


class ELU(HybridBlock):
    """Exponential Linear Unit (gluon/nn/activations.py:153)."""

    def __init__(self, alpha=1.0, **kwargs):
        super().__init__(**kwargs)
        self._alpha = alpha

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="elu", slope=self._alpha)


class SELU(HybridBlock):
    """Scaled Exponential Linear Unit (gluon/nn/activations.py:184)."""

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="selu", name="fwd")


class Swish(HybridBlock):
    """Swish: x * sigmoid(beta*x) (gluon/nn/activations.py:210)."""

    def __init__(self, beta=1.0, **kwargs):
        super().__init__(**kwargs)
        self._beta = beta

    def hybrid_forward(self, F, x):
        return x * F.sigmoid(self._beta * x)


class GELU(HybridBlock):
    """Gaussian Error Linear Unit (gluon/nn/activations.py:234)."""

    def hybrid_forward(self, F, x):
        return F.LeakyReLU(x, act_type="gelu", name="fwd")
