"""Neural-network operators: the north-star kernel set.

Covers the reference's src/operator/nn/ family (Convolution, FullyConnected,
BatchNorm, LayerNorm, GroupNorm, InstanceNorm, LRN, Pooling, Activation,
softmax, Dropout, UpSampling, CTCLoss — ~30k LoC of C++/cuDNN there) plus
the legacy output heads (SoftmaxOutput src/operator/softmax_output.cc).
On TPU these lower to XLA ops that hit the MXU (conv_general_dilated,
dot_general) and VPU; there is no cuDNN-style algo selection — XLA autotunes
(the analogue of src/operator/nn/cudnn/cudnn_algoreg-inl.h is gone by design).

Layout: NCHW, OIHW to match the reference's public API. XLA transposes to
its preferred layout internally during compilation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as _np

from ..base import np_dtype
from .registry import register


def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        return tuple(v)
    return (v,) * n


# ------------------------------------------------------------ FullyConnected

@register("FullyConnected", aliases=("fully_connected",))
def _fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False, flatten=True):
    """Parity: src/operator/nn/fully_connected-inl.h. weight: (num_hidden, in)."""
    x = data.reshape(data.shape[0], -1) if flatten and data.ndim > 2 else data
    # no preferred_element_type: the TPU MXU accumulates bf16 matmuls in f32
    # natively, and a f32-typed intermediate breaks jax's transpose rules
    # under mixed bf16/f32 autodiff
    out = jax.lax.dot_general(
        x, weight, (((x.ndim - 1,), (1,)), ((), ())))
    if bias is not None and not no_bias:
        out = out + bias
    return out


# ---------------------------------------------------------------- Convolution

def _conv_dn(ndim, layout=None):
    """Dimension-number triple for a data layout. Channels-first (the
    reference's public default) keeps OIHW weights; channels-last — the
    TPU-native layout, where C rides the 128-wide lane dimension — uses
    OHWI weights (kernel dim 0 stays num_filter, like the reference's
    NHWC conv contract)."""
    default = {3: "NCW", 4: "NCHW", 5: "NCDHW"}[ndim]
    layout = layout or default
    if layout[1] == "C":          # channels-first: NCW/NCHW/NCDHW
        w = "OI" + layout[2:]
    else:                         # channels-last: NWC/NHWC/NDHWC
        w = "O" + layout[1:-1] + "I"
    return (layout, w, layout)


def _conv_pads(pad):
    """pad elements may be ints (symmetric) or (lo, hi) pairs — the
    asymmetric form is what the space-to-depth stem's stride-folded
    kernel needs."""
    return [tuple(p) if isinstance(p, (tuple, list)) else (p, p)
            for p in pad]


@register("Convolution")
def _convolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                 pad=None, num_filter=None, num_group=1, no_bias=False,
                 cudnn_tune=None, cudnn_off=False, workspace=None, layout=None):
    """Parity: src/operator/nn/convolution.cc:399. Groups via XLA
    feature_group_count (depthwise included — replaces
    depthwise_convolution_tf.cuh). layout='NHWC' (et al.) runs the conv
    channels-last with OHWI weights — the TPU-native path."""
    sdims = data.ndim - 2
    stride = _pair(stride or 1, sdims)
    dilate = _pair(dilate or 1, sdims)
    pad = pad if isinstance(pad, (tuple, list)) else _pair(pad or 0, sdims)
    dn = jax.lax.conv_dimension_numbers(
        data.shape, weight.shape, _conv_dn(data.ndim, layout))
    # no preferred_element_type: MXU accumulates bf16 convs in f32 natively,
    # and the f32-typed intermediate breaks conv transpose under bf16 AD
    out = jax.lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=_conv_pads(pad), rhs_dilation=dilate,
        dimension_numbers=dn, feature_group_count=num_group)
    if bias is not None and not no_bias:
        if layout and layout[1] != "C":
            out = out + bias
        else:
            out = out + bias.reshape((1, -1) + (1,) * sdims)
    return out


@register("Deconvolution")
def _deconvolution(data, weight, bias=None, kernel=None, stride=None, dilate=None,
                   pad=None, adj=None, target_shape=None, num_filter=None,
                   num_group=1, no_bias=True, cudnn_tune=None, cudnn_off=False,
                   workspace=None, layout=None):
    """Parity: src/operator/nn/deconvolution.cc. Transposed conv as the
    gradient of conv (XLA conv_transpose)."""
    sdims = data.ndim - 2
    stride = _pair(stride or 1, sdims)
    pad = _pair(pad or 0, sdims)
    dilate = _pair(dilate or 1, sdims)
    adj = _pair(adj or 0, sdims)
    kernel = weight.shape[2:]
    # weight layout (in, out/g, *k) per reference
    dn = jax.lax.conv_dimension_numbers(data.shape, weight.shape, _conv_dn(data.ndim))
    pads = []
    for i in range(sdims):
        k = (kernel[i] - 1) * dilate[i] + 1
        pads.append((k - 1 - pad[i], k - 1 - pad[i] + adj[i]))
    w = jnp.flip(weight, axis=tuple(range(2, 2 + sdims)))
    w = jnp.swapaxes(w, 0, 1)  # -> (out/g? , in, *k) for grouped transpose
    if num_group > 1:
        ci = data.shape[1]
        w = weight.reshape(num_group, ci // num_group, -1, *kernel)
        w = jnp.flip(w, axis=tuple(range(3, 3 + sdims)))
        w = jnp.swapaxes(w, 1, 2).reshape(-1, ci // num_group, *kernel)
    out = jax.lax.conv_general_dilated(
        data, w, window_strides=(1,) * sdims, padding=pads,
        lhs_dilation=stride, rhs_dilation=dilate, dimension_numbers=dn,
        feature_group_count=num_group)
    if bias is not None and not no_bias:
        out = out + bias.reshape((1, -1) + (1,) * sdims)
    return out


# -------------------------------------------------------------------- Pooling

@register("Pooling")
def _pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None,
             pad=None, pooling_convention="valid", count_include_pad=True,
             cudnn_off=False, p_value=2, layout=None):
    """Parity: src/operator/nn/pooling.cc (+pool.cuh). lax.reduce_window.
    layout='NHWC' (et al.) pools channels-last."""
    sdims = data.ndim - 2
    channels_last = bool(layout) and layout[1] != "C"
    if global_pool:
        axes = (tuple(range(1, data.ndim - 1)) if channels_last
                else tuple(range(2, data.ndim)))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            red = jnp.mean if pool_type == "avg" else jnp.sum
            return red(data, axis=axes, keepdims=True)
        return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p_value), axis=axes,
                                 keepdims=True), 1.0 / p_value)
    kernel = _pair(kernel, sdims)
    stride = _pair(stride or 1, sdims)
    pad = _pair(pad or 0, sdims)
    sp0 = 1 if channels_last else 2  # first spatial dim index
    if channels_last:
        window = (1,) + kernel + (1,)
        strides = (1,) + stride + (1,)
    else:
        window = (1, 1) + kernel
        strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode output: pad high side enough for a final partial window
        spads = []
        for i in range(sdims):
            in_sz = data.shape[sp0 + i]
            out_sz = -(-(in_sz + 2 * pad[i] - kernel[i]) // stride[i]) + 1
            needed = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            spads.append((pad[i], max(needed, pad[i])))
    else:
        spads = [(p, p) for p in pad]
    if channels_last:
        pads = [(0, 0)] + spads + [(0, 0)]
    else:
        pads = [(0, 0), (0, 0)] + spads
    # init values must be PYTHON scalars: jax only recognizes the
    # max/add monoid (-> differentiable reduce_window_max/sum primitives)
    # for scalar inits; array inits fall back to the general reduce_window,
    # which has no transpose rule under jit
    if pool_type == "max":
        init = -_np.inf if jnp.issubdtype(data.dtype, jnp.floating) \
            else int(jnp.iinfo(data.dtype).min)
        return jax.lax.reduce_window(data, init, jax.lax.max,
                                     window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = jax.lax.reduce_window(data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
                                  jax.lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
        ones = jnp.ones_like(data)
        cnt = jax.lax.reduce_window(ones, zero, jax.lax.add,
                                    window, strides, pads)
        return s / cnt
    # lp pooling
    zero = 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0
    s = jax.lax.reduce_window(jnp.power(jnp.abs(data), p_value),
                              zero, jax.lax.add, window, strides, pads)
    return jnp.power(s, 1.0 / p_value)


@register("UpSampling",
          param_normalizer=lambda p: {k: v for k, v in p.items() if k != "num_args"})
def _upsampling(*args, scale=1, sample_type="nearest", num_filter=0, multi_input_mode="concat", workspace=None):
    data = args[0]
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, scale, axis=2), scale, axis=3)
        if len(args) > 1:
            outs = [out]
            for extra in args[1:]:
                s = data.shape[2] * scale // extra.shape[2]
                outs.append(jnp.repeat(jnp.repeat(extra, s, axis=2), s, axis=3))
            return jnp.concatenate(outs, axis=1) if multi_input_mode == "concat" else sum(outs)
        return out
    # bilinear upsampling via resize
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * scale, w * scale), method="bilinear")


@register("BilinearResize2D")
def _bilinear_resize(data, like=None, height=0, width=0, scale_height=None, scale_width=None, mode="size"):
    n, c, h, w = data.shape
    if like is not None:
        height, width = like.shape[2], like.shape[3]
    if scale_height is not None:
        height = int(h * scale_height)
        width = int(w * scale_width)
    return jax.image.resize(data, (n, c, height, width), method="bilinear")


# ------------------------------------------------------------- normalization

@register("BatchNorm", aliases=("batch_norm",), mutate=(3, 4))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                min_calib_range=None, max_calib_range=None, _train=True):
    """Parity: src/operator/nn/batch_norm.cc. Returns (out, new_mean, new_var)
    with the moving stats written back through mutate slots — the functional
    bridge for the reference's aux-state mutation."""
    axis = axis if axis >= 0 else data.ndim + axis
    red = tuple(i for i in range(data.ndim) if i != axis)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if _train and not use_global_stats:
        # Single-pass f32 statistics (E[x²] − E[x]²): one fused read of the
        # activation for both moments instead of mean-then-variance's two —
        # this is the BN-statistics lever that dominates the train-step's
        # HBM roofline (PERF.md). Stats stay f32 end-to-end; only the EMA
        # write-back converts to the moving-stat dtype.
        x32 = data.astype(jnp.float32)
        mean = jnp.mean(x32, axis=red)
        var = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=red) - jnp.square(mean), 0.0)
        new_mm = (moving_mean.astype(jnp.float32) * momentum
                  + mean * (1 - momentum)).astype(moving_mean.dtype)
        new_mv = (moving_var.astype(jnp.float32) * momentum
                  + var * (1 - momentum)).astype(moving_var.dtype)
    else:
        mean = moving_mean.astype(jnp.float32)
        var = moving_var.astype(jnp.float32)
        new_mm, new_mv = moving_mean, moving_var
    # fold to a single multiply-add pass in the input dtype: scale/shift are
    # per-channel vectors computed in f32
    inv = jax.lax.rsqrt(var + eps) * g.astype(jnp.float32)
    shift = beta.astype(jnp.float32) - mean * inv
    out = (data * inv.astype(data.dtype).reshape(bshape)
           + shift.astype(data.dtype).reshape(bshape))
    return out, new_mm, new_mv


@register("LayerNorm", aliases=("layer_norm",))
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    bshape = [1] * data.ndim
    ax = axis if axis >= 0 else data.ndim + axis
    bshape[ax] = data.shape[ax]
    out = out * gamma.reshape(bshape) + beta.reshape(bshape)
    if output_mean_var:
        return out, jnp.squeeze(mean, ax), jnp.squeeze(var, ax)
    return out


@register("RMSNorm", aliases=("rms_norm",))
def _rms_norm(data, gamma, gate=None, eps=1e-6, zero_centered=False):
    """Root-mean-square normalisation over the last axis, in float32:
    ``y = x * rsqrt(mean(x^2) + eps) * w``. ``zero_centered`` takes
    ``w = 1 + gamma`` (gamma trained from zero); with ``gate`` it is the
    gated form ``y * silu(gate)`` (a linear-attention layer's output
    norm). The result has ``data``'s dtype."""
    x = data.astype(jnp.float32)
    w = gamma.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps) * (1.0 + w if zero_centered else w)
    if gate is not None:
        y = y * jax.nn.silu(gate.astype(jnp.float32))
    return y.astype(data.dtype)


@register("rotary_embedding")
def _rotary_embedding(data, positions, rotary_dim=None, theta=10000.0):
    """Rotary position embedding on the first ``rotary_dim`` channels of
    each head, half-split pairing (channel i rotates with channel
    i + rotary_dim / 2); the other channels pass. ``data`` is
    (B, H, T, D), ``positions`` (T,) integers. Angles in float32."""
    d = data.shape[-1]
    r = d if rotary_dim is None else int(rotary_dim)
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # (T, r/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x = data[..., :r].astype(jnp.float32)
    x1, x2 = x[..., :r // 2], x[..., r // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1).astype(data.dtype)
    return out if r == d else jnp.concatenate([out, data[..., r:]], axis=-1)


@register("GroupNorm")
def _group_norm(data, gamma, beta, num_groups=1, eps=1e-5, output_mean_var=False):
    n, c = data.shape[:2]
    x = data.reshape((n, num_groups, c // num_groups) + data.shape[2:])
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    out = (x - mean) * jax.lax.rsqrt(var + eps)
    # gamma/beta are per-group, shape (num_groups,) — src/operator/nn/group_norm-inl.h
    bshape = (1, num_groups) + (1,) * (x.ndim - 2)
    out = out * gamma.reshape(bshape) + beta.reshape(bshape)
    return out.reshape(data.shape)


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    out = (data - mean) * jax.lax.rsqrt(var + eps)
    bshape = (1, data.shape[1]) + (1,) * (data.ndim - 2)
    return out * gamma.reshape(bshape) + beta.reshape(bshape)


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    parts = [padded[:, i:i + data.shape[1]] for i in range(nsize)]
    ssum = sum(parts)
    return data / jnp.power(knorm + alpha / nsize * ssum, beta)


# ----------------------------------------------------------------- activation

@register("Activation")
def _activation(data, act_type="relu"):
    fns = {
        "relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus, "softsign": jax.nn.soft_sign,
        "gelu": jax.nn.gelu, "silu": jax.nn.silu, "swish": jax.nn.silu,
    }
    return fns[act_type](data)


@register("LeakyReLU")
def _leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jax.nn.leaky_relu(data, slope)
    if act_type == "prelu":
        g = gamma.reshape((1, -1) + (1,) * (data.ndim - 2)) if gamma.ndim == 1 else gamma
        return jnp.where(data >= 0, data, g * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        return jax.nn.leaky_relu(data, (lower_bound + upper_bound) / 2)
    raise ValueError(act_type)


@register("softmax")
def _softmax(data, axis=-1, length=None, temperature=None, dtype=None, use_length=False):
    x = data / temperature if temperature else data
    out = jax.nn.softmax(x, axis=axis)
    return out.astype(np_dtype(dtype)) if dtype else out


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None, use_length=False):
    x = data / temperature if temperature else data
    out = jax.nn.log_softmax(x, axis=axis)
    return out.astype(np_dtype(dtype)) if dtype else out


@register("softmin")
def _softmin(data, axis=-1, temperature=None, dtype=None):
    return jax.nn.softmax(-data, axis=axis)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# --------------------------------------------------------------- output heads
# Legacy Module-API heads: forward is identity-ish; the *backward* defines the
# loss gradient. We implement them with custom VJPs so Module training matches
# the reference (src/operator/softmax_output.cc).

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _softmax_output_core(data, label, grad_scale, ignore_label, use_ignore, normalization_mult):
    return jax.nn.softmax(data, axis=-1)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore, normalization_mult):
    out = jax.nn.softmax(data, axis=-1)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore,
                        normalization_mult, res, g):
    out, label = res
    if label.ndim == out.ndim:
        one_hot = label
    else:
        one_hot = jax.nn.one_hot(label.astype(jnp.int32), out.shape[-1], dtype=out.dtype)
    grad = (out - one_hot)
    if use_ignore:
        mask = (label != ignore_label).astype(out.dtype)
        grad = grad * mask[..., None]
    grad = grad * grad_scale * normalization_mult
    return grad, jnp.zeros_like(label)


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=("Softmax",))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    """Parity: src/operator/softmax_output.cc — forward softmax, backward
    (p - onehot(label)) * grad_scale."""
    x = data
    if multi_output:
        # (n, c, d1...) -> softmax over c
        x = jnp.moveaxis(data, 1, -1)
    n_mult = 1.0
    if normalization == "batch":
        n_mult = 1.0
    elif normalization == "valid":
        n_mult = 1.0  # applied in bwd via mask mean; approximation documented
    out = _softmax_output_core(x, label, grad_scale, ignore_label,
                               bool(use_ignore), n_mult)
    if multi_output:
        out = jnp.moveaxis(out, -1, 1)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _regression_core(data, label, kind, grad_scale):
    if kind == 1:
        return jax.nn.sigmoid(data)
    return data


def _regression_fwd(data, label, kind, grad_scale):
    out = jax.nn.sigmoid(data) if kind == 1 else data
    return out, (out, label)


def _regression_bwd(kind, grad_scale, res, g):
    out, label = res
    # broadcast label up to out's shape for the residual, but keep the
    # ORIGINAL label shape for its (zero) cotangent — custom_vjp requires
    # bwd outputs to match the primal argument shapes exactly
    lbl = label.reshape(out.shape)
    if kind == 2:  # MAE
        grad = jnp.sign(out - lbl)
    else:  # linear / logistic both use (pred - label)
        grad = out - lbl
    num = out.shape[1] if out.ndim > 1 else 1
    return grad * grad_scale / num, jnp.zeros_like(label)


_regression_core.defvjp(_regression_fwd, _regression_bwd)


@register("LinearRegressionOutput")
def _linear_regression_output(data, label, grad_scale=1.0):
    return _regression_core(data, label, 0, grad_scale)


@register("LogisticRegressionOutput")
def _logistic_regression_output(data, label, grad_scale=1.0):
    return _regression_core(data, label, 1, grad_scale)


@register("MAERegressionOutput")
def _mae_regression_output(data, label, grad_scale=1.0):
    return _regression_core(data, label, 2, grad_scale)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1], dtype=data.dtype)
    return -jnp.sum(oh * logp)


@register("SVMOutput")
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0, use_linear=False):
    return data


# -------------------------------------------------------------------- dropout

@register("Dropout", mutate=(1,))
def _dropout(data, rng_key, p=0.5, mode="training", axes=(), cudnn_off=False, _train=True):
    """Parity: src/operator/nn/dropout-inl.h. The RNG key is an explicit
    mutable cell (threaded key-stream, SURVEY.md §7.8) so dropout stays
    correct across steps inside one jitted executable."""
    new_key, sub = jax.random.split(rng_key)
    if not _train and mode != "always":
        return data, new_key
    shape = data.shape
    if axes:
        shape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    keep = 1.0 - p
    mask = jax.random.bernoulli(sub, keep, shape).astype(data.dtype) / keep
    return data * mask, new_key


# ------------------------------------------------------------------- ctc loss

@register("CTCLoss", aliases=("ctc_loss",))
def _ctc_loss(data, label, data_lengths=None, label_lengths=None,
              use_data_lengths=False, use_label_lengths=False, blank_label="first"):
    """Parity: src/operator/nn/ctc_loss.cc (warp-ctc). Dense log-alpha
    recursion via lax.scan — XLA-friendly CTC."""
    # data: (T, N, C) alphabet incl. blank; label: (N, L)
    T, N, C = data.shape
    L = label.shape[1]
    blank = 0 if blank_label == "first" else C - 1
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    if blank_label == "first":
        pass
    ext_len = 2 * L + 1
    ext = jnp.full((N, ext_len), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(lab)
    lab_lens = (label_lengths.astype(jnp.int32) if use_label_lengths and label_lengths is not None
                else jnp.sum((lab != blank if blank_label == "first" else lab != -1).astype(jnp.int32), axis=1))
    dat_lens = (data_lengths.astype(jnp.int32) if use_data_lengths and data_lengths is not None
                else jnp.full((N,), T, jnp.int32))
    neg_inf = -1e30
    ext_lens = 2 * lab_lens + 1

    def step(alpha, logp_t):
        # alpha: (N, ext_len)
        p = jnp.take_along_axis(logp_t, ext, axis=1)  # (N, ext_len)
        a0 = alpha
        a1 = jnp.pad(alpha[:, :-1], ((0, 0), (1, 0)), constant_values=neg_inf)
        a2 = jnp.pad(alpha[:, :-2], ((0, 0), (2, 0)), constant_values=neg_inf)
        can_skip = (ext != jnp.pad(ext[:, :-2], ((0, 0), (2, 0)), constant_values=-1)) & (ext != blank)
        a2 = jnp.where(can_skip, a2, neg_inf)
        new = jnp.logaddexp(jnp.logaddexp(a0, a1), a2) + p
        return new, new

    alpha0 = jnp.full((N, ext_len), neg_inf)
    alpha0 = alpha0.at[:, 0].set(logp[0, :, blank])
    alpha0 = alpha0.at[:, 1].set(jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0])
    alphas_last, alphas = jax.lax.scan(step, alpha0, logp[1:])
    all_alphas = jnp.concatenate([alpha0[None], alphas], axis=0)  # (T, N, ext)
    t_idx = jnp.clip(dat_lens - 1, 0, T - 1)
    final = all_alphas[t_idx, jnp.arange(N)]  # (N, ext)
    lastm1 = jnp.take_along_axis(final, jnp.clip(ext_lens - 1, 0, ext_len - 1)[:, None], axis=1)[:, 0]
    lastm2 = jnp.take_along_axis(final, jnp.clip(ext_lens - 2, 0, ext_len - 1)[:, None], axis=1)[:, 0]
    return -jnp.logaddexp(lastm1, lastm2)


# ----------------------------------------------------- attention primitives
# Parity: src/operator/contrib/transformer.cc:650-819 (interleaved qkv matmul
# ops used by gluonnlp). Plus a fused scaled-dot attention that XLA/Pallas can
# turn into a flash-style kernel.

@register("_contrib_interleaved_matmul_selfatt_qk")
def _interleaved_qk(qkv, heads=1):
    # qkv: (L, N, 3*H*d) interleaved per head
    L, N, P = qkv.shape
    d = P // (3 * heads)
    x = qkv.reshape(L, N, heads, 3, d)
    q, k = x[..., 0, :], x[..., 1, :]
    q = q.transpose(1, 2, 0, 3).reshape(N * heads, L, d)
    k = k.transpose(1, 2, 0, 3).reshape(N * heads, L, d)
    return jnp.matmul(q, jnp.swapaxes(k, -1, -2)) / jnp.sqrt(d).astype(qkv.dtype)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def _interleaved_valatt(qkv, att, heads=1):
    L, N, P = qkv.shape
    d = P // (3 * heads)
    x = qkv.reshape(L, N, heads, 3, d)
    v = x[..., 2, :].transpose(1, 2, 0, 3).reshape(N * heads, L, d)
    out = jnp.matmul(att, v)  # (N*h, L, d)
    return out.reshape(N, heads, L, d).transpose(2, 0, 1, 3).reshape(L, N, heads * d)


@register("_contrib_interleaved_matmul_encdec_qk")
def _interleaved_encdec_qk(queries, keys_values, heads=1):
    """Encoder-decoder attention scores. queries (Lq, N, H*d); keys_values
    (Lkv, N, H*2*d) interleaved [k_h, v_h] per head. Returns
    (N*H, Lq, Lkv), scaled by 1/sqrt(d).
    Parity: src/operator/contrib/transformer.cc:736-778
    (InterleavedMatMulEncDecQKCPU strided-gemm layout)."""
    lq, n, p = queries.shape
    d = p // heads
    lkv = keys_values.shape[0]
    q = queries.reshape(lq, n, heads, d).transpose(1, 2, 0, 3) \
        .reshape(n * heads, lq, d)
    kv = keys_values.reshape(lkv, n, heads, 2, d)
    k = kv[..., 0, :].transpose(1, 2, 0, 3).reshape(n * heads, lkv, d)
    scale = jnp.asarray(1.0, queries.dtype) / jnp.sqrt(d).astype(queries.dtype)
    return jnp.matmul(q * scale, jnp.swapaxes(k, -1, -2))


@register("_contrib_interleaved_matmul_encdec_valatt")
def _interleaved_encdec_valatt(keys_values, attention, heads=1):
    """Attention-weighted values for encoder-decoder attention.
    keys_values (Lkv, N, H*2*d); attention (N*H, Lq, Lkv). Returns
    (Lq, N, H*d). Parity: transformer.cc:780-819."""
    lkv, n, p2 = keys_values.shape
    d = p2 // (2 * heads)
    kv = keys_values.reshape(lkv, n, heads, 2, d)
    v = kv[..., 1, :].transpose(1, 2, 0, 3).reshape(n * heads, lkv, d)
    out = jnp.matmul(attention, v)  # (N*H, Lq, d)
    lq = out.shape[1]
    return out.reshape(n, heads, lq, d).transpose(2, 0, 1, 3) \
        .reshape(lq, n, heads * d)


@register("scaled_dot_product_attention")
def _sdpa(q, k, v, mask=None, causal=False, scale=None, impl="xla",
          window=None):
    """TPU-native fused attention (new capability; long-context story lives
    in parallel/ring_attention.py). q,k,v: (B, H, L, D).

    ``window`` (with ``causal``): a query sees the keys
    ``0 <= i - j < window``, itself counted; in the kernels the tiles
    wholly behind the window are skipped as those past the diagonal are.

    impl='flash' opts into the Pallas streaming kernel
    (ops/pallas_kernels.py): O(T) HBM instead of the O(T^2) score matrix.
    Trainable: the op routes through flash_attention_with_grad
    (custom_vjp, blockwise backward from the saved log-sum-exp), so
    nd/sym/gluon models using impl='flash' get the kernel in BOTH passes."""
    if impl == "flash":
        from .pallas_kernels import flash_attention_with_grad

        if mask is not None:
            raise ValueError(
                "impl='flash' does not support an explicit mask (only "
                "causal=True); the dense path would defeat the O(T) memory "
                "guarantee you opted into")
        # the kernel or an error: an unsupported shape (ScheduleError) or
        # a non-TPU device (Pallas refuses to lower) raises — never the
        # dense composition under the name the caller asked for
        return flash_attention_with_grad(q, k, v, causal=causal, scale=scale,
                                         window=window)
    if window is not None and not causal:
        raise ValueError("a window is 0 <= i - j < window, so it needs "
                         "causal=True")
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / _np.sqrt(d)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * s
    if causal:
        L, S = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((L, S), bool))
        if window is not None:
            cm &= ~jnp.tril(jnp.ones((L, S), bool), -int(window))
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask.astype(bool), logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", w, v)
