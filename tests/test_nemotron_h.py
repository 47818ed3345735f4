"""Nemotron-H's parts and the whole tower against the plain float32
reference (``benchmarks/references/nemotron_h.py``, which imports nothing
of the program), at small sizes on the CPU, seeded weights: the chunked
state-space scan against the token-by-token recurrence (forward and
gradients, a length off the chunk grid, several groups), the Mamba-2
mixer against the reference's, the ungated squared-ReLU experts routed
and shared against a loop (with gradients), the short convolution's
bias in its ``jax.numpy`` form and in its kernels (interpret mode), the
7-layer tower through ``ShardedTrainer`` (logits, first-step loss, the
gradient of every parameter), the expert layer's shares adding up to
the uncut layer, the layers placed where the pattern says, and the
``ssm`` scope with the arithmetic of its roofline."""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.contrib import nn as contrib_nn
from mxnet_tpu.gluon.model_zoo import nemotron_h_lm
from mxnet_tpu.gluon.model_zoo.nemotron_h import layer_types_of
from mxnet_tpu.ops import conv_silu_kernels, linear_attention
from mxnet_tpu.ops import moe as moe_ops
from mxnet_tpu.ops.state_space import mamba_chunk_scan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import ssm_scope  # noqa: E402


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_t_{kind}_{name}", os.path.join(ROOT, "benchmarks", kind,
                                          name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "nemotron_h")
model = _load("models", "nemotron_h")

# the published unit EMEMEM*; 6 of 16 experts held, from the fifth on;
# a sequence (45) that is not a multiple of the scan's chunk (16)
CONFIG = dict(
    vocab_size=97, hidden_size=32, num_layers=7,
    layer_types=["moe", "mamba", "moe", "mamba", "moe", "mamba",
                 "attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=16, time_step_limit=[0.0, None],
    time_step_min=0.001, time_step_max=0.1, time_step_floor=1e-4,
    moe_intermediate_size=16, moe_shared_expert_intermediate_size=24,
    num_experts=6, num_experts_per_tok=3, norm_topk_prob=True,
    routed_scaling_factor=2.5, mlp_hidden_act="relu2",
    layer_norm_epsilon=1e-5,
    published={"num_experts": 16}, deployment={"first_expert": 5},
    model_type="nemotron_h", num_hidden_layers=52)
SIZES = model.reference_sizes(CONFIG)
TOL = dict(rtol=2e-4, atol=2e-5)
T = 45


def _rng(seed=0):
    return np.random.default_rng(seed)


def _array(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def _close(got, want, **tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**TOL, **tol})


def _apply(blk, x):
    """(output, moved auxiliary state) of ``blk`` on ``x``, compiled
    whole (an eager call compiles op by op)."""
    fwd = parallel.functional_call(blk, train=True)
    return jax.jit(fwd)(parallel.param_arrays(blk), parallel.aux_arrays(blk),
                        x)


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if not want.size:
        return 0.0 if got.shape == want.shape else float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ------------------------------------------------------- the chunked scan

def _recurrence(x, dt, A, B, C, D):
    """The scan token by token: S = exp(dt A) S + dt x B^T, y = S C + D x,
    head h reading group h // (heads / groups)."""
    h, g = x.shape[2], B.shape[2]
    B, C = (jnp.repeat(v, h // g, axis=2) for v in (B, C))

    def token(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        s = jnp.exp(dt_t * A)[..., None, None] * s \
            + dt_t[..., None, None] * x_t[..., :, None] * b_t[..., None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros(x.shape[:1] + (h, x.shape[3], B.shape[3])),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _scan_inputs(seed, b=2, t=37, h=6, p=5, g=3, n=4):
    rng = _rng(seed)
    return (_array(rng, b, t, h, p),
            jax.nn.softplus(_array(rng, b, t, h)),
            -jnp.exp(_array(rng, h)),
            _array(rng, b, t, g, n), _array(rng, b, t, g, n),
            _array(rng, h))


@pytest.mark.parametrize("chunk", [8, 64])
def test_the_chunked_scan_is_the_token_recurrence(chunk):
    """37 tokens in chunks of 8 (a ragged last chunk) and 64 (one chunk,
    padded), 6 heads in 3 groups: the outputs and the gradient of every
    input."""
    args = _scan_inputs(1)

    def outputs_and_grads(fn):
        def total(*a):
            out = fn(*a)
            return jnp.sum(jnp.sin(out)), out

        return jax.jit(jax.grad(total, argnums=tuple(range(6)),
                                has_aux=True))

    with jax.default_matmul_precision("highest"):
        got, out = outputs_and_grads(
            lambda *a: mamba_chunk_scan(*a, chunk=chunk))(*args)
        want, ref_out = outputs_and_grads(_recurrence)(*args)
    _close(out, ref_out)
    for g, w in zip(got, want):
        assert _rel(g, w) <= 2e-5


def test_the_scan_keeps_its_state_in_float32_and_its_dtype():
    args = _scan_inputs(2, t=40)
    low = (args[0].astype(jnp.bfloat16),) + args[1:3] + tuple(
        a.astype(jnp.bfloat16) for a in args[3:5]) + args[5:]
    out = mamba_chunk_scan(*low, chunk=16)
    assert out.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = _recurrence(*(a.astype(jnp.float32) for a in low))
    # bf16 operands, float32 state and accumulation: the error is the
    # operands' rounding, not a state rounded every chunk
    assert _rel(out, want) <= 0.03
    with pytest.raises(ValueError, match="groups"):
        mamba_chunk_scan(*_scan_inputs(3, h=5, g=3))


# --------------------------------------------------------------- the mixer

def _mixer(seed=3):
    mx.random.seed(seed)
    blk = contrib_nn.Mamba2Mixer(32, 8, 8, 2, 16, conv_kernel=4, chunk=16,
                                 prefix="mamba_")
    blk.initialize(mx.initializer.Xavier())
    rng = _rng(seed)
    blk.norm_weight.set_data(mx.nd.array(1 + _array(rng, 64, scale=0.2)))
    blk.D.set_data(mx.nd.array(1 + _array(rng, 8, scale=0.3)))
    return blk


def _mamba_tree(blk):
    """The mixer's weights as the reference's, [xBC | z | dt] laid back
    to the published [z | xBC | dt]."""
    conv_dim, inner = blk.conv_weight.shape[0], blk.norm_weight.shape[0]
    rows = blk.in_proj.weight.data().data_
    w = {k: getattr(blk, k).data().data_
         for k in ("conv_weight", "conv_bias", "A_log", "D", "dt_bias",
                   "norm_weight")}
    return {"in_w": jnp.concatenate([rows[conv_dim:conv_dim + inner],
                                     rows[:conv_dim],
                                     rows[conv_dim + inner:]]),
            "conv_w": w["conv_weight"], "conv_b": w["conv_bias"],
            "A_log": w["A_log"], "D": w["D"], "dt_bias": w["dt_bias"],
            "norm_w": w["norm_weight"],
            "out_w": blk.out_proj.weight.data().data_}


def test_the_mixer_is_the_reference_mixer():
    """The block (in_proj laid [xBC | z | dt], the convolution with its
    bias, the chunked scan, the grouped gated norm) against the
    reference's token-by-token mixer on the published [z | xBC | dt]."""
    blk = _mixer()
    x = _array(_rng(4), 2, T, 32)
    with jax.default_matmul_precision("highest"):
        got = _apply(blk, x)[0]
        want = jax.jit(lambda t: ref.mamba(x, t, dict(
            SIZES, mamba_heads=8)))(_mamba_tree(blk))
    _close(got, want, rtol=1e-4, atol=1e-5)
    # the dt at initialisation lies in [time_step_min, time_step_max]
    dt = jax.nn.softplus(blk.dt_bias.data().data_)
    assert float(dt.min()) >= 0.001 * 0.999 and float(dt.max()) <= 0.1001
    np.testing.assert_allclose(np.exp(blk.A_log.data().asnumpy()),
                               np.arange(1, 9), rtol=1e-6)


@pytest.mark.parametrize("limit", [(0.0, 1e-3), (1e-4, None)])
def test_a_time_step_limit_that_clamps_is_refused(limit):
    """The mixer clamps no step: a configuration whose limit would clamp
    a softplus is refused by name, and the published ones build."""
    with pytest.raises(ValueError, match="time_step_limit"):
        nemotron_h_lm(CONFIG, time_step_limit=limit, experts_held=(5, 6))
    for published in ((0.0, None), (0.0, float("inf"))):
        nemotron_h_lm(CONFIG, time_step_limit=published,
                      experts_held=(5, 6))


# ------------------------------------------------ the short convolution

PARTS = (128, 128)


def _conv_inputs(seed, t=18, rest=8):
    rng = _rng(seed)
    c = sum(PARTS)
    return (_array(rng, 2, t, c + rest), _array(rng, c, 4, scale=0.5),
            _array(rng, c, scale=0.5))


def test_the_convolution_takes_a_bias_in_both_forms():
    """The bias inside the SiLU: the ``jax.numpy`` form against the
    kernels in interpret mode, results and the gradients of input,
    weight and bias."""
    x, w, b = _conv_inputs(6)

    def total(fn):
        return lambda x, w, b: sum(jnp.sum(jnp.sin(o)) for o in fn(x, w, b))

    def by_kernels(x, w, b):
        return conv_silu_kernels.causal_conv_silu_kernels(
            x, w, PARTS, interpret=True, bias=b)

    def by_jnp(x, w, b):
        return linear_attention.causal_conv_silu(x, w, PARTS, b)

    for a, c in zip(by_kernels(x, w, b), by_jnp(x, w, b)):
        assert _rel(a, c) <= 2e-6
    want = by_jnp(x, w, b)[0]
    pre = linear_attention.causal_conv1d(x[..., :128], w[:128]) + b[:128]
    _close(want, jax.nn.silu(pre))
    got = jax.jit(jax.grad(total(by_kernels), argnums=(0, 1, 2)))(x, w, b)
    ref_grads = jax.jit(jax.grad(total(by_jnp), argnums=(0, 1, 2)))(x, w, b)
    for g, r in zip(got, ref_grads):
        assert _rel(g, r) <= 2e-6


def test_no_bias_is_the_convolution_as_it_was():
    """``bias=None`` computes what the function without a bias computed:
    the ``jax.numpy`` form bit for bit, the kernels built without the
    bias's row (the same cached builds), and a zero bias the same."""
    x, w, _ = _conv_inputs(7, rest=0)
    plain = linear_attention.causal_conv_silu(x, w, PARTS)
    before = jax.nn.silu(linear_attention.causal_conv1d(x, w))
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(plain[:2], -1)),
                                  np.asarray(before))
    for a, c in zip(linear_attention.causal_conv_silu(x, w, PARTS, None),
                    plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    conv_silu_kernels._build.cache_clear()
    kernels = conv_silu_kernels.causal_conv_silu_kernels(x, w, PARTS,
                                                         interpret=True)
    built = conv_silu_kernels._build.cache_info().currsize
    keys = conv_silu_kernels._build.cache_info()
    assert built == len(PARTS) and keys.misses == len(PARTS)
    zero = conv_silu_kernels.causal_conv_silu_kernels(
        x, w, PARTS, interpret=True, bias=jnp.zeros(sum(PARTS)))
    for a, c, d in zip(kernels, zero, plain):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
        assert _rel(a, d) <= 2e-6
    with pytest.raises(ValueError, match="bias"):
        linear_attention.causal_conv_silu(x, w, PARTS, jnp.zeros(3))


def test_the_registered_op_takes_the_bias_as_an_array():
    x, w, b = _conv_inputs(8, t=12, rest=3)
    outs = mx.nd.causal_conv_silu(mx.nd.array(x), mx.nd.array(w),
                                  mx.nd.array(b), parts=PARTS)
    for a, c in zip(outs, linear_attention.causal_conv_silu(x, w, PARTS, b)):
        np.testing.assert_allclose(a.asnumpy(), np.asarray(c), rtol=1e-6,
                                   atol=1e-6)


# ------------------------------------------- the ungated experts, relu^2

def _moe_weights(rng, experts=16, d=32, inner=16, shared=24):
    return {"router_w": _array(rng, experts, d, scale=0.3),
            "expert_bias": _array(rng, experts, scale=0.1),
            "up": _array(rng, experts, d, inner, scale=0.3),
            "down": _array(rng, experts, inner, d, scale=0.3),
            "shared_up_w": _array(rng, shared, d, scale=0.3),
            "shared_down_w": _array(rng, d, shared, scale=0.3)}


def _moe_block(w, first, count, shared=True):
    blk = contrib_nn.SparseMoE(
        32, 16, 16, 3, experts_held=(first, count),
        shared_hidden=24 if shared else 0, score_func="sigmoid",
        route_scale=2.5, expert_bias=True, shared_gate=False,
        activation="relu2")
    blk.initialize(mx.initializer.Xavier())
    blk.router_weight.set_data(mx.nd.array(w["router_w"]))
    blk.expert_bias.set_data(mx.nd.array(w["expert_bias"]))
    blk.experts_up_weight.set_data(mx.nd.array(w["up"][first:first + count]))
    blk.experts_down_weight.set_data(
        mx.nd.array(w["down"][first:first + count]))
    if shared:
        blk.shared.up.weight.set_data(mx.nd.array(w["shared_up_w"]))
        blk.shared.down.weight.set_data(mx.nd.array(w["shared_down_w"]))
    return blk


def test_ungated_experts_are_the_loop_with_their_gradients():
    """``moe_experts(activation='relu2')`` on 6 held experts from the
    fifth: the result and the gradients of input, weights, up and down
    against a loop over the experts."""
    rng = _rng(20)
    w = _moe_weights(rng)
    x = _array(rng, 40, 32)
    sizes = dict(SIZES, first_expert=5)
    weights, experts = moe_ops.moe_router(
        x, w["router_w"], w["expert_bias"], top_k=3, score_func="sigmoid",
        scale=2.5)
    held = (w["up"][5:11], w["down"][5:11])

    def program(x, weights, up, down):
        out, counts = moe_ops.moe_experts(x, weights, experts, up, down,
                                          jnp.zeros(7), first_expert=5,
                                          activation="relu2")
        return out, counts

    def loop(x, weights, up, down):
        y = jnp.zeros_like(x)
        for e in range(6):
            w_e = jnp.sum(jnp.where(experts == 5 + e, weights, 0.0),
                          axis=-1, keepdims=True)
            y = y + w_e * (ref.relu2(x @ up[e]) @ down[e])
        return y

    with jax.default_matmul_precision("highest"):
        out, counts = jax.jit(program)(x, weights, *held)
        _close(out, jax.jit(loop)(x, weights, *held))
        _close(out, jax.jit(lambda t: ref.routed(x, t, sizes))(
            dict(w, up=w["up"][5:11], down=w["down"][5:11])))
        got = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(program(*a)[0])),
                               argnums=(0, 1, 2, 3)))(x, weights, *held)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.sin(loop(*a))),
                                argnums=(0, 1, 2, 3)))(x, weights, *held)
    for g, r in zip(got, want):
        assert _rel(g, r) <= 1e-5
    chosen = np.asarray(experts)
    assert np.asarray(counts)[:6].tolist() == [
        int(np.sum(chosen == 5 + e)) for e in range(6)]
    with pytest.raises(ValueError, match="activation"):
        moe_ops.moe_experts(x, weights, experts, *held, jnp.zeros(7),
                            activation="gelu")


def test_the_ungated_layer_is_the_reference_layer():
    """``SparseMoE(activation='relu2')``: the routed experts and the
    shared one, result and every gradient, against the reference."""
    rng = _rng(21)
    w = _moe_weights(rng)
    x = _array(rng, 2, 33, 32)
    sizes = dict(SIZES, first_expert=5)
    blk = _moe_block(w, 5, 6)
    assert not hasattr(blk, "experts_gate_up_weight")
    assert type(blk.shared).__name__ == "SquaredReLUMLP"
    held = dict(w, up=w["up"][5:11], down=w["down"][5:11])
    fwd = parallel.functional_call(blk, train=True)
    params, aux = parallel.param_arrays(blk), parallel.aux_arrays(blk)
    names = {"up": blk.experts_up_weight.name,
             "down": blk.experts_down_weight.name,
             "router_w": blk.router_weight.name,
             "shared_up_w": blk.shared.up.weight.name,
             "shared_down_w": blk.shared.down.weight.name}
    with jax.default_matmul_precision("highest"):
        got, _ = jax.jit(fwd)(params, aux, x)
        _close(got, jax.jit(lambda t: ref.moe(x, t, sizes))(held))
        g_prog = jax.jit(jax.grad(
            lambda p: jnp.sum(jnp.sin(fwd(p, aux, x)[0]))))(params)
        g_ref = jax.jit(jax.grad(
            lambda t: jnp.sum(jnp.sin(ref.moe(x, t, sizes)))))(held)
    for key, name in names.items():
        assert _rel(g_prog[name], g_ref[key]) <= 1e-4, key


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four devices of an expert-parallel group, four experts each: the
    routed parts they give, with the shared expert (which every device
    computes alike) counted once, are the whole layer's result; and
    every token's three choices are computed by exactly one share."""
    w = _moe_weights(_rng(10))
    x = _array(_rng(11), 2, 33, 32)
    sizes = dict(SIZES, first_expert=0)

    @jax.jit
    def shares(w):
        weights, experts = moe_ops.moe_router(
            x, w["router_w"], w["expert_bias"], top_k=3,
            score_func="sigmoid", scale=2.5)
        return [moe_ops.moe_experts(
            x, weights, experts, w["up"][first:first + 4],
            w["down"][first:first + 4], jnp.zeros(5), first_expert=first,
            activation="relu2") for first in (0, 4, 8, 12)]

    with jax.default_matmul_precision("highest"):
        parts = shares(w)
        whole = jax.jit(lambda t: ref.moe(x, t, sizes))(w)
        shared = jax.jit(ref.mlp)(x, w["shared_up_w"], w["shared_down_w"])
    _close(sum(out for out, _ in parts) + shared, whole)
    assert sum(float(counts[:4].sum()) for _, counts in parts) == 2 * 33 * 3


# ------------------------------------------------ the tower, through the step

def _build(seed=7, **over):
    mx.random.seed(seed)
    built = nemotron_h_lm(CONFIG, n_routed_experts=16, experts_held=(5, 6),
                          **over)
    built.initialize(mx.initializer.Xavier())
    rng = _rng(14)
    for name, p in built.collect_params().items():
        if name.endswith(("norm_weight",)) and "mamba" not in name:
            p.set_data(mx.nd.array(1 + _array(rng, *p.shape, scale=0.2)))
        if name.endswith("expert_bias"):    # a bias that moves choices
            p.set_data(mx.nd.array(_array(rng, *p.shape, scale=0.1)))
    return built


@pytest.fixture(scope="module")
def net():
    return _build()


def _batch(seed=15):
    rng = _rng(seed)
    return (jnp.asarray(rng.integers(0, 97, (2, T)), jnp.int32),
            jnp.asarray(rng.integers(0, 97, (2, T)), jnp.int32))


LR = 1024.0     # a step that moves dt_bias (about -5) by far more than
                # its last place, so that the move gives back its gradient


def _step_gradients(net, x, y):
    """(first-step loss, {parameter: gradient}) of one float32 step of
    ``ShardedTrainer`` under ``capture``: plain SGD at learning rate
    ``LR`` moves a parameter by ``LR`` times its gradient."""
    from mxnet_tpu import capture

    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": LR}, mesh=mesh, dtype="float32",
        param_rules=parallel.SpecLayout.for_mesh(mesh).param_rules())
    before = {k: np.asarray(v, np.float64) for k, v in trainer.params.items()}
    loss = float(capture.capture(trainer)(x, y))
    return loss, {k: ((before[k] - np.asarray(v, np.float64)) / LR).astype(
        np.float32) for k, v in trainer.params.items()}


def _gradient_tree(net, grads):
    """The program's gradients, laid into the reference's tree."""
    kept = {name: p.data().data_ for name, p in net.collect_params().items()}
    try:
        for name, grad in grads.items():
            net.collect_params()[name].set_data(mx.nd.array(grad))
        return jax.tree_util.tree_map(np.asarray,
                                      model.reference_weights(net))
    finally:
        for name, value in kept.items():
            net.collect_params()[name].set_data(mx.nd.array(value))


def test_the_tower_through_the_trainer_matches_the_reference(net):
    """Seven layers EMEMEM* through ``ShardedTrainer`` in float32: the
    logits at seeded positions, the first-step loss and the gradient of
    every parameter (each within 2e-3 of its largest entry)."""
    x, y = _batch()
    positions = jnp.asarray(_rng(16).integers(0, T, (2, 9)), jnp.int32)
    tree = model.reference_weights(net)

    def reference(t):
        loss, logits, _ = ref.check_outputs(t, x, y, positions, SIZES)
        return loss, logits

    (want_loss, want_logits), want = jax.jit(jax.value_and_grad(
        reference, has_aux=True))(tree)

    fwd = parallel.functional_call(net, train=True)
    params, aux = parallel.param_arrays(net), parallel.aux_arrays(net)
    logits, _ = jax.jit(fwd)(params, aux, x)
    logits = jnp.take_along_axis(logits, positions[:, :, None], axis=1)
    assert np.abs(np.asarray(logits - want_logits)).max() <= 1e-4
    loss, grads = _step_gradients(net, x, y)
    assert abs(loss - float(want_loss)) <= 1e-5
    got = _gradient_tree(net, grads)
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    off = []
    for (path, g), w in zip(flat_got, flat_want):
        w = np.asarray(w)
        if "expert_bias" in str(path):
            # a state, not a parameter: the trainer holds no gradient
            # for it, and the reference's is zero
            assert not w.any()
            continue
        assert np.abs(w).max() > 0, path            # every kind trains
        err = np.abs(g - w).max() / np.abs(w).max()
        if err > 2e-3:
            off.append(f"{jax.tree_util.keystr(path)} {err}")
    assert off == []


def test_the_layers_sit_where_the_pattern_says(net):
    assert [blk.kind for blk in net.blocks] == CONFIG["layer_types"]
    kinds = [type(blk.mixer).__name__ for blk in net.blocks]
    assert kinds == ["SparseMoE", "Mamba2Mixer"] * 3 \
        + ["GroupedQueryAttention"]
    names = list(net.collect_params())
    assert not [n for n in names if n.endswith("bias")
                and not n.endswith(("expert_bias", "conv_bias",
                                    "dt_bias"))]
    assert sum(n.endswith("moe_experts_up_weight") for n in names) == 3
    assert net.head.weight.shape == (97, 32)
    whole = nemotron_h_lm(CONFIG, layer_types=None, n_routed_experts=16)
    pattern = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    assert [b.kind for b in whole.blocks] == list(layer_types_of(pattern))
    assert len(whole.blocks) == 52
    assert [b.kind for b in whole.blocks][6:13] == CONFIG["layer_types"]
    with pytest.raises(ValueError, match="layer_types"):
        nemotron_h_lm(CONFIG, layer_types=["linear_attention"])
    with pytest.raises(ValueError, match="relu2"):
        nemotron_h_lm(CONFIG, mlp_hidden_act="silu")


def test_the_trainer_takes_the_tower_as_it_takes_any_other():
    """ShardedTrainer + capture, every layer under contrib.nn.Remat, the
    bf16 policy: one captured executable, a finite loss that falls, the
    experts' counts moved by the step, and the Mamba-2 layers' core
    under its own scope ``ssm``, the projections outside it."""
    from mxnet_tpu import capture
    from mxnet_tpu.observability import perf

    perf.clear()
    built = _build(seed=8, remat=True)
    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        built, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=mesh, dtype="bfloat16",
        param_rules=parallel.SpecLayout.for_mesh(mesh).param_rules())
    step = capture.capture(trainer)
    x, y = _batch(17)
    before = capture.stats()
    losses = [float(step(x, y)) for _ in range(4)]
    after = capture.stats()
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert after["capture_fallback_eager"] == before["capture_fallback_eager"]
    counts = [np.asarray(v) for k, v in sorted(trainer.aux.items())
              if k.endswith("moe_expert_tokens")]
    assert len(counts) == 3
    for c in counts:
        assert 0 < c[:6].sum() <= 2 * T * 3 and c[6] < 2 * T
    key = next(k for k, e in perf.ledger().items()
               if e["label"] == "sharded_step")
    ops = [n["op_name"] for n in perf.op_names(key).values() if n["op_name"]]
    for scope in ("ssm", "attention", "moe", "moe_experts"):
        inside = [o for o in ops if f"/{scope}/" in o]
        assert any("transpose(" in o for o in inside), scope
        assert any("transpose(" not in o for o in inside), scope
    assert not [o for o in ops if "/ssm/" in o
                and ("mamba_in/" in o or "mamba_out/" in o)]


def test_the_layout_has_a_rule_for_every_matrix_of_the_model(net):
    import re

    rules = [re.compile(p) for p, _ in parallel.SpecLayout().param_rules()]
    unmatched = [n for n, p in net.collect_params().items()
                 if len(p.shape) > 1 and p.grad_req != "null"
                 and not any(r.match(n) for r in rules)]
    assert all(n.endswith(("moe_router_weight", "mamba_conv_weight"))
               for n in unmatched)


# ---------------------------------------------- the cell's own arithmetic

def _cell_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron-twotower-30b-a3b.json")) as f:
        return json.load(f)


def test_the_cut_and_the_flops_of_a_token():
    """528.1 M parameters held (the configuration's own count), the
    matrix parameters a token touches, and the FLOPs of a token: the
    scan at its least (3 x 64 x 128 multiply-adds a head a token, x 3
    for forward and backward)."""
    config = _cell_config()
    assert config["layer_types"] == list(
        layer_types_of(config["hybrid_override_pattern"]))[6:13]
    assert round(model.matmul_params(config) / 1e6, 1) == 255.7
    flops = model.flops_per_item(config, {"seq_len": 8192})
    scan = 3 * 6 * 3 * 64 * 64 * 128
    attention = 3 * 2 * 2 * 32 * 128 * 8193 / 2
    assert flops == pytest.approx(6 * model.matmul_params(config)
                                  + scan + attention)
    assert round(flops / 1e9, 3) == 1.764


def test_the_ssm_readers_join_their_scope_and_count_least_work():
    """``benchmarks/ssm_scope.py``: an instruction counts by the scope in
    its name (a fusion's by the name inside it), in its phase; and the
    roofline's least time by the configuration's widths."""
    names = {"a": {"op_name": "jit(step)/jvp(net)/blk/ssm/while/body/dot",
                   "called": []},
             "b": {"op_name": "jit(step)/transpose(jvp(net))/blk/ssm/add",
                   "called": []},
             "c": {"op_name": "jit(step)/jvp(net)/blk/mamba_in/dot",
                   "called": []},
             "d": {"op_name": "", "called": [
                 "jit(step)/jvp(net)/blk/ssm/exp"]}}
    ops = [(0, "a", "", 2e6), (1, "b", "", 6e6), (2, "c", "", 5e6),
           (3, "d", "", 1e6), (4, "a", "", 2e6)]
    found, top = ssm_scope.by_phase(ops, names, 2)
    assert found == {"forward": 2.5, "backward": 3.0}
    assert top["forward"][0] == ("body/dot", 2.0)
    config = _cell_config()
    config["train"] = {"compute_dtype": "bfloat16"}
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = ssm_scope.least_ms(config, {"batch": 1, "seq_len": 8192},
                                      peaks)
    # a layer: 8192 x (2 x 6144 + 4096 + 2048 + 64 + 4096 + 2 x 4096)
    # x 2 bytes = 504.4 MB at 819 GB/s, against 25.8 GFLOP at 197 TF/s
    moved = 8192 * (2 * 6144 + 4096 + 2048 + 64 + 4096 + 2 * 4096) * 2
    assert bound == "memory"
    assert least == pytest.approx(3 * moved / 819e9 * 1e3)
    assert ssm_scope.mamba_layers(config) == 3
