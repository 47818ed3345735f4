"""Trinity's blocks and the whole model against the plain float32
reference (``benchmarks/references/trinity.py``, which imports nothing
of the program), at small sizes on the CPU, seeded weights: the model
through ``ShardedTrainer`` (logits, first-step loss, the gradient of
every parameter; float32 policy tight, bf16 policy at a stated
tolerance), the expert layer's shares adding up to the uncut layer, the
sigmoid router with its bias, a layer whose window is ignored caught by
the first comparison's tolerance, and the layers placed where the
configuration says."""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel
from mxnet_tpu.gluon.contrib import nn as contrib_nn
from mxnet_tpu.gluon.model_zoo import trinity_lm
from mxnet_tpu.ops import moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_t_{kind}_{name}", os.path.join(ROOT, "benchmarks", kind,
                                          name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "trinity")
model = _load("models", "trinity")

# the window (24) is shorter than the sequence (70): most queries lose
# keys to it; 6 of 16 experts held, from the fifth on
CONFIG = dict(
    vocab_size=97, hidden_size=32, num_layers=5, num_dense_layers=1,
    layer_types=["sliding_attention"] * 4 + ["full_attention"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window=24, rope_theta=10000, intermediate_size=48,
    moe_intermediate_size=16, num_experts=6, num_experts_per_tok=3,
    num_shared_experts=1, score_func="sigmoid", route_norm=True,
    route_scale=2.826, mup_enabled=True, rms_norm_eps=1e-5,
    published={"num_experts": 16}, deployment={"first_expert": 5},
    model_type="afmoe", load_balance_coeff=0.001, num_hidden_layers=32)
SIZES = model.reference_sizes(CONFIG)
TOL = dict(rtol=2e-4, atol=2e-5)
T = 70


def _rng(seed=0):
    return np.random.default_rng(seed)


def _array(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def _close(got, want, **tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**TOL, **tol})


def _build(seed=7, **over):
    mx.random.seed(seed)
    built = trinity_lm(CONFIG, num_experts=16, experts_held=(5, 6), **over)
    built.initialize(mx.initializer.Xavier())
    rng = _rng(14)
    for name, p in built.collect_params().items():
        if name.endswith(("norm1_weight", "norm2_weight", "norm3_weight",
                          "norm4_weight", "qnorm_weight", "knorm_weight",
                          "lm0_norm_weight")):         # norms off one
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


def _trainer(net, dtype):
    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    return parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 1.0}, mesh=mesh, dtype=dtype,
        param_rules=parallel.SpecLayout.for_mesh(mesh).param_rules())


def _step_gradients(net, dtype, x, y):
    """(first-step loss, {parameter: gradient}) of one step of
    ``ShardedTrainer`` under ``capture``: with plain SGD at learning rate
    1 and float32 masters, the step moves a parameter by its gradient."""
    from mxnet_tpu import capture

    trainer = _trainer(net, dtype)
    before = {k: np.asarray(v, np.float32)
              for k, v in trainer.params.items()}
    loss = float(capture.capture(trainer)(x, y))
    return loss, {k: before[k] - np.asarray(v, np.float32)
                  for k, v in trainer.params.items()}


def _gradient_tree(net, grads):
    """The program's gradients, laid into the reference's tree."""
    kept = {name: p.data().data_          # the arrays: set_data writes
            for name, p in net.collect_params().items()}    # in place
    try:
        for name, grad in grads.items():
            net.collect_params()[name].set_data(mx.nd.array(grad))
        return jax.tree_util.tree_map(np.asarray,
                                      model.reference_weights(net))
    finally:
        for name, value in kept.items():
            net.collect_params()[name].set_data(mx.nd.array(value))


def _largest(a):
    return np.abs(a).max()


def _rms(a):
    return np.sqrt(np.mean(np.square(a, dtype=np.float64)))


def _compare(net, dtype, logit_atol, loss_atol, grad_tol, sizes=SIZES,
             measure=_largest):
    """The model through the trainer against the reference; returns the
    list of what is outside the tolerances: the logits' difference by
    ``measure``, the loss, and each gradient's difference by ``measure``
    as a share of the gradient's own."""
    x, y = _batch()
    positions = jnp.asarray(_rng(16).integers(0, T, (2, 9)), jnp.int32)
    tree = model.reference_weights(net)
    want_loss, want_logits, _ = jax.jit(lambda t: ref.check_outputs(
        t, x, y, positions, sizes))(tree)
    want = jax.jit(jax.grad(lambda t: ref.loss(t, x, y, sizes)))(tree)
    want.pop("sizes", None)

    fwd = parallel.functional_call(net, train=True)
    params, aux = parallel.param_arrays(net), parallel.aux_arrays(net)
    if dtype != "float32":
        params = {k: v.astype(dtype) for k, v in params.items()}
    logits, _ = jax.jit(fwd)(params, aux, x)
    logits = jnp.take_along_axis(logits.astype(jnp.float32),
                                 positions[:, :, None], axis=1)
    loss, grads = _step_gradients(net, dtype, x, y)
    got = _gradient_tree(net, grads)

    off = []
    worst = float(measure(np.asarray(logits - want_logits)))
    if worst > logit_atol:
        off.append(f"logits {worst}")
    if abs(loss - float(want_loss)) > loss_atol:
        off.append(f"loss {loss} vs {float(want_loss)}")
    flat_got = jax.tree_util.tree_leaves_with_path(got)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        w = np.asarray(w)
        if "expert_bias" in str(path):
            # a state, not a parameter: the trainer holds no gradient
            # for it, and the reference's is zero
            assert not w.any()
            continue
        assert np.abs(w).max() > 0, path            # every kind trains
        err = measure(g - w) / measure(w)
        if err > grad_tol:
            off.append(f"gradient {jax.tree_util.keystr(path)} {err}")
    return off


# -------------------------------------------- (a) the model, both policies

def test_the_model_through_the_trainer_matches_the_reference_float32(net):
    assert _compare(net, "float32", 1e-4, 1e-5, 2e-3) == []


def test_the_model_through_the_trainer_matches_the_reference_bf16():
    """The bf16 policy, at three times what it measures here: logits of
    size 2 within 0.2 (0.068), the loss within 0.003 (0.00095), every
    gradient within 0.2 of its largest entry (0.044-0.070). Every
    token's choice of experts is pinned by the bias (experts 5, 7 and
    12, two of them held; the weights still come from the scores): as
    the weights are, bf16 turns the third and fourth expert of one token
    in ten around, and each such token moves every gradient behind it
    (0.2-0.6 of the gradient's size at 140 tokens), which is routing and
    not precision. The float32 comparison above has the free choice."""
    pinned = _build()
    for name, p in pinned.collect_params().items():
        if name.endswith("expert_bias"):
            bias = np.zeros(16, np.float32)
            bias[[5, 7, 12]] = 4.0
            p.set_data(mx.nd.array(bias))
    assert _compare(pinned, "bfloat16", 0.2, 0.003, 0.2) == []


# ------------------------------------------ (d) a window ignored is caught

def test_a_layer_that_ignores_its_window_is_caught(net):
    """The same weights in a model whose first window layer sees every
    earlier key: the float32 comparison's own tolerances refuse it, by
    the logits and by the gradients."""
    blind = _build()
    blind.blocks[0].attn._window = None
    off = _compare(blind, "float32", 1e-4, 1e-5, 2e-3)
    assert any(o.startswith("logits") for o in off)
    assert any(o.startswith("gradient") for o in off)


# ----------------------------------------------------- (b) the shares add up

def _moe_weights(rng, experts=16, d=32, inner=16):
    return {"router_w": _array(rng, experts, d, scale=0.3),
            "expert_bias": _array(rng, experts, scale=0.1),
            "gate_up": _array(rng, experts, d, 2 * inner, scale=0.2),
            "down": _array(rng, experts, inner, d, scale=0.2),
            "shared_gate_up_w": _array(rng, 2 * inner, d, scale=0.2),
            "shared_down_w": _array(rng, d, inner, scale=0.2)}


def _moe_block(w, first, count, shared=True):
    blk = contrib_nn.SparseMoE(
        32, 16, 16, 3, experts_held=(first, count),
        shared_hidden=16 if shared else 0, score_func="sigmoid",
        route_scale=2.826, expert_bias=True, shared_gate=False)
    blk.initialize(mx.initializer.Xavier())
    blk.router_weight.set_data(mx.nd.array(w["router_w"]))
    blk.expert_bias.set_data(mx.nd.array(w["expert_bias"]))
    blk.experts_gate_up_weight.set_data(
        mx.nd.array(w["gate_up"][first:first + count]))
    blk.experts_down_weight.set_data(
        mx.nd.array(w["down"][first:first + count]))
    if shared:
        blk.shared.gate_up.weight.set_data(mx.nd.array(w["shared_gate_up_w"]))
        blk.shared.down.weight.set_data(mx.nd.array(w["shared_down_w"]))
    return blk


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four devices of an expert-parallel group, four experts each: the
    routed parts they give, with the shared expert (which every device
    computes alike) counted once, are the whole layer's result."""
    w = _moe_weights(_rng(10))
    x = _array(_rng(11), 2, 33, 32)
    sizes = dict(SIZES, first_expert=0)
    parts = [_moe_block(w, first, 4, shared=False)(mx.nd.array(x)).data_
             for first in (0, 4, 8, 12)]
    whole = ref.moe(x, w, sizes)
    shared = ref.gated_mlp(x, w["shared_gate_up_w"], w["shared_down_w"])
    _close(sum(parts) + shared, whole)
    # and the program's own uncut layer says the same
    _close(_moe_block(w, 0, 16)(mx.nd.array(x)).data_, whole)
    # every token's three choices are computed by exactly one share
    counted = 0
    for first in (0, 4, 8, 12):
        blk = _moe_block(w, first, 4, shared=False)
        blk(mx.nd.array(x))
        counted += blk.expert_tokens.data().asnumpy()[:4].sum()
    assert counted == 2 * 33 * 3


def test_rows_no_expert_owns_give_no_gradient_whatever_they_hold(monkeypatch):
    """On the chip the grouped product leaves the rows past the
    assignments held as the buffer was, NaN bit patterns at times (PR 34:
    Trinity-Mini's twentieth step). Selected away in the forward pass,
    such a row must not reach a weight's gradient as NaN x 0 either."""
    rng = _rng(30)
    x = _array(rng, 40, 32)
    w = _moe_weights(rng)
    weights, experts = moe_ops.moe_router(x, w["router_w"], top_k=3,
                                          score_func="sigmoid")
    held = (w["gate_up"][5:11], w["down"][5:11])

    def total(x, weights, gate_up, down):
        out, _ = moe_ops.moe_experts(x, weights, experts, gate_up, down,
                                     jnp.zeros(7), first_expert=5)
        return jnp.sum(jnp.sin(out))

    grad = jax.grad(total, argnums=(0, 1, 2, 3))
    want = grad(x, weights, *held)
    real = jax.lax.ragged_dot

    def undefined_past_the_groups(lhs, rhs, group_sizes, **kwargs):
        out = real(lhs, rhs, group_sizes, **kwargs)
        owned = jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(owned[:, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", undefined_past_the_groups)
    got = grad(x, weights, *held)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        _close(a, b)


# ------------------------------------------------------------ (c) the router

def test_the_bias_moves_the_choice_and_not_the_weights():
    rng = _rng(20)
    x, w = _array(rng, 50, 32), _array(rng, 16, 32, scale=0.3)
    route = dict(top_k=3, score_func="sigmoid", renormalize=False)
    plain_w, plain_e = moe_ops.moe_router(x, w, **route)
    scores = jax.nn.sigmoid(x @ w.T)
    _close(plain_w, jnp.take_along_axis(scores, plain_e, axis=-1))
    assert plain_w.dtype == jnp.float32 and plain_e.dtype == jnp.int32
    # a large bias on expert 11 puts it among every token's three ...
    bias = jnp.zeros(16).at[11].set(10.0)
    moved_w, moved_e = moe_ops.moe_router(x, w, bias, **route)
    assert bool(jnp.all(jnp.any(moved_e == 11, axis=-1)))
    assert not bool(jnp.all(jnp.any(plain_e == 11, axis=-1)))
    # ... at the weight its score gives, without the bias
    _close(moved_w, jnp.take_along_axis(scores, moved_e, axis=-1))
    # and the bias takes no gradient, the router's matrix does
    g_bias, g_w = jax.grad(
        lambda b, m: jnp.sum(moe_ops.moe_router(x, m, b, **route)[0]),
        argnums=(0, 1))(bias, w)
    assert not np.asarray(g_bias).any() and np.asarray(g_w).any()


def test_route_norm_and_route_scale():
    rng = _rng(21)
    x, w = _array(rng, 40, 32), _array(rng, 16, 32, scale=0.3)
    raw, chosen = moe_ops.moe_router(x, w, top_k=3, score_func="sigmoid",
                                     renormalize=False)
    normed, same = moe_ops.moe_router(x, w, top_k=3, score_func="sigmoid",
                                      renormalize=True, scale=2.826)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(same))
    _close(normed, 2.826 * raw / jnp.sum(raw, axis=-1, keepdims=True))
    _close(jnp.sum(normed, axis=-1), jnp.full(40, 2.826))
    # the scores are float32 whatever the input is
    low, _ = moe_ops.moe_router(x.astype(jnp.bfloat16),
                                w.astype(jnp.bfloat16), top_k=3,
                                score_func="sigmoid")
    assert low.dtype == jnp.float32
    # and they match the reference's router
    sizes = dict(SIZES, route_norm=True)
    want_w, want_e = ref.route(x, {"router_w": w,
                                   "expert_bias": jnp.zeros(16)}, sizes)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(want_e))
    _close(normed, want_w)
    with pytest.raises(ValueError, match="score_func"):
        moe_ops.moe_router(x, w, score_func="tanh")


# ------------------------------------------------- (e) where the layers sit

def test_the_layers_sit_where_the_configuration_says(net):
    windows = [blk.attn._window for blk in net.blocks]
    assert windows == [24, 24, 24, 24, None]
    rotary = [blk.attn._rotary["rotary_dim"] for blk in net.blocks]
    assert rotary == [16, 16, 16, 16, 0]        # full layers: no positions
    kinds = [type(blk.mlp).__name__ for blk in net.blocks]
    assert kinds == ["GatedMLP"] + ["SparseMoE"] * 4
    assert net.blocks[0].mlp.gate_up.weight.shape == (2 * 48, 32)
    assert net.head.bias is None and net.head.weight.shape == (97, 32)
    names = list(net.collect_params())
    assert not [n for n in names if "pos" in n or n.endswith("bias")
                and not n.endswith("expert_bias")]
    assert sum(n.endswith("moe_expert_tokens") for n in names) == 4
    assert sum(n.endswith("moe_expert_bias") for n in names) == 4
    assert sum(n.endswith("attn_gate_weight") for n in names) == 5
    # the published 32 layers, two dense: the pattern as the config has it
    whole = trinity_lm(
        CONFIG, num_experts=16, num_dense_layers=2,
        layer_types=(["sliding_attention"] * 3 + ["full_attention"]) * 8)
    assert [type(b.mlp).__name__ for b in whole.blocks] \
        == ["GatedMLP"] * 2 + ["SparseMoE"] * 30
    assert [b.attn._window for b in whole.blocks] == [24, 24, 24, None] * 8
    with pytest.raises(ValueError, match="layer_types"):
        trinity_lm(CONFIG, layer_types=["linear_attention"])


def test_the_trainer_takes_the_model_as_it_takes_any_other():
    """ShardedTrainer + capture, every half-layer under
    contrib.nn.Remat, the bf16 policy: one captured executable, a finite
    loss that falls, the experts' counts moved by the step, and the
    window layers' core under its own scope inside ``attention``."""
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
    assert len(counts) == 4
    for c in counts:
        assert 0 < c[:6].sum() <= 2 * T * 3 and c[6] < 2 * T
    key = next(k for k, e in perf.ledger().items()
               if e["label"] == "sharded_step")
    ops = [n["op_name"] for n in perf.op_names(key).values() if n["op_name"]]
    for scope in ("attention", "window_attention", "moe", "moe_router",
                  "moe_experts"):
        inside = [o for o in ops if f"/{scope}/" in o]
        assert any("transpose(" in o for o in inside), scope
        assert any("transpose(" not in o for o in inside), scope
    assert all("/attention/" in o for o in ops if "/window_attention/" in o)
    # the full layer's core is under ``attention`` alone
    assert [o for o in ops if "/attention/" in o
            and "/window_attention/" not in o]
    assert not [o for o in ops if "/attention/" in o
                and ("_q/" in o or "_gate/" in o or "_out/" in o)]


def test_the_layout_has_a_rule_for_every_matrix_of_the_model(net):
    import re

    rules = [re.compile(p) for p, _ in parallel.SpecLayout().param_rules()]
    unmatched = [n for n, p in net.collect_params().items()
                 if len(p.shape) > 1 and p.grad_req != "null"
                 and not any(r.match(n) for r in rules)]
    assert all(n.endswith("moe_router_weight") for n in unmatched)


def test_the_flops_of_a_token_count_the_keys_a_query_sees():
    import json

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "trinity-mini.json")) as f:
        config = json.load(f)
    assert round(model.matmul_params(config) / 1e6, 1) == 264.1
    assert model.seen_pairs(8192, 2048) == 2048 * 8192 - 2048 * 2047 / 2
    assert model.seen_pairs(8192) == model.seen_pairs(8192, 8192) \
        == 8192 * 8193 / 2
    flops = model.flops_per_item(config, {"seq_len": 8192})
    assert round(flops / 1e9, 2) == 2.14
    # a mask alone would cost the four window layers the full layer's keys
    every = dict(config, sliding_window=8192)
    assert round((model.flops_per_item(every, {"seq_len": 8192}) - flops)
                 / 1e9, 2) == 0.45
