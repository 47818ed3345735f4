"""Qwen3-Next's blocks and the whole model against the plain float32
reference (``benchmarks/references/qwen3_next.py``, which imports
nothing of the program), at small sizes on the CPU in float32, seeded
weights: forward passes and gradients; the chunked delta rule against
the token recurrence at a length that is not a multiple of the chunk;
rotary positions on part of a head; K/V heads shared between query
heads; the expert layer's shares adding up to the uncut layer; routing
so skewed that every token, or none, lands on the experts held."""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import parallel, remat
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.contrib import nn as contrib_nn
from mxnet_tpu.gluon.model_zoo import qwen3_next_lm
from mxnet_tpu.ops import linear_attention, moe as moe_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    spec = importlib.util.spec_from_file_location(
        f"_t_{kind}_{name}", os.path.join(ROOT, "benchmarks", kind,
                                          name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("references", "qwen3_next")
model = _load("models", "qwen3_next")

CONFIG = dict(
    vocab_size=97, hidden_size=32, num_layers=4, full_attention_interval=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    partial_rotary_factor=0.25, rope_theta=10000000, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_experts=6, num_experts_per_tok=3,
    moe_intermediate_size=16, shared_expert_intermediate_size=16,
    norm_topk_prob=True, rms_norm_eps=1e-6, published={"num_experts": 16},
    deployment={"first_expert": 5}, model_type="qwen3_next")
SIZES = model.reference_sizes(CONFIG)
TOL = dict(rtol=2e-4, atol=2e-5)


def _rng(seed=0):
    return np.random.default_rng(seed)


def _array(rng, *shape, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)


def _close(got, want, **tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **{**TOL, **tol})


def _grad(fn, **kwargs):
    """Gradients from one compiled program, not op by op."""
    return jax.jit(jax.grad(fn, **kwargs))


def _call(block, *inputs):
    """(params -> output) of a gluon block as a pure function, and the
    block's parameters."""
    fwd = parallel.functional_call(block, train=True)
    aux = parallel.aux_arrays(block)
    return (jax.jit(lambda params: fwd(params, aux, *inputs)[0]),
            parallel.param_arrays(block))


# ------------------------------------------------------------------ norms

def test_rms_norm_zero_centred_and_gated():
    rng = _rng()
    x, z = _array(rng, 2, 5, 16), _array(rng, 2, 5, 16)
    w = _array(rng, 16, scale=0.3)
    plain = nn.RMSNorm(16, zero_centered=True)
    plain.initialize()
    assert float(plain.weight.data().asnumpy().max()) == 0.0   # from zero
    plain.weight.set_data(mx.nd.array(w))
    _close(plain(mx.nd.array(x)).data_, ref.rms_norm(x, w, 1e-6))
    gated = nn.RMSNorm(16)
    gated.initialize()
    assert float(gated.weight.data().asnumpy().min()) == 1.0    # from one
    gated.weight.set_data(mx.nd.array(w))
    want = w * x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) \
        * jax.nn.silu(z)
    _close(gated(mx.nd.array(x), mx.nd.array(z)).data_, want)
    # in float32 whatever the input's type, and in the input's type out
    low = mx.nd.array(x).astype("bfloat16")
    out = plain(low)
    assert out.dtype == low.dtype
    _close(out.data_.astype(jnp.float32),
           ref.rms_norm(low.data_.astype(jnp.float32), w, 1e-6), atol=2e-2)


def test_rotary_turns_part_of_a_head_and_passes_the_rest():
    x = _array(_rng(), 2, 3, 11, 16)
    got = mx.nd.rotary_embedding(mx.nd.array(x), mx.nd.arange(
        0, 11, dtype="int32"), rotary_dim=4, theta=1e7).data_
    _close(got, ref.rotary(x, 4, 1e7))
    assert np.array_equal(np.asarray(got[..., 4:]), np.asarray(x[..., 4:]))
    assert np.array_equal(np.asarray(got[:, :, 0]), np.asarray(x[:, :, 0]))
    assert not np.allclose(np.asarray(got[:, :, 1:, :4]),
                           np.asarray(x[:, :, 1:, :4]))


# -------------------------------------------------------------- attention

def _attention_block():
    blk = contrib_nn.GatedAttention(32, 4, 2, 16, rotary_dim=4,
                                    rope_theta=1e7)
    blk.initialize(mx.initializer.Xavier())
    rng = _rng(1)
    for norm in (blk.q_norm, blk.k_norm):       # not the zeros they start at
        norm.weight.set_data(mx.nd.array(_array(rng, 16, scale=0.3)))
    return blk


def _attention_tree(blk):
    def w(p):
        return p.data().data_

    return {"q_w": w(blk.q_proj.weight), "k_w": w(blk.k_proj.weight),
            "v_w": w(blk.v_proj.weight), "o_w": w(blk.out_proj.weight),
            "q_norm": w(blk.q_norm.weight), "k_norm": w(blk.k_norm.weight)}


def test_gated_attention_matches_the_reference_forward_and_backward():
    blk = _attention_block()
    x = _array(_rng(2), 2, 19, 32)
    fn, params = _call(blk, x)
    tree = _attention_tree(blk)
    _close(fn(params), ref.attention(x, tree, SIZES))
    got = _grad(lambda p: jnp.sum(jnp.sin(fn(p))))(params)
    want = _grad(lambda t: jnp.sum(jnp.sin(ref.attention(x, t, SIZES))))(
        tree)
    by_suffix = {"q_w": "q_weight", "k_w": "k_weight", "v_w": "v_weight",
                 "o_w": "out_weight", "q_norm": "qnorm_weight",
                 "k_norm": "knorm_weight"}
    for key, suffix in by_suffix.items():
        name = next(n for n in got if n.endswith(suffix))
        _close(got[name], want[key], atol=1e-4)


def test_each_kv_head_serves_its_group_of_query_heads():
    """Query heads 0-1 read K/V head 0 and heads 2-3 head 1: changing
    K/V head 1's projection moves only what heads 2-3 give."""
    blk = _attention_block()
    x = mx.nd.array(_array(_rng(3), 1, 9, 32))
    # look at the heads before out_proj mixes them: an identity there
    blk.out_proj.weight.set_data(mx.nd.array(np.eye(32, 64, dtype="f4")))
    before = blk(x).asnumpy()
    k_w = blk.k_proj.weight.data().asnumpy().copy()
    k_w[16:] *= -1.0                                    # K/V head 1 only
    blk.k_proj.weight.set_data(mx.nd.array(k_w))
    after = blk(x).asnumpy()
    # out_proj's identity keeps the first 32 of the 64 head channels:
    # heads 0 and 1, both of K/V head 0's group
    assert np.allclose(before, after, atol=1e-6)
    blk.out_proj.weight.set_data(mx.nd.array(
        np.eye(64, dtype="f4")[32:][:32]))              # heads 2 and 3
    k_w[16:] *= -1.0
    blk.k_proj.weight.set_data(mx.nd.array(k_w))
    before = blk(x).asnumpy()
    k_w[16:] *= -1.0
    blk.k_proj.weight.set_data(mx.nd.array(k_w))
    assert not np.allclose(before, blk(x).asnumpy(), atol=1e-3)


# ---------------------------------------------------------------- DeltaNet

def _rule_inputs(t, seed=4):
    rng = _rng(seed)
    return (_array(rng, 2, t, 2, 16), _array(rng, 2, t, 2, 16),
            _array(rng, 2, t, 4, 8),
            -jnp.asarray(rng.uniform(0, 2, (2, t, 4)), jnp.float32),
            jnp.asarray(rng.uniform(0, 1, (2, t, 4)), jnp.float32))


def _recurrent(q, k, v, g, beta):
    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    return ref.delta_rule_recurrent(
        jnp.repeat(unit(q) * q.shape[-1] ** -0.5, 2, axis=2),
        jnp.repeat(unit(k), 2, axis=2), v, g, beta)


@pytest.mark.parametrize("t,chunk", [(150, 64), (64, 64), (7, 64),
                                     (100, 16)])
def test_chunked_delta_rule_matches_the_token_recurrence(t, chunk):
    args = _rule_inputs(t)
    _close(linear_attention.gated_delta_rule(*args, chunk=chunk),
           _recurrent(*args), atol=1e-5)


def test_chunked_delta_rule_gradients_match_the_token_recurrence():
    args = _rule_inputs(150)                 # 2 chunks of 64 and 22 more
    got = _grad(lambda *a: jnp.sum(jnp.sin(
        linear_attention.gated_delta_rule(*a, chunk=64))),
        argnums=tuple(range(5)))(*args)
    want = _grad(lambda *a: jnp.sum(jnp.sin(_recurrent(*a))),
                    argnums=tuple(range(5)))(*args)
    for g, w in zip(got, want):
        _close(g, w, atol=1e-5)


def test_causal_convolution_sees_the_past_only():
    rng = _rng(5)
    x, w = _array(rng, 2, 9, 6), _array(rng, 6, 4)
    got = linear_attention.causal_conv1d(x, w)
    _close(got, ref.causal_conv(x, w))
    later = x.at[:, 5:].add(1.0)
    assert np.array_equal(
        np.asarray(linear_attention.causal_conv1d(later, w)[:, :5]),
        np.asarray(got[:, :5]))
    _close(got[:, 0], x[:, 0] * w[:, 3])    # the last tap is the present


def test_gated_deltanet_matches_the_reference_forward_and_backward():
    blk = contrib_nn.GatedDeltaNet(32, 2, 4, 8, 8)
    blk.initialize(mx.initializer.Xavier())
    assert np.all(blk.dt_bias.data().asnumpy() == 1.0)
    a = np.exp(blk.A_log.data().asnumpy())
    assert np.all((a > 0) & (a < 16))
    blk.norm.weight.set_data(mx.nd.array(_array(_rng(6), 8, scale=0.5)))
    x = _array(_rng(7), 2, 70, 32)          # one chunk of 64 and 6 more
    fn, params = _call(blk, x)

    def tree_of(p):
        by = {k.split("gateddeltanet")[-1].split("_", 1)[1]: v
              for k, v in p.items()}
        return {"qkvz_w": by["qkvz_weight"], "ba_w": by["ba_weight"],
                "conv_w": by["conv_weight"], "A_log": by["A_log"],
                "dt_bias": by["dt_bias"], "norm": by["norm_weight"],
                "out_w": by["out_weight"]}

    _close(fn(params), ref.deltanet(x, tree_of(params), SIZES), atol=1e-5)
    got = tree_of(_grad(lambda p: jnp.sum(jnp.sin(fn(p))))(params))
    want = _grad(lambda t: jnp.sum(jnp.sin(ref.deltanet(x, t, SIZES))))(
        tree_of(params))
    for key in want:
        _close(got[key], want[key], atol=1e-4)


def test_gated_deltanet_through_the_kernels_matches_the_jnp_path(
        monkeypatch):
    """At widths on the lane grid, what the dispatch takes on a TPU (the
    short convolution's kernels and the delta rule's, here in interpret
    mode): the output and every parameter's gradient equal the
    ``jax.numpy`` path's."""
    from mxnet_tpu.ops import (conv_silu_kernels, delta_rule_kernels,
                               pallas_kernels)

    blk = contrib_nn.GatedDeltaNet(32, 1, 2, 128, 128)
    blk.initialize(mx.initializer.Xavier())
    x = _array(_rng(8), 2, 70, 32)
    fn, params = _call(blk, x)

    def loss(p):
        return jnp.sum(jnp.sin(fn(p)))

    want, want_grads = fn(params), _grad(loss)(params)
    taken = []
    for mod, name in ((conv_silu_kernels, "causal_conv_silu_kernels"),
                      (delta_rule_kernels, "gated_delta_rule_kernels")):
        def interpreted(*args, _real=getattr(mod, name), _name=name,
                        **kwargs):
            taken.append(_name)
            return _real(*args, **dict(kwargs, interpret=True))

        monkeypatch.setattr(mod, name, interpreted)
    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    fn, _ = _call(blk, x)       # traced again, through the kernels
    _close(fn(params), want, atol=1e-5)
    got_grads = _grad(loss)(params)
    assert set(taken) == {"causal_conv_silu_kernels",
                          "gated_delta_rule_kernels"}
    assert set(got_grads) == set(want_grads) and len(got_grads) == 7
    for key in want_grads:
        _close(got_grads[key], want_grads[key], atol=1e-4)


# ------------------------------------------------------------ expert layer

def _moe_weights(rng, experts=16, d=32, inner=16):
    return {"router_w": _array(rng, experts, d, scale=0.3),
            "gate_up": _array(rng, experts, d, 2 * inner, scale=0.2),
            "down": _array(rng, experts, inner, d, scale=0.2),
            "shared_gate_up_w": _array(rng, 2 * inner, d, scale=0.2),
            "shared_down_w": _array(rng, d, inner, scale=0.2),
            "shared_gate_w": _array(rng, 1, d, scale=0.3)}


def _moe_block(w, first, count, shared=True):
    blk = contrib_nn.SparseMoE(32, 16, 16, 3, experts_held=(first, count),
                               shared_hidden=16 if shared else 0)
    blk.initialize(mx.initializer.Xavier())
    blk.router_weight.set_data(mx.nd.array(w["router_w"]))
    blk.experts_gate_up_weight.set_data(
        mx.nd.array(w["gate_up"][first:first + count]))
    blk.experts_down_weight.set_data(
        mx.nd.array(w["down"][first:first + count]))
    if shared:
        blk.shared.gate_up.weight.set_data(mx.nd.array(w["shared_gate_up_w"]))
        blk.shared.down.weight.set_data(mx.nd.array(w["shared_down_w"]))
        blk.shared_gate.weight.set_data(mx.nd.array(w["shared_gate_w"]))
    return blk


def _held(w, first, count):
    return dict(w, gate_up=w["gate_up"][first:first + count],
                down=w["down"][first:first + count])


def test_sparse_moe_matches_the_reference_forward_and_backward():
    w = _moe_weights(_rng(8))
    blk = _moe_block(w, 5, 6)
    x = _array(_rng(9), 2, 40, 32)
    sizes = dict(SIZES, first_expert=5)
    fn, params = _call(blk, x)
    _close(fn(params), ref.moe(x, _held(w, 5, 6), sizes))
    got = _grad(lambda p: jnp.sum(jnp.sin(fn(p))))(params)
    want = _grad(lambda t: jnp.sum(jnp.sin(ref.moe(x, t, sizes))))(
        _held(w, 5, 6))
    by_suffix = {"router_w": "router_weight",
                 "gate_up": "experts_gate_up_weight",
                 "down": "experts_down_weight",
                 "shared_gate_up_w": "shared_gate_up_weight",
                 "shared_down_w": "shared_down_weight",
                 "shared_gate_w": "shared_gate_weight"}
    for key, suffix in by_suffix.items():
        name = next(n for n in got if n.endswith(suffix))
        _close(got[name], want[key], atol=1e-4)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four devices of an expert-parallel group, four experts each: the
    routed parts they give, with the shared expert (which every device
    computes alike) counted once, are the whole layer's result."""
    w = _moe_weights(_rng(10))
    x = _array(_rng(11), 2, 33, 32)
    parts = [_moe_block(w, first, 4, shared=False)(mx.nd.array(x)).data_
             for first in (0, 4, 8, 12)]
    whole = ref.moe(x, w, dict(SIZES, first_expert=0))
    _close(sum(parts) + ref.shared(x, w), whole)
    # and the program's own uncut layer says the same
    _close(_moe_block(w, 0, 16)(mx.nd.array(x)).data_, whole)
    # every token's three choices are computed by exactly one share
    counted = 0
    for first in (0, 4, 8, 12):
        blk = _moe_block(w, first, 4, shared=False)
        blk(mx.nd.array(x))
        counted += blk.expert_tokens.data().asnumpy()[:4].sum()
    assert counted == 2 * 33 * 3


@pytest.mark.parametrize("favoured,expect", [
    ("one held expert", "every token on expert 6, none on the other held"),
    ("no held expert", "no token on any held expert"),
    ("the held experts", "every choice of every token held"),
])
def test_a_skewed_router_drops_no_token(favoured, expect):
    """No capacity factor: whatever the routing, every assignment that
    falls on a held expert is computed, and the result is the
    reference's."""
    rng = _rng(12)
    w = _moe_weights(rng)
    x = _array(rng, 2, 50, 32).at[..., 0].set(1.0)      # a constant input
    bias = np.zeros(16, np.float32)                     # rides on it
    if favoured == "one held expert":
        bias[5:11] = -30.0
        bias[6] = 30.0
    elif favoured == "no held expert":
        bias[5:11] = -30.0
    else:
        bias[5:11] = 30.0
    w["router_w"] = w["router_w"].at[:, 0].set(jnp.asarray(bias))
    # a hundred tokens, so the sorted assignments are taken a hundred at
    # a time: the three hundred of the last case take the scan over blocks
    blk = _moe_block(w, 5, 6)
    got = blk(mx.nd.array(x)).data_
    _close(got, ref.moe(x, _held(w, 5, 6), dict(SIZES, first_expert=5)))
    counts = blk.expert_tokens.data().asnumpy()
    per_expert, idle = counts[:6], counts[6]
    if favoured == "one held expert":
        assert per_expert[1] == 100 and per_expert.sum() == 100 and idle == 0
    elif favoured == "no held expert":
        assert per_expert.sum() == 0 and idle == 100
        _close(got, ref.shared(x, w))
    else:
        assert per_expert.sum() == 300 and idle == 0


def _every_choice_held():
    """60 tokens whose 3 choices all fall on the 7 experts held from 2:
    180 assignments, 3 blocks of 60."""
    rng = _rng(13)
    w = _moe_weights(rng)
    x = _array(rng, 60, 32).at[..., 0].set(1.0)
    w["router_w"] = w["router_w"].at[2:9, 0].set(30.0)  # all 3 choices held
    weights, experts = moe_ops.moe_router(x, w["router_w"], top_k=3)
    return w, x, weights, experts


@pytest.mark.parametrize("path", ["one block", "scan"])
def test_one_block_of_rows_and_the_scan_over_blocks_agree(path):
    """The same assignments through the single block (120 more tokens
    that choose no held expert, so that the 180 fit the 180 tokens) and
    through the scan (the 60 tokens alone, which 180 do not fit): the
    result and, under ``Remat``'s default policy, the gradients of the
    tokens and of both expert weights are the reference's."""
    w, x, weights, experts = _every_choice_held()
    idle = 120 if path == "one block" else 0
    x = jnp.concatenate([x, _array(_rng(14), idle, 32)])
    weights = jnp.concatenate([weights, jnp.full((idle, 3), 1 / 3)])
    experts = jnp.concatenate([experts, jnp.tile(jnp.asarray(
        [[0, 1, 12]], jnp.int32), (idle, 1))])
    gate_up, down = w["gate_up"][2:9], w["down"][2:9]

    def layer(x, gate_up, down):
        return moe_ops.moe_experts(x, weights, experts, gate_up, down,
                                   jnp.zeros(8), first_expert=2)

    out, counts = layer(x, gate_up, down)
    assert counts[:7].sum() == 180 and counts[7] == idle
    _close(out, _routed_with(x, weights, experts, w, 2, 7))
    _close(out[:60], ref.routed(x[:60], _held(w, 2, 7),
                                dict(SIZES, first_expert=2)))
    checkpointed = jax.checkpoint(lambda *a: layer(*a)[0],
                                  policy=remat.resolve_policy(None))
    grads = _grad(lambda *a: jnp.sum(jnp.sin(checkpointed(*a))),
                  argnums=(0, 1, 2))(x, gate_up, down)

    def reference(x, gate_up, down):
        held = dict(w, gate_up=w["gate_up"].at[2:9].set(gate_up),
                    down=w["down"].at[2:9].set(down))
        return jnp.sum(jnp.sin(_routed_with(x, weights, experts, held, 2,
                                            7)))

    want = _grad(reference, argnums=(0, 1, 2))(x, gate_up, down)
    for got_one, want_one in zip(grads, want):
        _close(got_one, want_one, atol=1e-4)


def test_the_scan_keeps_no_per_block_copy_of_its_inputs():
    """The backward pass of the expert layer under ``Remat``'s default
    policy: the scan over blocks keeps what its checkpoint closes over
    once, not a stack of ``min(top_k, held)`` copies of the tokens or
    the expert weights, which the single block that runs would then have
    to fill with zeros."""
    w, x, weights, experts = _every_choice_held()
    gate_up, down = w["gate_up"][2:9], w["down"][2:9]
    blocks = min(experts.shape[-1], gate_up.shape[0])
    assert blocks > 1

    def loss(x, gate_up, down):
        return jnp.sum(jnp.sin(moe_ops.moe_experts(
            x, weights, experts, gate_up, down, jnp.zeros(8),
            first_expert=2)[0]))

    grad = jax.grad(jax.checkpoint(loss, policy=remat.resolve_policy(None)),
                    argnums=(0, 1, 2))
    jaxpr = str(jax.make_jaxpr(grad)(x, gate_up, down))
    hlo = jax.jit(grad).lower(x, gate_up, down).as_text()
    stacks = [(blocks,) + a.shape for a in (x, gate_up, down)]
    found = [s for s in stacks
             if f"f32[{','.join(map(str, s))}]" in jaxpr
             or f"tensor<{'x'.join(map(str, s))}xf32>" in hlo]
    assert not found


def _routed_with(x, weights, experts, w, first, count):
    """The reference's loop over the held experts, on given routing."""
    inner = w["down"].shape[1]
    y = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1,
                      keepdims=True)
        h = x @ w["gate_up"][first + e]
        y = y + w_e * ((jax.nn.silu(h[:, :inner]) * h[:, inner:])
                       @ w["down"][first + e])
    return y


# ------------------------------------------------------------- whole model

@pytest.fixture(scope="module")
def net():
    mx.random.seed(7)
    built = qwen3_next_lm(CONFIG, num_hidden_layers=4, num_experts=16,
                          experts_held=(5, 6))
    built.initialize(mx.initializer.Xavier())
    rng = _rng(14)
    for name, p in built.collect_params().items():      # norms off zero
        if name.endswith(("norm1_weight", "norm2_weight", "qnorm_weight",
                          "knorm_weight", "lm0_norm_weight")):
            p.set_data(mx.nd.array(_array(rng, *p.shape, scale=0.2)))
    return built


def test_the_layers_alternate_three_linear_and_one_full(net):
    kinds = [type(blk.attn).__name__ for blk in net.blocks]
    assert kinds == ["GatedDeltaNet"] * 3 + ["GatedAttention"]
    assert net.head.bias is None and net.head.weight.shape == (97, 32)
    names = list(net.collect_params())
    assert not [n for n in names if "pos" in n]         # no learned positions
    assert sum(n.endswith("moe_expert_tokens") for n in names) == 4


def test_the_model_matches_the_reference_forward_and_backward(net):
    rng = _rng(15)
    tokens = jnp.asarray(rng.integers(0, 97, (2, 70)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, 97, (2, 70)), jnp.int32)
    positions = jnp.asarray(rng.integers(0, 70, (2, 5)), jnp.int32)
    fwd = parallel.functional_call(net, train=True)
    params, aux = parallel.param_arrays(net), parallel.aux_arrays(net)

    def loss_of(p):
        logits, _ = fwd(p, aux, tokens)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))

    tree = model.reference_weights(net)
    want_loss, want_logits, _ = jax.jit(lambda t: ref.check_outputs(
        t, tokens, labels, positions, SIZES))(tree)
    logits, moved = jax.jit(fwd)(params, aux, tokens)
    _close(jnp.take_along_axis(logits, positions[:, :, None], axis=1),
           want_logits, atol=1e-4)
    _close(jax.jit(loss_of)(params), want_loss, atol=1e-5)
    state = next(v for k, v in moved.items()
                 if k.endswith("moe_expert_tokens"))
    assert state[:6].sum() > 0 and state.shape == (7,)

    got = _grad(loss_of)(params)
    want = _grad(lambda t: ref.check_outputs(
        t, tokens, labels, positions, SIZES)[0])(tree)
    # the program's gradients, laid into the reference's tree
    kept = {name: p.data() for name, p in net.collect_params().items()}
    try:
        for name, grad in got.items():
            net.collect_params()[name].set_data(mx.nd.array(grad))
        got_tree = model.reference_weights(net)
    finally:
        for name, value in kept.items():
            net.collect_params()[name].set_data(value)
    flat_got = jax.tree_util.tree_leaves_with_path(got_tree)
    flat_want = jax.tree_util.tree_leaves(want)
    assert len(flat_got) == len(flat_want) == len(got)
    for (path, g), w in zip(flat_got, flat_want):
        assert float(jnp.abs(w).max()) > 0, path        # every kind trains
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-3,
            atol=2e-3 * float(jnp.abs(w).max()), err_msg=str(path))


def test_the_trainer_takes_the_model_as_it_takes_any_other(net):
    """ShardedTrainer + capture, every layer under contrib.nn.Remat, the
    bf16 policy: one captured executable, a finite loss that falls, the
    experts' counts moved by the step."""
    from mxnet_tpu import capture, gluon
    from mxnet_tpu.observability import perf

    perf.clear()
    mx.random.seed(8)
    built = qwen3_next_lm(CONFIG, num_hidden_layers=4, num_experts=16,
                          experts_held=(5, 6), remat=True)
    built.initialize(mx.initializer.Xavier())
    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        built, gluon.loss.SoftmaxCrossEntropyLoss(), "adam",
        {"learning_rate": 1e-3}, mesh=mesh, dtype="bfloat16",
        param_rules=parallel.SpecLayout.for_mesh(mesh).param_rules())
    step = capture.capture(trainer)
    rng = _rng(16)
    x = jnp.asarray(rng.integers(0, 97, (2, 70)), jnp.int32)
    y = jnp.asarray(rng.integers(0, 97, (2, 70)), jnp.int32)
    before = capture.stats()
    losses = [float(step(x, y)) for _ in range(4)]
    after = capture.stats()
    assert np.all(np.isfinite(losses)) and losses[-1] < losses[0]
    assert after["capture_fallback_eager"] == before["capture_fallback_eager"]
    counts = [np.asarray(v) for k, v in sorted(trainer.aux.items())
              if k.endswith("moe_expert_tokens")]
    assert len(counts) == 4
    for c in counts:
        assert 0 < c[:6].sum() <= 140 * 3 and c[6] < 140
    # the program names the new mechanisms in both directions, the
    # projections outside the mixer's scope
    key = next(k for k, e in perf.ledger().items()
               if e["label"] == "sharded_step")
    ops = [n["op_name"] for n in perf.op_names(key).values() if n["op_name"]]
    for scope in ("linear_attention", "attention", "moe", "moe_router",
                  "moe_experts"):
        inside = [o for o in ops if f"/{scope}/" in o]
        assert any("transpose(" in o for o in inside), scope
        assert any("transpose(" not in o for o in inside), scope
    assert not [o for o in ops if "/linear_attention/" in o
                and ("_qkvz/" in o or "_ba/" in o or "_out/" in o)]
    assert all("/moe/" in o for o in ops if "/moe_experts/" in o)


def test_the_layout_has_a_rule_for_every_matrix_of_the_model(net):
    import re

    layout = parallel.SpecLayout()
    rules = [(re.compile(p), s) for p, s in layout.param_rules()]
    unmatched = [n for n, p in net.collect_params().items()
                 if len(p.shape) > 1 and p.grad_req != "null"
                 and not any(r.match(n) for r, _ in rules)]
    # the router and the depthwise convolution replicate by design
    assert all(n.endswith(("moe_router_weight", "linattn_conv_weight",
                           "moe_shared_gate_weight")) for n in unmatched)
    experts = next(s for r, s in rules
                   if r.match("x_moe_experts_down_weight"))
    assert tuple(experts) == ("ep",)
    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    dropped = parallel.SpecLayout.for_mesh(mesh)
    assert dropped.ep_axis is None and tuple(dropped.experts()) == ()
