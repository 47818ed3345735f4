"""The gated delta rule's Pallas kernels (``ops/delta_rule_kernels.py``)
in interpret mode on the CPU: forward and all five gradients against the
token recurrence of the benchmark's reference and against the
``jax.numpy`` chunked form they replace on the chip, the triangular
inverse against ``solve_triangular``, and the dispatch of
``ops.linear_attention.gated_delta_rule`` between the two."""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.references import qwen3_next as ref             # noqa: E402
from mxnet_tpu.ops import delta_rule_kernels, linear_attention  # noqa: E402
from mxnet_tpu.ops import pallas_kernels                        # noqa: E402
from mxnet_tpu.tune import schedule                             # noqa: E402

ALL = (0, 1, 2, 3, 4)
NAMES = ("q", "k", "v", "g", "beta")


def _inputs(t, rep=2, dk=128, dv=128, dtype=jnp.float32, seed=0, g_low=2.0,
            beta=(0.0, 1.0), hk=1):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    hv = hk * rep
    return (normal(1, t, hk, dk).astype(dtype),
            normal(1, t, hk, dk).astype(dtype),
            normal(1, t, hv, dv).astype(dtype),
            -jnp.asarray(rng.uniform(0, g_low, (1, t, hv)), jnp.float32),
            jnp.asarray(rng.uniform(*beta, (1, t, hv)), jnp.float32))


def _recurrent(q, k, v, g, beta):
    """The reference's one-update-a-token recurrence, in float32."""
    rep = v.shape[2] // k.shape[2]
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    return ref.delta_rule_recurrent(
        jnp.repeat(unit(q) * q.shape[-1] ** -0.5, rep, axis=2),
        jnp.repeat(unit(k), rep, axis=2), v, g, beta)


def _kernels(*args, **kwargs):
    return delta_rule_kernels.gated_delta_rule_kernels(
        *args, interpret=True, **kwargs)


def _chunked(*args):
    return linear_attention._chunked_delta_rule(*args, 64)


def _out_and_grads(fn, args, weight):
    def loss(*a):
        out = fn(*a)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    (_, out), grads = jax.value_and_grad(loss, argnums=ALL, has_aux=True)(
        *args)
    return (out,) + grads


def _weight(args, seed=9):
    shape = args[2].shape
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _rel(got, want):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _assert_all_close(got, want, bar, what):
    assert got[0].dtype == want[0].dtype or what != "chunked"
    for name, a, b in zip(("o",) + tuple("d" + n for n in NAMES), got, want):
        assert a.shape == b.shape, (what, name)
        assert _rel(a, b) <= bar, (what, name, _rel(a, b))


# T on and off the chunk grid (one chunk; two and a part; three and a
# part; nine chunks, so three steps of three under the default's eight),
# each key head serving one value head and two
@pytest.mark.parametrize("t,rep", [(64, 1), (64, 2), (150, 1), (150, 2),
                                   (200, 1), (200, 2), (520, 1), (520, 2)])
def test_kernels_match_the_recurrence_and_the_chunked_form(t, rep):
    args = _inputs(t, rep=rep, seed=t + rep)
    weight = _weight(args)
    got = _out_and_grads(_kernels, args, weight)
    _assert_all_close(got, _out_and_grads(_recurrent, args, weight), 2e-5,
                      "recurrent")
    _assert_all_close(got, _out_and_grads(_chunked, args, weight), 2e-5,
                      "chunked")


@pytest.mark.parametrize("dk,dv", [(128, 256), (256, 128)])
def test_kernels_take_key_and_value_heads_of_different_sizes(dk, dv):
    args = _inputs(150, dk=dk, dv=dv, seed=3)
    weight = _weight(args)
    got = _out_and_grads(_kernels, args, weight)
    assert got[0].shape == (1, 150, 2, dv)
    _assert_all_close(got, _out_and_grads(_recurrent, args, weight), 2e-5,
                      "recurrent")
    _assert_all_close(got, _out_and_grads(_chunked, args, weight), 2e-5,
                      "chunked")


@pytest.mark.parametrize("g_low,beta", [(20.0, (0.0, 1.0)),
                                        (2.0, (0.0, 1e-3)),
                                        (2.0, (1.0 - 1e-3, 1.0)),
                                        (1e-3, (0.9, 1.0))])
def test_kernels_hold_at_strong_decays_and_extreme_write_strengths(g_low,
                                                                   beta):
    """g down to -20 a token (a chunk's decays underflow to 0), beta
    next to 0 (nothing written) and to 1 (the old value replaced), and
    hardly any decay under strong writes (the system at its fullest)."""
    args = _inputs(200, g_low=g_low, beta=beta, seed=5)
    weight = _weight(args)
    got = _out_and_grads(_kernels, args, weight)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got)
    _assert_all_close(got, _out_and_grads(_recurrent, args, weight), 5e-5,
                      "recurrent")
    _assert_all_close(got, _out_and_grads(_chunked, args, weight), 5e-5,
                      "chunked")


@pytest.mark.parametrize("t,rep", [(150, 2), (200, 1)])
def test_kernels_in_bfloat16_follow_the_chunked_form(t, rep):
    """bf16 operands where the docstring says so, float32 elsewhere:
    the output in ``v``'s dtype, gradients in their inputs', within bf16
    rounding of the ``jax.numpy`` form under the same policy and of the
    float32 recurrence."""
    args = _inputs(t, rep=rep, dtype=jnp.bfloat16, seed=t)
    weight = _weight(args)
    got = _out_and_grads(_kernels, args, weight)
    assert got[0].dtype == jnp.bfloat16
    assert [x.dtype for x in got[1:]] == [a.dtype for a in args]
    _assert_all_close(got, _out_and_grads(_chunked, args, weight), 3e-2,
                      "chunked")
    exact = _out_and_grads(_recurrent, args, weight)
    _assert_all_close(got, exact, 3e-2, "recurrent")


@pytest.mark.parametrize("chunks,bwd_chunks", [(1, 1), (2, 4), (8, 3)])
def test_chunks_a_grid_step_are_a_schedule_not_a_result(chunks, bwd_chunks):
    """Eight chunks under every split of the grid: the same output and
    gradients to float32 rounding (a step's chunks only share a DMA)."""
    args = _inputs(512, seed=11)
    weight = _weight(args)
    want = _out_and_grads(_kernels, args, weight)
    got = _out_and_grads(
        lambda *a: _kernels(*a, chunks=chunks, bwd_chunks=bwd_chunks),
        args, weight)
    _assert_all_close(got, want, 1e-5, "schedules")


def test_a_chunk_of_32_is_a_different_chunk_with_the_same_result():
    args = _inputs(100, seed=13)
    _assert_all_close((_kernels(*args, chunk=32),), (_recurrent(*args),),
                      2e-5, "recurrent")


def _system(rng, c, d, strong):
    """A chunk's ``A`` as the kernels build it: unit keys, write
    strengths, decays; ``strong`` leaves the decays out and correlates
    the keys, which fills the triangle."""
    k = rng.standard_normal((c, d)).astype(np.float32)
    if strong:
        k = k + 2.0 * rng.standard_normal((1, d)).astype(np.float32)
    k /= np.linalg.norm(k, axis=1, keepdims=True)
    beta = rng.uniform(0.5 if strong else 0.0, 1.0, (c, 1)).astype(
        np.float32)
    gc = np.cumsum(-rng.uniform(0, 0.0 if strong else 1.0, c)).astype(
        np.float32)
    decay = np.exp(gc[:, None] - gc[None, :])
    return jnp.asarray(np.tril(k @ k.T * beta * decay, -1))


@pytest.mark.parametrize("c,d,strong", [(64, 128, False), (64, 128, True),
                                        (64, 16, False), (32, 128, True),
                                        (48, 64, False), (16, 128, False)])
def test_triangular_inverse_matches_solve_triangular(c, d, strong):
    a = _system(np.random.default_rng(c + d), c, d, strong)
    eye = jnp.eye(c, dtype=jnp.float32)
    want = jax.scipy.linalg.solve_triangular(
        a + eye, eye, lower=True, unit_diagonal=True)
    got = delta_rule_kernels.unit_lower_inverse(a)
    assert _rel(got, want) <= 1e-5, _rel(got, want)
    assert _rel(got @ (a + eye), eye) <= 1e-5
    assert float(jnp.max(jnp.abs(jnp.triu(got, 1)))) == 0.0


# ---------------------------------------------------------------- dispatch

@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the dispatch sees on the chip, with the kernels it then
    takes run in interpret mode and counted."""
    calls = []
    real = delta_rule_kernels.gated_delta_rule_kernels

    def interpreted(*args, **kwargs):
        calls.append({k: v for k, v in kwargs.items() if k != "interpret"})
        return real(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    monkeypatch.setattr(delta_rule_kernels, "gated_delta_rule_kernels",
                        interpreted)
    return calls


def test_dispatch_takes_the_kernels_on_a_tpu_where_the_shape_is_legal(
        on_a_tpu):
    args = _inputs(150, seed=17)
    got = jax.jit(linear_attention.gated_delta_rule)(*args)
    assert on_a_tpu == [{"chunk": 64}]
    _assert_all_close((got,), (_chunked(*args),), 2e-5, "chunked")


@pytest.mark.parametrize("dk,dv,chunk", [(16, 128, 64), (128, 8, 64),
                                         (128, 128, 40)])
def test_dispatch_keeps_the_chunked_form_off_the_lane_grid(on_a_tpu, dk, dv,
                                                           chunk):
    assert not schedule.delta_rule_shape_supported(dk, dv, chunk)
    args = _inputs(90, dk=dk, dv=dv, seed=19)
    got = jax.jit(lambda *a: linear_attention.gated_delta_rule(
        *a, chunk=chunk))(*args)
    assert on_a_tpu == []
    _assert_all_close((got,), (_recurrent(*args),), 2e-5, "recurrent")
    with pytest.raises(schedule.ScheduleError):
        _kernels(*args, chunk=chunk)


def test_dispatch_keeps_the_chunked_form_on_the_cpu(monkeypatch):
    """No chip: a traced call lands on jax's default backend and an
    array says where it lives; neither takes the kernels, which would
    not compile here."""
    def never(*args, **kwargs):
        raise AssertionError("the kernels were taken on the CPU")

    monkeypatch.setattr(delta_rule_kernels, "gated_delta_rule_kernels",
                        never)
    args = _inputs(64, seed=23)
    assert not pallas_kernels.pallas_available()
    traced = jax.jit(linear_attention.gated_delta_rule)(*args)
    eager = linear_attention.gated_delta_rule(*args)
    _assert_all_close((traced,), (eager,), 1e-6, "eager")
    # an array on the CPU decides for itself, whatever the default is
    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    _assert_all_close((linear_attention.gated_delta_rule(*args),), (eager,),
                      1e-6, "eager")
