"""The short convolution + SiLU kernels (``ops/conv_silu_kernels.py``) in
interpret mode on the CPU: results and the gradients of input and weight
against the ``jax.numpy`` form ``silu(causal_conv1d(x, w))`` they
replace on the chip, a token never seeing a later one, tiles that are a
schedule and not a result, and the dispatch of
``ops.linear_attention.causal_conv_silu`` between the two."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.gluon.contrib import nn as contrib_nn
from mxnet_tpu.observability import trace
from mxnet_tpu.ops import conv_silu_kernels, linear_attention, pallas_kernels
from mxnet_tpu.tune import schedule

PARTS = (128, 384, 256)      # three unequal parts, then 128 columns more
REST = 128


def _inputs(b, t, taps, dtype, parts=PARTS, rest=REST, seed=0):
    rng = np.random.default_rng(seed)
    c = sum(parts)
    x = jnp.asarray(rng.standard_normal((b, t, c + rest)), jnp.float32)
    w = jnp.asarray(rng.uniform(-1, 1, (c, taps)) * taps ** -0.5,
                    jnp.float32)
    weights = [jnp.asarray(rng.standard_normal((b, t, p)), jnp.float32)
               for p in parts + (rest,)]
    return x.astype(dtype), w.astype(dtype), weights


def _jnp_form(x, w, parts=PARTS):
    c = w.shape[0]
    mixed = jax.nn.silu(linear_attention.causal_conv1d(x[..., :c], w))
    ends = np.cumsum(parts)
    return tuple(mixed[..., e - p:e] for e, p in zip(ends, parts)) \
        + (x[..., c:],)


def _kernels(x, w, parts=PARTS, **tiles):
    return conv_silu_kernels.causal_conv_silu_kernels(
        x, w, parts, interpret=True, **tiles)


def _outs_and_grads(fn, x, w, weights):
    def loss(x, w):
        outs = fn(x, w)
        return sum(jnp.sum(o.astype(jnp.float32) * m)
                   for o, m in zip(outs, weights)), outs

    (_, outs), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                          has_aux=True)(x, w)
    return tuple(outs) + tuple(grads)


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if not want.size:
        return 0.0
    return float(np.max(np.abs(got - want))
                 / max(np.max(np.abs(want)), 1e-30))


def _assert_close(got, want, bar):
    names = [f"part{n}" for n in range(len(got) - 3)] + ["rest", "dx", "dw"]
    for name, a, b in zip(names, got, want):
        assert a.shape == b.shape, name
        assert _rel(a, b) <= bar, (name, _rel(a, b))


# T a multiple of the row tile (two tiles of 32) and not (three and a
# part), one and two sequences, a convolution of 2 and of 4 taps
@pytest.mark.parametrize("dtype,bar", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("taps", [2, 4])
@pytest.mark.parametrize("b", [1, 2])
@pytest.mark.parametrize("t", [64, 100])
def test_kernels_match_the_jnp_form(dtype, bar, taps, b, t):
    """Results in the input's dtype; in float32 equal to rounding, in
    bf16 within bf16 rounding of the float32 ``jax.numpy`` form (the
    kernels round once, the bf16 form at every tap)."""
    x, w, weights = _inputs(b, t, taps, dtype, seed=t + taps + b)
    got = _outs_and_grads(
        lambda x, w: _kernels(x, w, rows=32, bwd_rows=32), x, w, weights)
    assert all(a.dtype == dtype for a in got)
    assert [a.shape[-1] for a in got[:4]] == list(PARTS) + [REST]
    want = _outs_and_grads(_jnp_form, x.astype(jnp.float32),
                           w.astype(jnp.float32), weights)
    _assert_close(got, want, bar)
    np.testing.assert_array_equal(np.asarray(got[3]),
                                  np.asarray(x[..., sum(PARTS):]))


def test_no_columns_past_the_parts_and_one_part():
    x, w, weights = _inputs(1, 40, 3, jnp.float32, parts=(256,), rest=0)
    got = _outs_and_grads(lambda x, w: _kernels(x, w, (256,)), x, w, weights)
    assert got[1].shape == (1, 40, 0)
    want = _outs_and_grads(lambda x, w: _jnp_form(x, w, (256,)), x, w,
                           weights)
    _assert_close(got, want, 2e-6)


def test_a_token_never_sees_a_later_one():
    """Change the tail: the head's results are the same bit for bit,
    inside a tile and across tiles."""
    x, w, _ = _inputs(2, 100, 4, jnp.bfloat16, seed=3)
    later = x.at[:, 37:].add(1.0)
    for a, b in zip(_kernels(x, w, rows=32)[:3],
                    _kernels(later, w, rows=32)[:3]):
        np.testing.assert_array_equal(np.asarray(a[:, :37]),
                                      np.asarray(b[:, :37]))
        assert np.any(np.asarray(a[:, 37:]) != np.asarray(b[:, 37:]))


@pytest.mark.parametrize("rows,cols", [(16, 128), (48, 256), (2048, 2048)])
def test_tiles_are_a_schedule_not_a_result(rows, cols):
    """Every split of the rows and the channels: the same results and
    gradients to float32 rounding (the weight's gradient is summed in
    another order)."""
    x, w, weights = _inputs(1, 100, 4, jnp.float32, seed=5)
    want = _outs_and_grads(_kernels, x, w, weights)
    got = _outs_and_grads(
        lambda x, w: _kernels(x, w, rows=rows, cols=cols, bwd_rows=rows,
                              bwd_cols=cols), x, w, weights)
    _assert_close(got, want, 1e-6)


def test_tiles_are_legalized_to_the_shape():
    tile = schedule.conv_silu_tile
    # the default, and the table's key
    assert tile("conv_silu_fwd", 1, 8192, 2048, 2048, 4, "bfloat16") == \
        (schedule.DEFAULT_SCHEDULES["conv_silu_fwd"]["rows"],
         schedule.DEFAULT_SCHEDULES["conv_silu_fwd"]["cols"])
    assert schedule.conv_silu_shape_key(1, 8192, 2048, 4) == \
        "b1-t8192-c2048-k4"
    # rows: whole halos, no longer than the padded sequence
    assert tile("conv_silu_fwd", 1, 100, 128, 0, 4, "float32",
                rows=4096, cols=128) == (112, 128)
    assert tile("conv_silu_bwd", 1, 5, 128, 0, 4, "float32") == (16, 128)
    # channels: a divisor of the part's width and of its first column
    assert tile("conv_silu_fwd", 1, 64, 384, 128, 4, "float32",
                cols=512)[1] == 128
    assert tile("conv_silu_fwd", 1, 64, 768, 256, 4, "float32",
                cols=512)[1] == 256
    assert schedule.validate_table({
        "schema_version": schedule.SCHEMA_VERSION, "entries": {
            "conv_silu_bwd|tpu|bfloat16|b1-t8192-c2048-k4": {
                "schedule": {"rows": 256, "cols": 1024}}}}) == []


def test_a_built_kernel_records_a_build_span():
    was = trace.enabled()
    trace.set_enabled(True)
    try:
        trace.clear()
        conv_silu_kernels._build.cache_clear()
        x, w, _ = _inputs(1, 48, 2, jnp.float32, parts=(128, 256), rest=0)
        for _ in range(2):      # the builders are cached: noted once
            _kernels(x, w, (128, 256))
        spans = [s["attrs"] for s in trace.spans()
                 if s["name"] == "kernel.build"]
    finally:
        trace.set_enabled(was)
        trace.clear()
    assert [(s["kernel"], s["channels"]) for s in spans] == [
        ("causal_conv_silu_fwd", 128), ("causal_conv_silu_fwd", 256)]
    assert spans[0]["t"] == 48 and spans[0]["taps"] == 2
    assert spans[0]["rows"] == 48 and spans[0]["cols"] == 128


# ---------------------------------------------------------------- dispatch

@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the dispatch sees on the chip, with the kernels it then
    takes run in interpret mode and counted."""
    calls = []
    real = conv_silu_kernels.causal_conv_silu_kernels

    def interpreted(x, w, parts, **kwargs):
        calls.append(tuple(parts))
        return real(x, w, parts, **dict(kwargs, interpret=True))

    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    monkeypatch.setattr(conv_silu_kernels, "causal_conv_silu_kernels",
                        interpreted)
    return calls


def test_dispatch_takes_the_kernels_on_a_tpu_where_the_shape_is_legal(
        on_a_tpu):
    x, w, _ = _inputs(1, 50, 4, jnp.float32, seed=7)
    got = jax.jit(lambda x, w: linear_attention.causal_conv_silu(
        x, w, PARTS))(x, w)
    assert on_a_tpu == [PARTS]
    for a, b in zip(got, _jnp_form(x, w)):
        assert _rel(a, b) <= 2e-6


@pytest.mark.parametrize("parts,taps", [((128, 64), 4), ((100,), 4),
                                        ((128, 128), 12)])
def test_dispatch_keeps_the_jnp_form_off_the_lane_grid(on_a_tpu, parts,
                                                       taps):
    assert not schedule.conv_silu_shape_supported(parts, taps)
    x, w, _ = _inputs(1, 30, taps, jnp.float32, parts=parts, rest=8)
    got = jax.jit(lambda x, w: linear_attention.causal_conv_silu(
        x, w, parts))(x, w)
    assert on_a_tpu == []
    for a, b in zip(got, _jnp_form(x, w, parts)):
        assert _rel(a, b) <= 1e-6
    with pytest.raises(schedule.ScheduleError):
        _kernels(x, w, parts)


def test_dispatch_keeps_the_jnp_form_on_the_cpu(monkeypatch):
    """No chip: a traced call lands on jax's default backend and an
    array says where it lives; neither takes the kernels, which would
    not compile here."""
    def never(*args, **kwargs):
        raise AssertionError("the kernels were taken on the CPU")

    monkeypatch.setattr(conv_silu_kernels, "causal_conv_silu_kernels", never)
    x, w, _ = _inputs(1, 20, 4, jnp.float32)
    assert not pallas_kernels.pallas_available()
    traced = jax.jit(lambda x, w: linear_attention.causal_conv_silu(
        x, w, PARTS))(x, w)
    eager = linear_attention.causal_conv_silu(x, w, PARTS)
    for a, b in zip(traced, eager):
        assert _rel(a, b) <= 1e-6
    # an array on the CPU decides for itself, whatever the default is
    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    linear_attention.causal_conv_silu(x, w, PARTS)
    with pytest.raises(ValueError):
        linear_attention.causal_conv_silu(x, w, (128, 128))


def test_the_registered_op_hands_on_the_parts_and_the_rest():
    x, w, _ = _inputs(2, 12, 4, jnp.float32, parts=(8, 4), rest=3)
    outs = mx.nd.causal_conv_silu(mx.nd.array(x), mx.nd.array(w),
                                  parts=(8, 4))
    assert [o.shape for o in outs] == [(2, 12, 8), (2, 12, 4), (2, 12, 3)]
    for a, b in zip(outs, _jnp_form(x, w, (8, 4))):
        np.testing.assert_allclose(a.asnumpy(), np.asarray(b), rtol=1e-6,
                                   atol=1e-6)


def test_an_unsupported_shape_never_raises_from_the_mixer(on_a_tpu):
    """Widths off the lane grid on a TPU: ``GatedDeltaNet`` runs its
    ``jax.numpy`` forms."""
    blk = contrib_nn.GatedDeltaNet(32, 2, 4, 8, 8)
    blk.initialize(mx.initializer.Xavier())
    out = blk(mx.nd.array(np.random.default_rng(0).standard_normal(
        (1, 20, 32)).astype(np.float32)))
    assert out.shape == (1, 20, 32) and on_a_tpu == []
    assert np.all(np.isfinite(out.asnumpy()))
