"""The grouped-product kernels (``ops/grouped_matmul_kernels.py``) in
interpret mode on the CPU: the output and the gradients of lhs and rhs
against ``jax.lax.ragged_dot``, which they replace on the chip, at
widths off the lane grid, empty groups, one group of every row, group
boundaries inside a row tile and rows past the groups prefilled with
NaN; tiles that are a schedule and not a result; the expert layer's
scan branch through them; and the dispatch of ``ops.moe`` between the
two."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.observability import trace
from mxnet_tpu.ops import grouped_matmul_kernels as gmk
from mxnet_tpu.ops import moe, pallas_kernels
from mxnet_tpu.tune import schedule


def _operands(m, k, n, sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs = jnp.asarray(rng.standard_normal((m, k)) * k ** -0.5, dtype)
    rhs = jnp.asarray(rng.standard_normal((sizes.shape[0], k, n)), dtype)
    cot = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    return lhs, rhs, sizes, cot


def _through_selects(fn, lhs, rhs, sizes, cot):
    """(out, dlhs, drhs) of ``fn`` as ``ops.moe._block_of_rows`` uses it:
    the rows past the groups selected to zero going in and going out."""
    valid = (jnp.arange(lhs.shape[0]) < jnp.sum(sizes))[:, None]

    def loss(lhs, rhs):
        out = jnp.where(valid, fn(jnp.where(valid, lhs, 0), rhs, sizes), 0)
        return jnp.sum(out.astype(jnp.float32) * cot), out

    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1),
                                         has_aux=True)(lhs, rhs)
    return (out,) + grads


def _kernels(**tiles):
    return lambda lhs, rhs, sizes: gmk.grouped_matmul_kernels(
        lhs, rhs, sizes, interpret=True, **tiles)


def _rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want))
                 / max(np.max(np.abs(want)), 1e-30))


def _assert_close(got, want, bar):
    for name, a, b in zip(("out", "dlhs", "drhs"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _rel(a, b) <= bar, (name, _rel(a, b))


# (m, k, n, group sizes, tiles (tm, tk, tn)): Nemotron-H's widths 2688 /
# 1856 over 16 (168 / 116: ragged last tiles of the contraction and of
# the columns, both ways round), an empty group and rows past the
# groups, every row in one group, boundaries inside row tiles of 32,
# groups on tile boundaries, no row held at all
_CASES = [
    (64, 168, 116, (10, 0, 20, 5), (16, 128, 128)),
    (64, 116, 168, (10, 0, 20, 5), (32, 128, 128)),
    (64, 168, 116, (64, 0, 0, 0), (32, 128, 128)),
    (64, 168, 116, (0, 0, 0, 64), (16, 256, 128)),
    (96, 40, 24, (7, 30, 0, 25, 0), (32, 128, 128)),
    (96, 40, 24, (32, 32, 0, 16), (32, 128, 128)),
    (48, 40, 24, (0, 0, 0), (16, 128, 128)),
]


@pytest.mark.parametrize("dtype,bar", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("m,k,n,sizes,tiles", _CASES)
def test_kernels_match_ragged_dot(m, k, n, sizes, tiles, dtype, bar):
    """Output and both gradients, through the selects of the expert
    layer, against ``ragged_dot``'s (float32 in both cases: bf16
    operands, each result rounded once), in the operands' dtype."""
    lhs, rhs, sizes, cot = _operands(m, k, n, sizes, dtype, seed=m + k)
    got = _through_selects(
        _kernels(tiles=tiles, bwd_tiles=tiles, wgrad_tiles=tiles),
        lhs, rhs, sizes, cot)
    want = _through_selects(
        jax.lax.ragged_dot, lhs.astype(jnp.float32),
        rhs.astype(jnp.float32), sizes, cot)
    _assert_close(got, [w.astype(dtype) for w in want], bar)


@pytest.mark.parametrize("m,k,n,sizes,tiles", _CASES[:5])
def test_rows_past_the_groups_never_reach_a_sum(m, k, n, sizes, tiles):
    """Rows past the groups prefilled with NaN, in lhs and in the
    output's gradient: the weight gradient never reads them (finite,
    and the same as with zeros there), and the output and lhs's gradient
    on the groups' rows are what they are with zeros there."""
    lhs, rhs, sizes, cot = _operands(m, k, n, sizes, jnp.float32, seed=3)
    held = int(jnp.sum(sizes))
    past = (jnp.arange(m) >= held)[:, None]
    f = _kernels(tiles=tiles, bwd_tiles=tiles, wgrad_tiles=tiles)

    def run(lhs, cot):
        out, vjp = jax.vjp(lambda a, b: f(a, b, sizes), lhs, rhs)
        return (out,) + vjp(cot)

    nan_cot = jnp.where(past, jnp.nan, cot)
    got = run(jnp.where(past, jnp.nan, lhs), nan_cot)
    want = run(jnp.where(past, 0, lhs), jnp.where(past, 0, cot))
    assert np.isfinite(np.asarray(got[2])).all()
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-6, atol=1e-6)
    for a, b in zip(got[:2], want[:2]):
        np.testing.assert_allclose(np.asarray(a[:held]), np.asarray(b[:held]),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tiles", [(16, 128, 128), (32, 256, 128),
                                   (64, 4096, 4096), (16, 128, 256)])
def test_tiles_are_a_schedule_not_a_result(tiles):
    lhs, rhs, sizes, cot = _operands(64, 300, 200, (9, 23, 0, 17), jnp.float32,
                                     seed=5)
    want = _through_selects(_kernels(), lhs, rhs, sizes, cot)
    got = _through_selects(
        _kernels(tiles=tiles, bwd_tiles=tiles, wgrad_tiles=tiles),
        lhs, rhs, sizes, cot)
    _assert_close(got, want, 2e-6)


def test_tiles_are_legalized_to_the_shape():
    tiles = schedule.grouped_mm_tiles
    default = schedule.DEFAULT_SCHEDULES["grouped_mm"]
    assert tiles("grouped_mm", 8192, 2048, 4096, 8, "bfloat16") == (
        default["tm"], min(2048, default["tk"]), min(4096, default["tn"]))
    assert schedule.grouped_mm_shape_key(8192, 1856, 2688, 8) == \
        "m8192-k1856-n2688-g8"
    assert schedule.grouped_mm_shape_key(8192, 1856, 2688, 8, True) == \
        "m8192-k1856-n2688-g8-rt"
    # rows: a divisor of the block on the 16-row grid; widths in whole
    # lane tiles, as few as the tile asked for allows, the last ragged
    assert tiles("grouped_mm", 96, 40, 24, 4, "float32", tm=64, tk=128,
                 tn=128) == (48, 128, 128)
    assert tiles("grouped_mm_t", 8192, 2688, 1856, 8, "bfloat16", tm=512,
                 tk=1000, tn=4096) == (512, 896, 1920)
    assert tiles("grouped_mm", 8192, 1856, 2688, 8, "bfloat16", tm=256,
                 tk=1024, tn=512) == (256, 1024, 512)
    assert schedule.grouped_mm_row_tiles(8192, 512, 8) == 23
    assert schedule.grouped_mm_shape_supported(8192)
    assert not schedule.grouped_mm_shape_supported(40)
    assert schedule.validate_table({
        "schema_version": schedule.SCHEMA_VERSION, "entries": {
            "grouped_mm|tpu|bfloat16|m8192-k1856-n2688-g8-rt": {
                "schedule": {"tm": 256, "tk": 1856, "tn": 512}},
            "grouped_mm_t|tpu|bfloat16|m8192-k2688-n1856-g8": {
                "schedule": {"tm": 512, "tk": 1024, "tn": 1024}}}}) == []
    with pytest.raises(schedule.ScheduleError):
        gmk.grouped_matmul(jnp.zeros((40, 8)), jnp.zeros((2, 8, 8)),
                           jnp.asarray([20, 20]), interpret=True)


def test_a_built_kernel_records_a_build_span():
    was = trace.enabled()
    trace.set_enabled(True)
    try:
        trace.clear()
        gmk._build.cache_clear()
        lhs, rhs, sizes, cot = _operands(64, 40, 24, (30, 0, 20),
                                         jnp.float32)
        for _ in range(2):      # the builders are cached: noted once
            jax.vjp(_kernels(tiles=(16, 128, 128)), lhs, rhs,
                    sizes)[1](jnp.ones((64, 24)))
        spans = [s["attrs"] for s in trace.spans()
                 if s["name"] == "kernel.build"]
    finally:
        trace.set_enabled(was)
        trace.clear()
    assert [(s["kernel"], s["transpose_rhs"]) for s in spans] == [
        ("grouped_matmul", False), ("grouped_matmul", True),
        ("grouped_matmul_t", False)]
    assert (spans[0]["m"], spans[0]["k"], spans[0]["n"], spans[0]["groups"]) \
        == (64, 40, 24, 3)
    assert (spans[0]["tm"], spans[0]["tk"], spans[0]["tn"]) == (16, 128, 128)
    assert spans[0]["row_tiles"] == 64 // 16 + 3 - 1


# ---------------------------------------------------------------- dispatch

@pytest.fixture
def on_a_tpu(monkeypatch):
    """What the dispatch sees on the chip, with the kernels it then
    takes run in interpret mode and counted."""
    calls = []
    real = gmk.grouped_matmul_kernels

    def interpreted(lhs, rhs, sizes, **kwargs):
        calls.append(lhs.shape)
        return real(lhs, rhs, sizes, **dict(kwargs, interpret=True))

    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    monkeypatch.setattr(gmk, "grouped_matmul_kernels", interpreted)
    return calls


def _expert_layer(tokens, d, inner, n_experts, held, top_k, activation,
                  seed=0):
    """An expert layer's inputs, the router's bias putting every choice
    of every token on the held experts (the scan over blocks runs)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((n_experts, d)) * d ** -0.5,
                         jnp.float32)
    bias = jnp.zeros(n_experts).at[:held].set(30.0)
    weights, experts = moe.moe_router(x, router, bias, top_k=top_k)
    up = inner if activation == "relu2" else 2 * inner
    gate_up = jnp.asarray(rng.standard_normal((held, d, up)) * d ** -0.5,
                          jnp.float32)
    down = jnp.asarray(rng.standard_normal((held, inner, d)) * inner ** -0.5,
                       jnp.float32)
    return x, weights, experts, gate_up, down


def _layer_grads(x, weights, experts, gate_up, down, activation):
    counts = jnp.zeros(gate_up.shape[0] + 1)

    def loss(x, weights, gate_up, down):
        out = moe.moe_experts(x, weights, experts, gate_up, down, counts,
                              activation=activation)[0]
        return jnp.sum(jnp.sin(out)), out

    (_, out), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True))(x, weights, gate_up, down)
    return (out,) + grads


@pytest.mark.parametrize("activation", ["relu2", "swiglu"])
def test_the_scan_branch_takes_the_kernels(on_a_tpu, activation):
    """Every choice held: the layer's scan over blocks (each checkpointed
    around its skip) runs the kernels, with the same output and
    gradients as ``ragged_dot`` gives."""
    args = _expert_layer(32, 40, 24, 8, 4, 3, activation)
    assert int(jnp.sum(args[2] < 4)) == 96      # three blocks of 32 rows
    got = _layer_grads(*args, activation)
    assert on_a_tpu and all(shape[0] == 32 for shape in on_a_tpu)
    calls = len(on_a_tpu)
    on_a_tpu.clear()
    with pytest.MonkeyPatch.context() as off_the_chip:
        off_the_chip.setattr(pallas_kernels, "pallas_available",
                             lambda: False)
        want = _layer_grads(*args, activation)
    assert calls >= 2 and not on_a_tpu
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        assert _rel(a, b) <= 2e-6


def test_dispatch_keeps_ragged_dot_off_the_row_grid(on_a_tpu):
    """A block of rows off the 16-row grid (40 tokens) runs
    ``ragged_dot`` on a TPU."""
    args = _expert_layer(40, 24, 16, 8, 4, 2, "relu2", seed=1)
    _layer_grads(*args, "relu2")
    assert on_a_tpu == []


def test_dispatch_keeps_ragged_dot_on_the_cpu(monkeypatch):
    def never(*args, **kwargs):
        raise AssertionError("the kernels were taken on the CPU")

    monkeypatch.setattr(gmk, "grouped_matmul_kernels", never)
    assert not pallas_kernels.pallas_available()
    args = _expert_layer(32, 40, 24, 8, 4, 3, "relu2")
    out = _layer_grads(*args, "relu2")
    assert all(np.isfinite(np.asarray(a)).all() for a in out)
