"""Pallas flash-attention kernel (interpret mode on CPU).

The same kernel code the TPU runs, executed by the Pallas interpreter so
numerics are CI-checked without hardware: online-softmax streaming over
K blocks with VMEM scratch accumulators.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops.pallas_kernels import flash_attention


def _qkv(B=2, H=2, T=256, D=64, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randn(B, H, T, D).astype(np.float32) * 0.3 for _ in range(3)]


def _dense(q, k, v, causal):
    return mx.nd.scaled_dot_product_attention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
        causal=causal).asnumpy()


@pytest.mark.parametrize("causal", [False, True])
def test_matches_dense(causal):
    import jax.numpy as jnp

    q, k, v = _qkv()
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=causal, interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, causal),
                               atol=1e-5)


def test_multiple_k_blocks_exercised():
    """T=512 with BLOCK_K=128 runs 4 K-steps per q block — the scratch
    carry across the innermost grid dimension is what's under test."""
    import jax.numpy as jnp

    q, k, v = _qkv(B=1, H=1, T=512, D=128, seed=3)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, True),
                               atol=1e-5)


def test_small_sequence_single_block():
    import jax.numpy as jnp

    q, k, v = _qkv(T=64, seed=1)
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, False),
                               atol=1e-5)


def test_rejects_unsupported_shapes():
    import jax.numpy as jnp

    # T=130 has no legal block: > 128 (no single block) and its only
    # divisors (65, 26, 13, ...) are off the sublane grid
    q = jnp.zeros((1, 1, 130, 64))
    with pytest.raises(ValueError):
        flash_attention(q, q, q)


def test_legalized_nondivisible_t():
    """T=200 used to be rejected (not a multiple of the hardcoded 128
    block); the centralized legalizer now picks the largest
    multiple-of-8 divisor (40) and the kernel matches dense."""
    import jax.numpy as jnp

    q, k, v = _qkv(B=1, H=1, T=200, D=32, seed=7)
    from mxnet_tpu.tune.schedule import legalize_block

    assert legalize_block(200, 128) == 40
    out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                          causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, True),
                               atol=1e-5)


def test_cross_attention_rejected():
    import jax.numpy as jnp

    q, _, _ = _qkv(T=128)
    k, _, _ = _qkv(T=512)
    with pytest.raises(ValueError, match="self-attention only"):
        flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(k))


def test_sdpa_impl_flash_contract():
    """mx.nd.scaled_dot_product_attention(impl='flash'): mask is rejected,
    and a caller that asked for the kernel gets the kernel or an error —
    on a non-TPU backend Pallas refuses to lower it; the op never runs
    the dense composition under the name 'flash'."""
    q, k, v = _qkv(T=64)
    with pytest.raises(Exception, match="mask"):
        mx.nd.scaled_dot_product_attention(
            mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), impl="flash",
            mask=mx.nd.ones((1, 1, 64, 64)))
    from mxnet_tpu.ops.pallas_kernels import pallas_available

    if not pallas_available():
        with pytest.raises(Exception, match="(?i)interpret"):
            mx.nd.scaled_dot_product_attention(
                mx.nd.array(q), mx.nd.array(k), mx.nd.array(v),
                impl="flash").asnumpy()


def test_attention_auto_selects_on_where_q_lives(monkeypatch):
    """parallel.attention(impl='auto') on inputs placed on the host takes
    the dense composition even when the process's default backend is the
    TPU (JAX_PLATFORMS=tpu,cpu + mx.cpu() inputs): the kernel cannot
    lower there, and 'auto' must not raise where dense is right."""
    from mxnet_tpu import parallel
    from mxnet_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "pallas_available", lambda: True)
    q, k, v = (mx.nd.array(a, ctx=mx.cpu(0)) for a in _qkv(T=128))
    out = parallel.attention(q, k, v, causal=True, impl="auto")
    ref = mx.nd.scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_array_equal(out.asnumpy(), ref.asnumpy())


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    """custom_vjp backward kernel vs autodiff through dense attention."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_grad

    q, k, v = _qkv(B=1, H=2, T=256, D=64, seed=5)
    D = 64

    def loss_flash(q_, k_, v_):
        out = flash_attention_with_grad(q_, k_, v_, causal=causal,
                                        interpret=True)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(D)
        if causal:
            T = q_.shape[2]
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        w = jax.nn.softmax(s, -1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", w, v_) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(jnp.asarray(q),
                                                 jnp.asarray(k),
                                                 jnp.asarray(v))
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(jnp.asarray(q),
                                                 jnp.asarray(k),
                                                 jnp.asarray(v))
    for a, b, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   err_msg=f"grad {name}")


def _dense_ref(q, k, v, causal, q_offset=0, k_offset=0):
    """float32 (out, lse, seen) with the causal mask placed by global
    offsets; ``seen`` marks the rows that see a key at all."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    keep = np.ones(s.shape[-2:], bool)
    if causal:
        qpos = q_offset + np.arange(q.shape[2])
        kpos = k_offset + np.arange(k.shape[2])
        keep = qpos[:, None] >= kpos[None, :]
        s = np.where(keep, s, -1e30)
    m = s.max(-1, keepdims=True)
    e = np.exp(s - m)
    l = e.sum(-1, keepdims=True)
    return (np.einsum("bhqk,bhkd->bhqd", e / l, v), m + np.log(l),
            keep.any(-1))


# (T, D, block_q, block_k): block_q != block_k both ways, a block of 512
# at T = 1024, and 64-wide tiles at T = 256, where a q block meets tiles
# wholly below, on and wholly above the diagonal
_TILINGS = [(256, 32, 64, 64), (256, 32, 128, 32), (256, 32, 32, 128),
            (1024, 16, 512, 512), (1024, 16, 512, 256), (200, 32, 40, 200)]


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                        ("bfloat16", 2e-2)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,d,bq,bk", _TILINGS)
def test_tilings_match_float32_reference(t, d, bq, bk, causal, dtype, atol):
    """Operands go to the MXU in the input's dtype and accumulate in
    float32: float32 inputs keep their 1e-5 agreement with the dense
    path, bf16 inputs stay within bf16's rounding of the float32
    reference computed from the same (rounded) inputs."""
    import jax.numpy as jnp

    q, k, v = (jnp.asarray(a, dtype)
               for a in _qkv(B=1, H=2, T=t, D=d, seed=t + bq))
    out, lse = flash_attention(q, k, v, causal=causal, interpret=True,
                               return_lse=True, block_q=bq, block_k=bk)
    assert out.dtype == q.dtype and out.shape == q.shape
    assert lse.dtype == jnp.float32 and lse.shape == (1, 2, t, 1)
    ref, ref_lse, _ = _dense_ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, atol=atol)
    np.testing.assert_allclose(np.asarray(lse), ref_lse,
                               atol=1e-5 if dtype == "float32" else 1e-3)


# ring-attention hops: (q_offset, k_offset) of a T = 256 block pair
_HOPS = [(512, 256),    # K/V wholly in the past: no tile is masked
         (256, 256),    # the diagonal hop
         (256, 384),    # K/V half a block ahead: rows 0..127 see nothing
         (0, 256),      # K/V wholly in the future: no live tile at all
         (0, 1024)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("bq,bk", [(256, 256), (64, 128), (128, 64)])
@pytest.mark.parametrize("q_offset,k_offset", _HOPS)
def test_hop_offsets_place_the_mask(q_offset, k_offset, bq, bk, dtype):
    """The K/V index maps are clamped by the traced offsets, not by
    q_offset == k_offset: rows that see a key match the reference; a hop
    wholly in the future comes out as zeros with lse about -1e30."""
    import jax
    import jax.numpy as jnp

    t = 256
    q, k, v = (jnp.asarray(a, dtype)
               for a in _qkv(B=1, H=2, T=t, D=32, seed=k_offset))
    hop = jax.jit(lambda q, k, v, qo, ko: flash_attention(
        q, k, v, causal=True, interpret=True, return_lse=True,
        q_offset=qo, k_offset=ko, block_q=bq, block_k=bk))
    out, lse = hop(q, k, v, jnp.int32(q_offset), jnp.int32(k_offset))
    out, lse = np.asarray(out, np.float32), np.asarray(lse)
    ref, ref_lse, seen = _dense_ref(q, k, v, True, q_offset, k_offset)
    atol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out[:, :, seen], ref[:, :, seen], atol=atol)
    np.testing.assert_allclose(lse[:, :, seen], ref_lse[:, :, seen],
                               atol=atol)
    # a row that sees nothing carries no weight into the ring's merge
    assert (lse[:, :, ~seen] < -1e29).all()
    if not seen.any():
        assert (out == 0).all()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bq,bk", [(None, None), (64, 128), (128, 32)])
def test_bf16_gradients_match_float32_reference(bq, bk, causal):
    """flash_attention_with_grad on bf16 inputs against autodiff through
    the float32 dense composition of the same inputs."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_grad

    q, k, v = (jnp.asarray(a, jnp.bfloat16)
               for a in _qkv(B=1, H=2, T=256, D=32, seed=9))

    def loss_flash(q_, k_, v_):
        out = flash_attention_with_grad(q_, k_, v_, causal=causal,
                                        interpret=True, block_q=bq,
                                        block_k=bk)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_dense(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(q_.shape[-1])
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s, -1e30)
        w = jax.nn.softmax(s, -1)
        return jnp.sum(jnp.einsum("bhqk,bhkd->bhqd", w, v_) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b, name in zip(gf, gd, "qkv"):
        assert a.dtype == jnp.bfloat16
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale,
                                   np.asarray(b) / scale, atol=2e-2,
                                   err_msg=f"grad {name}")


# The backward kernel: (B, H, T, D, causal, tile, q_offset, k_offset).
# ``tile`` is None (the default schedule), a (block_q, block_k) pair (a
# table entry, as a tuned shape gets one) or an int (``bwd_block_k=``,
# legalized). Its sequence lies along lanes, so a tile is a multiple of
# 128 or all of T. T = 384 in tiles of 128 meets tiles wholly below, on
# and wholly above the diagonal; T = 640 is a length the default's 512
# does not divide (tiles of 128); T = 33 / 65 / 200 are off the lane
# tile (one block of T); T = 520 is off it and too long for one block:
# padded to 640, tiles of 128; (2, 4, 64, 32) takes eight heads a grid
# step and (1, 2, 65, 64) two; the overrides are widths the scan before
# the kernel took (one that divides T, one under the lane tile, one that
# divides nothing); the last four are ring hops (K/V in the past, on
# the diagonal, half a sequence ahead so that rows 0..127 see nothing,
# wholly in the future)
_BWD_CASES = [
    (1, 2, 384, 32, True, (128, 128), 0, 0),
    (1, 2, 256, 64, True, (128, 256), 0, 0),
    (1, 2, 256, 64, True, (256, 128), 0, 0),
    (1, 2, 256, 32, False, (128, 128), 0, 0),
    (1, 2, 256, 32, False, (256, 128), 0, 0),
    (1, 1, 640, 32, True, None, 0, 0),
    (1, 1, 33, 32, True, None, 0, 0),
    (1, 2, 65, 64, True, None, 0, 0),
    (1, 1, 200, 32, True, None, 0, 0),
    (1, 1, 520, 32, True, None, 0, 0),
    (1, 1, 520, 32, False, None, 0, 0),
    (2, 4, 64, 32, True, None, 0, 0),
    (1, 1, 256, 128, True, (128, 128), 0, 0),
    (1, 1, 256, 256, True, (128, 128), 0, 0),
    (1, 1, 64, 256, False, None, 0, 0),
    (1, 2, 384, 32, True, 128, 0, 0),
    (1, 1, 256, 64, True, 64, 0, 0),
    (1, 1, 33, 32, True, 8, 0, 0),
    (1, 2, 256, 32, True, (128, 256), 512, 256),
    (1, 2, 256, 32, True, (256, 128), 256, 256),
    (1, 2, 256, 32, True, (128, 128), 256, 384),
    (1, 2, 256, 32, True, (128, 256), 0, 256),
]


def _bwd_tile(t, tile):
    """The tile the backward runs a case at, by the rules above."""
    from mxnet_tpu.tune import schedule

    tp = schedule.flash_bwd_length(t)
    assert tp == (640 if t == 520 else t)
    if isinstance(tile, tuple):
        return tile
    whole = 128 if t in (520, 640) else tp
    if tile is None:
        return whole, whole
    return whole, {128: 128, 64: 128, 8: 33}[tile]


def _dense_out_lse(q, k, v, causal, q_offset, k_offset):
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        qpos = q_offset + jnp.arange(q.shape[2])
        kpos = k_offset + jnp.arange(k.shape[2])
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
    lse = jax.scipy.special.logsumexp(s, -1, keepdims=True)
    return jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse), v), lse


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("b,h,t,d,causal,tile,q_offset,k_offset",
                         _BWD_CASES)
def test_backward_kernel_matches_float32_dense_autodiff(
        b, h, t, d, causal, tile, q_offset, k_offset, dtype, tol,
        tmp_path, monkeypatch):
    """dq, dk, dv of ``flash_attention_with_lse`` (the backward kernel,
    offsets as traced values, a non-zero ``dlse``) against autodiff
    through the float32 dense composition of the same inputs, each
    relative to the gradient's largest entry. A row that sees no key
    gives nothing to any gradient, whatever its cotangents are: the
    reference gets them zeroed there, the kernel does not."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_lse
    from mxnet_tpu.tune import schedule

    rng = np.random.RandomState(t + d + k_offset)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d).astype(np.float32) * 0.3,
                           dtype) for _ in range(3))
    w_out = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    w_lse = jnp.asarray(rng.randn(b, h, t, 1).astype(np.float32))
    # a per-host table of its own: the case's entry, or none (and not
    # the committed table's interpret entries either)
    table = str(tmp_path / "table.json")
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", table)
    monkeypatch.setattr(schedule, "default_table_path", lambda: table)
    override = None
    if isinstance(tile, tuple):
        schedule.put_entry(table, "flash_bwd",
                           schedule.flash_shape_key(b * h, t, d), dtype,
                           "interpret", {"block_q": tile[0],
                                         "block_k": tile[1]})
    elif tile is not None:
        override = tile
    want_tile = _bwd_tile(t, tile)
    tp = schedule.flash_bwd_length(t)
    assert schedule.flash_bwd_block(b * h, tp, d, dtype, interpret=True,
                                    block_k=override) == want_tile
    if tile is None and t <= 200:
        # short sequences: every head in one grid step, unrolled
        assert schedule.flash_bwd_heads(b * h, t, t, t, d, 4) == b * h

    def loss_flash(q_, k_, v_, qo, ko):
        out, lse = flash_attention_with_lse(
            q_, k_, v_, causal=causal, interpret=True, q_offset=qo,
            k_offset=ko, bwd_block_k=override)
        return jnp.sum(out.astype(jnp.float32) * w_out) + \
            jnp.sum(lse * w_lse)

    seen = np.ones(t, bool)
    if causal:
        seen = q_offset + np.arange(t) >= k_offset
    mask = jnp.asarray(seen, jnp.float32)[:, None]

    def loss_dense(q_, k_, v_):
        out, lse = _dense_out_lse(q_, k_, v_, causal, q_offset, k_offset)
        return jnp.sum(out * w_out * mask) + jnp.sum(lse * w_lse * mask)

    got = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(
        q, k, v, jnp.int32(q_offset), jnp.int32(k_offset))
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, ref, name in zip(got, want, "qkv"):
        assert a.dtype == q.dtype and a.shape == q.shape
        a, ref = np.asarray(a, np.float32), np.asarray(ref)
        if not seen.any():
            assert (a == 0).all(), f"grad {name} of a hop in the future"
            continue
        scale = np.abs(ref).max()
        np.testing.assert_allclose(a / scale, ref / scale, atol=tol,
                                   err_msg=f"grad {name}")


def test_backward_q_windows_add_up(monkeypatch):
    """A sequence whose dq does not fit VMEM in one piece is cut into q
    windows (a grid axis): each window's dk / dv part comes out in
    float32 and the parts are added. Forced here by a small ceiling."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_grad
    from mxnet_tpu.tune import schedule

    t, d, bq, bk = 256, 32, 128, 128
    assert schedule.flash_bwd_windows(t, bq, bk, d, 4) == 1
    assert schedule.flash_bwd_windows(65536, 512, 512, 128, 2) == 4
    q, k, v = (jnp.asarray(a) for a in _qkv(B=1, H=2, T=t, D=d, seed=4))
    monkeypatch.setitem(schedule.DEFAULT_SCHEDULES, "flash_bwd",
                        {"block_q": bq, "block_k": bk})
    monkeypatch.setenv("MXNET_TPU_AUTOTUNE", "0")

    def grads():
        return jax.grad(lambda *a: jnp.sum(flash_attention_with_grad(
            *a, causal=True, interpret=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)

    whole = grads()
    monkeypatch.setattr(
        schedule, "FLASH_VMEM_CEILING",
        schedule.flash_bwd_vmem_bytes(1, bq, bk, t // 2, d, 4) * 3 // 2)
    assert schedule.flash_bwd_windows(t, bq, bk, d, 4) == 2
    for a, b in zip(grads(), whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_several_heads_share_a_grid_step():
    """Short sequences take several heads a grid step
    (schedule.flash_fwd_heads); every head still gets its own K/V."""
    import jax.numpy as jnp

    from mxnet_tpu.tune import schedule

    q, k, v = (jnp.asarray(a) for a in _qkv(B=2, H=4, T=64, D=32, seed=2))
    assert schedule.flash_fwd_heads(8, 64, 64, 32, 4) > 1
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), _dense(q, k, v, True),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# a window: a query sees the keys 0 <= i - j < window, itself counted
# ---------------------------------------------------------------------------

def _window_ref(q, k, v, window, q_offset=0, k_offset=0):
    """float32 dense masked softmax: (out, lse, rows that see a key)."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    ahead = (q_offset + np.arange(q.shape[2]))[:, None] \
        - (k_offset + np.arange(k.shape[2]))[None, :]
    keep = (ahead >= 0) & (ahead < window)
    s = np.where(keep, s, -1e30)
    m = s.max(-1, keepdims=True)
    e = np.exp(s - m)
    l = e.sum(-1, keepdims=True)
    return (np.einsum("bhqk,bhkd->bhqd", e / l, v), m + np.log(l),
            keep.any(-1))


# (T, D, block_q, block_k, window, q_offset, k_offset): a window smaller
# than a tile, equal to one, not a multiple of one, crossing several,
# wider than the sequence, one key wide; block_q != block_k both ways; T
# off the lane grid (tiles of 40); then ring hops, the offsets traced:
# K/V a sequence behind (the window reaches 99 of its keys), on the
# diagonal with a window wider than the hop, half a sequence ahead, and
# so far behind that the window reaches nothing
_WINDOWS = [
    (256, 32, 64, 64, 40, 0, 0), (256, 32, 64, 64, 64, 0, 0),
    (256, 32, 64, 64, 100, 0, 0), (256, 32, 64, 64, 200, 0, 0),
    (256, 32, 64, 64, 300, 0, 0), (256, 32, 64, 64, 1, 0, 0),
    (256, 32, 128, 32, 70, 0, 0), (256, 32, 32, 128, 70, 0, 0),
    (200, 32, 40, 40, 77, 0, 0),
    (256, 32, 64, 64, 100, 512, 256), (256, 32, 64, 64, 300, 256, 256),
    (256, 32, 64, 64, 100, 256, 384), (256, 32, 64, 64, 100, 1024, 0)]


@pytest.mark.parametrize("dtype,atol", [("float32", 1e-5),
                                        ("bfloat16", 2e-2)])
@pytest.mark.parametrize("t,d,bq,bk,window,q_offset,k_offset", _WINDOWS)
def test_window_forward_matches_the_dense_masked_softmax(
        t, d, bq, bk, window, q_offset, k_offset, dtype, atol):
    import jax
    import jax.numpy as jnp

    q, k, v = (jnp.asarray(a, dtype)
               for a in _qkv(B=1, H=2, T=t, D=d, seed=window + bq))
    kwargs = dict(causal=True, interpret=True, return_lse=True, block_q=bq,
                  block_k=bk, window=window)
    if q_offset or k_offset:
        out, lse = jax.jit(lambda q, k, v, qo, ko: flash_attention(
            q, k, v, q_offset=qo, k_offset=ko, **kwargs))(
                q, k, v, jnp.int32(q_offset), jnp.int32(k_offset))
    else:
        out, lse = flash_attention(q, k, v, **kwargs)
    assert out.dtype == q.dtype and lse.shape == (1, 2, t, 1)
    out, lse = np.asarray(out, np.float32), np.asarray(lse)
    ref, ref_lse, seen = _window_ref(q, k, v, window, q_offset, k_offset)
    np.testing.assert_allclose(out[:, :, seen], ref[:, :, seen], atol=atol)
    np.testing.assert_allclose(lse[:, :, seen], ref_lse[:, :, seen],
                               atol=atol)
    # a row that sees nothing carries no weight into the ring's merge
    assert (lse[:, :, ~seen] < -1e29).all()
    if not seen.any():
        assert (out == 0).all()


# the backward's tiles lie on the lane grid: (T, D, (block_q, block_k) or
# None for the default, window, q_offset, k_offset). T = 200 is one block
# of T; T = 520 runs padded to 640 in tiles of 128
_BWD_WINDOWS = [
    (384, 32, (128, 128), 40, 0, 0), (384, 32, (128, 128), 128, 0, 0),
    (384, 32, (128, 128), 200, 0, 0), (512, 32, (128, 256), 300, 0, 0),
    (512, 32, (256, 128), 130, 0, 0), (384, 32, (128, 128), 1000, 0, 0),
    (200, 32, None, 77, 0, 0), (520, 32, None, 150, 0, 0),
    (256, 32, (128, 128), 100, 512, 256),
    (256, 32, (128, 128), 300, 256, 256),
    (256, 32, (128, 128), 100, 256, 384),
    (256, 32, (128, 128), 100, 1024, 0)]


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("t,d,tile,window,q_offset,k_offset", _BWD_WINDOWS)
def test_window_backward_matches_float32_dense_autodiff(
        t, d, tile, window, q_offset, k_offset, dtype, tol, tmp_path,
        monkeypatch):
    """dq, dk, dv of ``flash_attention_with_lse`` under a window (traced
    offsets, a non-zero ``dlse``) against autodiff through the float32
    dense masked softmax, each relative to the gradient's largest entry;
    rows that see no key give nothing."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_lse
    from mxnet_tpu.tune import schedule

    rng = np.random.RandomState(t + window + k_offset)
    q, k, v = (jnp.asarray(rng.randn(1, 2, t, d).astype(np.float32) * 0.3,
                           dtype) for _ in range(3))
    w_out = jnp.asarray(rng.randn(1, 2, t, d).astype(np.float32))
    w_lse = jnp.asarray(rng.randn(1, 2, t, 1).astype(np.float32))
    table = str(tmp_path / "table.json")
    monkeypatch.setenv("MXNET_TPU_SCHEDULE_TABLE", table)
    monkeypatch.setattr(schedule, "default_table_path", lambda: table)
    if tile is not None:
        # a kernel with a window is keyed apart: the entry without one
        # is not read
        tp = schedule.flash_bwd_length(t)
        schedule.put_entry(table, "flash_bwd",
                           schedule.flash_shape_key(2, tp, d, window), dtype,
                           "interpret", {"block_q": tile[0],
                                         "block_k": tile[1]})
        schedule.put_entry(table, "flash_bwd",
                           schedule.flash_shape_key(2, tp, d), dtype,
                           "interpret", {"block_q": tp, "block_k": tp})
        assert schedule.flash_bwd_block(2, tp, d, dtype, interpret=True,
                                        window=window) == tile

    def loss_flash(q_, k_, v_, qo, ko):
        out, lse = flash_attention_with_lse(
            q_, k_, v_, causal=True, interpret=True, q_offset=qo,
            k_offset=ko, window=window)
        return jnp.sum(out.astype(jnp.float32) * w_out) + \
            jnp.sum(lse * w_lse)

    ahead = (q_offset + np.arange(t))[:, None] \
        - (k_offset + np.arange(t))[None, :]
    keep = jnp.asarray((ahead >= 0) & (ahead < window))
    seen = np.asarray(keep).any(-1)
    mask = jnp.asarray(seen, jnp.float32)[:, None]

    def loss_dense(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) / np.sqrt(d)
        s = jnp.where(keep, s, -1e30)
        lse = jax.scipy.special.logsumexp(s, -1, keepdims=True)
        out = jnp.einsum("bhqk,bhkd->bhqd", jnp.exp(s - lse), v_)
        return jnp.sum(out * w_out * mask) + jnp.sum(lse * w_lse * mask)

    got = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(
        q, k, v, jnp.int32(q_offset), jnp.int32(k_offset))
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, ref, name in zip(got, want, "qkv"):
        assert a.dtype == q.dtype and a.shape == q.shape
        a, ref = np.asarray(a, np.float32), np.asarray(ref)
        if not seen.any():
            assert (a == 0).all(), f"grad {name} of a hop out of the window"
            continue
        scale = np.abs(ref).max()
        np.testing.assert_allclose(a / scale, ref / scale, atol=tol,
                                   err_msg=f"grad {name}")


def test_window_through_the_op_and_its_gradient():
    """``scaled_dot_product_attention(window=)``: the dense composition
    masks as the kernels do, and ``flash_attention_with_grad`` (static
    offsets: the grids count their steps exactly) gives its gradients."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_grad

    q, k, v = (jnp.asarray(a) for a in _qkv(B=1, H=2, T=384, D=32, seed=6))
    dense = mx.nd.scaled_dot_product_attention(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), causal=True,
        window=100).asnumpy()
    np.testing.assert_allclose(dense, _window_ref(q, k, v, 100)[0],
                               atol=1e-5)
    with pytest.raises(Exception, match="causal"):
        mx.nd.scaled_dot_product_attention(
            mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), window=100)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=100, interpret=True)

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))

    got = jax.grad(loss(lambda *a: flash_attention_with_grad(
        *a, causal=True, interpret=True, window=100)),
        argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: mx.nd.scaled_dot_product_attention(
        *(mx.nd.array(x) for x in a), causal=True, window=100).data_),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_window_grids_leave_out_the_tiles_behind_it():
    """What the schedule counts: under a window the innermost grid
    dimension is as long as the tiles a block's band can touch, exactly
    where the offsets are known to be zero; a window no narrower than
    the sequence is the causal kernel itself."""
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas_kernels
    from mxnet_tpu.tune import schedule

    # Trinity-Mini's cell: T = 8192, window 2048, tiles of 512
    assert schedule.flash_tiles(8192, 512, 512, 2048) == (70, 136)
    assert schedule.flash_tiles(8192, 512, 512) == (136, 136)
    assert schedule.flash_window_steps(8192, 512, 512, 2048, True) == 5
    assert schedule.flash_window_steps(8192, 512, 512, 2048, True,
                                       backward=True) == 5
    assert schedule.flash_window_steps(8192, 512, 512, 2048) == 6
    assert schedule.flash_window_steps(8192, 512, 256, 2048, True) == 10
    assert schedule.flash_window_steps(8192, 512, 256, 2048, True,
                                       backward=True) == 5
    assert schedule.flash_window_steps(256, 64, 64, 1000) == 4
    assert schedule.flash_shape_key(32, 8192, 128) == "bh32-t8192-d128"
    assert schedule.flash_shape_key(32, 8192, 128, 2048) \
        == "bh32-t8192-d128-w2048"
    assert pallas_kernels._window_of(None, True, 256) is None
    assert pallas_kernels._window_of(256, True, 256) is None
    assert pallas_kernels._window_of(255, True, 256) == 255
    # a hop's offsets are traced: the window stays whatever its width
    assert pallas_kernels._window_of(256, True, 256, jnp.int32(0), 0) == 256


# sha256 of the jaxprs (kernel bodies, grids and index maps inside) of
# forward + backward through flash_attention_with_grad and
# flash_attention_with_lse at the shapes below, causal and not, without a
# window, as the commit before the window made them. A caller that
# passes no window builds the kernels it always did.
_NO_WINDOW_JAXPRS = \
    "846a16198f57de12ad8f0fac2e490fb1433f44fe91705bd1c4a3c98c01c8030c"


def _no_window_digest():
    import hashlib

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import (flash_attention_with_grad,
                                              flash_attention_with_lse)

    def total(outs):
        return sum(jnp.sum(x.astype(jnp.float32)) for x in outs)

    texts = []
    for b, h, t, d, dtype in [(1, 2, 256, 64, "float32"),
                              (1, 2, 384, 32, "bfloat16"),
                              (2, 4, 64, 32, "float32"),
                              (1, 1, 520, 32, "float32")]:
        q = jnp.zeros((b, h, t, d), dtype)
        for causal in (False, True):
            texts.append(str(jax.make_jaxpr(jax.grad(
                lambda q, k, v: total([flash_attention_with_grad(
                    q, k, v, causal=causal, interpret=True)]),
                argnums=(0, 1, 2)))(q, q, q)))
            texts.append(str(jax.make_jaxpr(jax.grad(
                lambda q, k, v, qo, ko: total(flash_attention_with_lse(
                    q, k, v, causal=causal, interpret=True, q_offset=qo,
                    k_offset=ko)), argnums=(0, 1, 2)))(
                        q, q, q, jnp.int32(0), jnp.int32(0))))
    return hashlib.sha256("\n".join(texts).encode()).hexdigest()


def test_without_a_window_the_kernels_are_what_they_were(monkeypatch):
    """Holds ``window=None`` to the kernels of the commit before windows
    existed (PR 33's; the digest is ``_no_window_digest()`` run with that
    commit's ``mxnet_tpu`` on the path). A PR that changes the kernels
    on purpose computes the digest anew; one that adds an argument has
    to leave it alone. The forward rules' ``remat.KERNEL_RESIDUAL``
    names (PR 35) are equations of the surrounding jaxpr, outside every
    ``pallas_call``: with them taken out the text is that commit's."""
    from mxnet_tpu.ops import pallas_kernels

    monkeypatch.setattr(pallas_kernels, "kernel_residuals",
                        lambda *values: values)
    assert _no_window_digest() == _NO_WINDOW_JAXPRS
