"""Ring attention — sequence/context parallelism over the 'sp' mesh axis.

The long-context capability the north star calls for (absent in the
reference, whose longest-sequence tool is BucketingModule — SURVEY.md
§2.3): the sequence axis is sharded over the mesh, each device holds one
block of Q/K/V, and K/V blocks rotate around the ring via
`lax.ppermute` while each device accumulates its queries' attention with
a numerically-stable online (flash-style) softmax. Peak memory per device
is O(T_local^2) instead of O(T^2), compute overlaps with the ICI
transfers, and the whole thing is one jitted SPMD program —
reverse-mode AD through the loop comes from jax for free.

Usage (global arrays, T sharded over 'sp')::

    mesh = parallel.create_mesh({"sp": 8})
    out = parallel.ring.ring_attention(q, k, v, mesh=mesh, causal=True)

`ring_attention_inner` is the raw per-shard function for embedding inside
a larger shard_map'd training step.
"""
from __future__ import annotations

import functools

import numpy as _np

__all__ = ["ring_attention", "ring_attention_inner", "attention"]

_NEG = -1e30


def ring_attention_inner(q, k, v, axis_name="sp", causal=False, scale=None,
                         impl="dense", interpret=False):
    """Per-shard ring attention body (call inside shard_map).

    q, k, v: (B, H, T_local, D) — this device's sequence block. Returns
    (B, H, T_local, D) attention output for the local queries over the
    GLOBAL sequence.

    impl='dense' materializes the per-hop (T_local, T_local) score block;
    impl='flash' runs each hop through the Pallas streaming kernel
    (ops/pallas_kernels.py) with global positional offsets, dropping
    per-device attention memory from O(T_local²) to O(T_local·BLOCK_K) —
    the two kernels composed. Hop results merge by log-sum-exp, and the
    kernel's custom_vjp carries the lse cotangent, so reverse-mode AD
    through the ring works for both implementations.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    b, h, t, d = q.shape
    s_scale = scale if scale is not None else 1.0 / _np.sqrt(d)
    axis_size = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)

    q32 = q.astype(jnp.float32)
    # derive the accumulators from q so they inherit its full
    # varying-manual-axes type (dp, sp, ...) — fresh constants would make
    # the fori_loop carry type diverge from the rotating K/V blocks
    m0 = q32[..., :1] * 0 + _NEG
    l0 = q32[..., :1] * 0
    o0 = q32 * 0
    qpos = my_idx * t + jnp.arange(t)

    if impl == "flash":
        from ..ops.pallas_kernels import flash_attention_with_lse

        def body(i, carry):
            m, w, o, kc, vc = carry
            # axis_index must be (re)taken INSIDE the loop body: a value
            # closed over from outside becomes a while-body constant, and
            # under check_vma=False jax re-materializes it as a
            # PartitionId HLO, which SPMD partitioning rejects
            # ("UNIMPLEMENTED: PartitionId instruction is not supported").
            my = lax.axis_index(axis_name)
            src = (my - i) % axis_size
            # per-hop streaming kernel: normalized block output + its lse
            out_i, lse_i = flash_attention_with_lse(
                q, kc, vc, causal=causal, scale=s_scale,
                interpret=interpret, q_offset=my * t,
                k_offset=src * t)
            # merge normalized hop results by log-sum-exp weight
            lse32 = lse_i.astype(jnp.float32)
            m_new = jnp.maximum(m, lse32)
            corr = jnp.exp(m - m_new)
            wi = jnp.exp(lse32 - m_new)
            o_new = o * corr + wi * out_i.astype(jnp.float32)
            w_new = w * corr + wi
            perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)
            return m_new, w_new, o_new, kc, vc

        m, w, o, _, _ = lax.fori_loop(0, axis_size, body, (m0, l0, o0, k, v))
        return (o / jnp.maximum(w, 1e-20)).astype(q.dtype)

    def body(i, carry):
        m, l, o, kc, vc = carry
        # the K/V block currently held arrived from device (my_idx - i)
        src = (my_idx - i) % axis_size
        logits = jnp.einsum("bhqd,bhkd->bhqk", q32,
                            kc.astype(jnp.float32)) * s_scale
        if causal:
            kpos = src * t + jnp.arange(t)
            mask = qpos[:, None] >= kpos[None, :]
            logits = jnp.where(mask, logits, _NEG)
        blk_max = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, blk_max)
        p = jnp.exp(logits - m_new)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o_new = o * corr + jnp.einsum("bhqk,bhkd->bhqd", p,
                                      vc.astype(jnp.float32))
        # rotate K/V one hop around the ring (overlaps with next block's
        # compute under XLA's async collectives)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return m_new, l_new, o_new, kc, vc

    m, l, o, _, _ = lax.fori_loop(0, axis_size, body, (m0, l0, o0, k, v))
    return (o / jnp.maximum(l, 1e-20)).astype(q.dtype)


@functools.lru_cache(maxsize=64)
def _ring_fn(mesh, axis_name, causal, scale, impl, interpret,
             sched_tag=""):
    """One jitted SPMD program per config — re-built closures would defeat
    jax.jit's identity-keyed cache and recompile on every call.
    ``sched_tag`` is the schedule-table digest (tune.table_digest()): the
    per-hop flash kernel resolves its blocks from the table at trace
    time, so a table change must re-key this cache instead of serving a
    program built under the old schedule."""
    import jax
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis_name, None)
    inner = functools.partial(ring_attention_inner, axis_name=axis_name,
                              causal=causal, scale=scale, impl=impl,
                              interpret=interpret)
    from .mesh import shard_map

    # pallas_call outputs carry no varying-mesh-axes (vma) annotation, so
    # the flash path runs with the replication/vma type check off
    return jax.jit(shard_map(inner, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec, check=(impl != "flash")))


def _pick_impl(impl, t_local, d, on_tpu):
    """An explicit ``impl`` is honoured as asked (the kernel raises on a
    shape or device it cannot serve). ``'auto'`` selects from what it can
    observe: the Pallas kernel when the computation runs on TPU devices
    and the shape has a legal schedule, the dense composition otherwise."""
    from ..tune import schedule as _tune_schedule

    if impl != "auto":
        return impl
    if on_tpu and _tune_schedule.flash_shape_supported(t_local, d):
        return "flash"
    return "dense"


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False,
                   scale=None, impl="auto", interpret=False):
    """Sequence-parallel attention over global arrays.

    q, k, v: (B, H, T, D) NDArrays or jax arrays with T divisible by the
    mesh's `axis_name` size. The sequence axis is sharded over the ring;
    output has the same global shape/sharding.

    impl: 'dense' | 'flash' | 'auto'. 'flash' streams each hop through
    the Pallas kernel (O(T_local·BLOCK_K) memory per device) or raises;
    'auto' picks flash on TPU when shapes allow, dense otherwise.
    ``interpret=True`` (tests) runs the kernel in Pallas interpret mode.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from .mesh import create_mesh

    if mesh is None:
        mesh = create_mesh({axis_name: len(jax.devices())})
    if axis_name not in mesh.shape:
        raise ValueError(f"mesh {dict(mesh.shape)} has no {axis_name!r} "
                         "axis; build it with parallel.create_mesh("
                         f"{{'{axis_name}': n}})")
    raw = [a._data if hasattr(a, "_data") else jnp.asarray(a)
           for a in (q, k, v)]
    t = raw[0].shape[2]
    n = mesh.shape[axis_name]
    if t % n != 0:
        raise ValueError(f"sequence length {t} not divisible by "
                         f"{axis_name} size {n}")
    chosen = _pick_impl(impl, t // n, raw[0].shape[3],
                        mesh.devices.flat[0].platform == "tpu")
    spec = P(None, None, axis_name, None)
    from ..tune import schedule as _tune_schedule

    # fingerprint_token (not table_digest): the MXNET_TPU_AUTOTUNE kill
    # switch collapses the token to '' exactly like the AOT cache key,
    # so flipping it re-keys the cached jitted program too
    fn = _ring_fn(mesh, axis_name, causal, scale, chosen, bool(interpret),
                  _tune_schedule.fingerprint_token()
                  if chosen == "flash" else "")
    arrs = [jax.device_put(a, NamedSharding(mesh, spec)) for a in raw]
    out = fn(*arrs)
    if hasattr(q, "_data"):
        from ..ndarray.ndarray import NDArray

        return NDArray(out, getattr(q, "_ctx", None))
    return out


def attention(q, k, v, causal=False, scale=None, mesh=None,
              axis_name="sp", impl="auto", interpret=False):
    """Unified attention entry: picks dense / flash / ring by shape+mesh.

    - a mesh with an `axis_name` axis of size > 1 -> ring attention
      (sequence parallel; per-hop kernel chosen by `impl`)
    - single device, flash-compatible shape on TPU -> Pallas flash kernel
    - otherwise -> the fused XLA dense composition
      (ops/nn.py scaled_dot_product_attention)
    """
    import jax.numpy as jnp

    if mesh is not None and mesh.shape.get(axis_name, 1) > 1:
        return ring_attention(q, k, v, mesh=mesh, axis_name=axis_name,
                              causal=causal, scale=scale, impl=impl,
                              interpret=interpret)
    import jax

    raw_q = q._data if hasattr(q, "_data") else jnp.asarray(q)
    b, h, t, d = raw_q.shape
    from ..ops.pallas_kernels import flash_attention_with_grad, \
        pallas_available

    # where q lives decides, as the mesh does for the ring; a tracer has
    # no device, and its computation lands on jax's default backend
    if isinstance(raw_q, jax.core.Tracer):
        on_tpu = pallas_available()
    else:
        on_tpu = next(iter(raw_q.devices())).platform == "tpu"
    if _pick_impl(impl, t, d, on_tpu) == "flash":
        return flash_attention_with_grad(q, k, v, causal=causal,
                                         scale=scale, interpret=interpret)
    if hasattr(q, "_data"):
        from .. import ndarray as nd

        return nd.scaled_dot_product_attention(q, k, v, causal=causal,
                                               scale=scale)
    from ..ops.nn import _sdpa

    return _sdpa(raw_q, jnp.asarray(k), jnp.asarray(v), causal=causal,
                 scale=scale)
