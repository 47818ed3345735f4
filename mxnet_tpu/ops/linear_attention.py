"""Linear attention by the gated delta rule (Gated DeltaNet, Yang et al.
arXiv:2412.06464), and the short causal convolution in front of it.

Per value head, with a state ``S`` (Dk x Dv, float32, from zero) and,
for each token, a decay ``g_t <= 0`` and a write strength ``beta_t``:

    S = exp(g_t) S
    S = S + k_t (beta_t (v_t - S^T k_t))^T
    o_t = S^T q_t

``gated_delta_rule`` computes this in the chunked form (the upstream
``torch_chunk_gated_delta_rule``): inside a chunk of ``chunk`` tokens
the decays are a mask ``exp(cumsum g)``, the chunk's writes are the WY
factors of a unit lower-triangular solve, and the state moves once a
chunk, so a sequence of T tokens is T / chunk sequential steps of
matrix products instead of T rank-one updates.

One algorithm each, two implementations, chosen by what the code can
see. Which part of a ``GatedDeltaNet`` mixer runs where, on a TPU at
Qwen3-Next's widths: the short convolution with its SiLU
(:func:`causal_conv_silu`) as the Pallas kernels
``causal_conv_silu_fwd`` / ``causal_conv_silu_bwd``
(``ops/conv_silu_kernels.py``: one pass over the projection's output
each way, q, k and v handed on as three arrays, no slice in between);
the delta rule as the kernels below; the gates, the gated norm with z
and the projections as XLA's own fusions. Everywhere else all of it is
``jax.numpy``.

The delta rule: on a TPU, at head sizes on the lane grid
(``tune.schedule.delta_rule_shape_supported``), it runs as the Pallas
kernels ``gated_delta_rule_fwd`` and ``gated_delta_rule_bwd``
(``ops/delta_rule_kernels.py``): the state and every chunk's tensors
stay in VMEM, the triangular system is inverted by products, and the
backward pass is written, under one ``jax.custom_vjp``. Everywhere else
(the CPU, other head sizes) it is ``jax.numpy`` under one ``lax.scan``
(:func:`_chunked_delta_rule`), differentiable as it stands with the
scan's own backward pass; that form is also the kernels' oracle in the
tests, which run them in interpret mode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("causal_conv1d")
def causal_conv1d(data, weight):
    """Causal depthwise convolution along time, no bias: ``data``
    (B, T, C), ``weight`` (C, K);
    ``y[t, c] = sum_j weight[c, j] * data[t - (K - 1) + j, c]`` with
    zeros before the sequence."""
    k = weight.shape[1]
    t = data.shape[1]
    padded = jnp.pad(data, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + t, :] * weight[:, j] for j in range(k))


def _on_tpu(x):
    """Whether a computation on ``x`` lands on a TPU: where ``x``
    lives; a tracer has no device and lands on jax's default backend,
    as ``parallel.ring_attention`` reads it."""
    from .pallas_kernels import pallas_available

    if isinstance(x, jax.core.Tracer):
        return pallas_available()
    return next(iter(x.devices())).platform == "tpu"


def causal_conv_silu(data, weight, parts, bias=None):
    """``silu(causal_conv1d(data[..., :C], weight) + bias)`` handed on in
    column ``parts`` (widths that sum to C, the rows of ``weight``
    (C, K)), then the columns of ``data`` (B, T, W >= C) past C as they
    are (none: an array of no columns): ``len(parts) + 1`` arrays in
    ``data``'s dtype. ``bias`` (C,), added before the SiLU, is optional
    (None: no bias, and the same computation as a function without one).
    On a TPU with every part on the lane grid
    (``tune.schedule.conv_silu_shape_supported``) the Pallas kernels of
    ``ops/conv_silu_kernels.py``, float32 inside with one rounding;
    ``jax.numpy`` in ``data``'s dtype everywhere else."""
    from ..tune import schedule

    data, weight = jnp.asarray(data), jnp.asarray(weight)
    biases = () if bias is None else (jnp.asarray(bias),)
    parts = tuple(int(p) for p in parts)
    channels = weight.shape[0]
    if sum(parts) != channels or data.shape[-1] < channels \
            or any(b.shape != (channels,) for b in biases):
        raise ValueError(
            f"causal_conv_silu: parts {parts} of a weight {weight.shape} "
            f"over data {data.shape}"
            + "".join(f", bias {b.shape}" for b in biases))
    if _on_tpu(data) and schedule.conv_silu_shape_supported(
            parts, weight.shape[1], data.shape[-1]):
        from .conv_silu_kernels import causal_conv_silu_kernels

        extra = {"bias": biases[0]} if biases else {}
        return causal_conv_silu_kernels(data, weight, parts, **extra)
    pre = causal_conv1d(data[..., :channels], weight)
    for b in biases:
        pre = pre + b.astype(pre.dtype)
    mixed = jax.nn.silu(pre)
    firsts = [sum(parts[:n]) for n in range(1, len(parts))]
    return tuple(jnp.split(mixed, firsts, axis=-1)) \
        + (data[..., channels:],)


@register("causal_conv_silu", num_outputs=lambda p: len(p["parts"]) + 1)
def _causal_conv_silu_op(data, weight, *bias, parts):
    """:func:`causal_conv_silu` as an operator: the arrays positional
    (``data``, ``weight`` and, where there is one, ``bias``), ``parts``
    a keyword."""
    return causal_conv_silu(data, weight, parts, *bias)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _chunked(x, n, c, hk):
    """(B, N * C, Hk * R, ...) -> (B, Hk, R, N, C, ...)."""
    x = x.reshape((x.shape[0], n, c, hk, x.shape[2] // hk) + x.shape[3:])
    return jnp.moveaxis(x, (3, 4), (1, 2))


def _kernels_take(v, dk, chunk):
    """Whether this call runs as the Pallas kernels: its computation
    lands on a TPU (:func:`_on_tpu`) and the shape has a legal
    schedule."""
    from ..tune import schedule

    return _on_tpu(v) and schedule.delta_rule_shape_supported(
        dk, v.shape[-1], chunk)


@register("gated_delta_rule")
def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """``q``, ``k`` (B, T, Hk, Dk), ``v`` (B, T, Hv, Dv), ``g`` and
    ``beta`` (B, T, Hv) -> (B, T, Hv, Dv) in ``v``'s dtype. ``q`` and
    ``k`` are L2-normalised over the head here (eps 1e-6), ``q`` scaled
    by Dk^-0.5; each q/k head serves Hv / Hk value heads (value head h
    reads key head h // (Hv / Hk)). ``g`` is the log of the decay,
    ``beta`` in (0, 1). T need not be a multiple of ``chunk``. Decays,
    the solve, the state and every accumulation are float32; the
    operands of the products that do not come out of the solve keep
    ``v``'s dtype (under a bf16 policy that is what the MXU takes of
    them anyway). Kernels or ``jax.numpy``: the module's docstring."""
    q, k, v, g, beta = (jnp.asarray(x) for x in (q, k, v, g, beta))
    if _kernels_take(v, k.shape[-1], chunk):
        from .delta_rule_kernels import gated_delta_rule_kernels

        return gated_delta_rule_kernels(q, k, v, g, beta, chunk=chunk)
    return _chunked_delta_rule(q, k, v, g, beta, chunk)


def _chunked_delta_rule(q, k, v, g, beta, chunk):
    """:func:`gated_delta_rule` in ``jax.numpy`` under one ``lax.scan``
    over the chunks."""
    f32 = jnp.float32
    b, t, hv, dv = v.shape
    hk, dk = k.shape[2:]
    rep = hv // hk
    q = _l2norm(q.astype(f32)) * dk ** -0.5
    k = _l2norm(k.astype(f32))
    vf, g, beta = v.astype(f32), g.astype(f32), beta.astype(f32)
    pad = (-t) % chunk
    if pad:     # padded tokens write nothing (beta 0) and do not decay
        q, k, vf, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, vf, g, beta))
    n = (t + pad) // chunk
    # key-head tensors (B, Hk, 1, N, C, ..) against value-head tensors
    # (B, Hk, Hv / Hk, N, C, ..): the key heads broadcast, never repeat
    q, k, vf, g, beta = (_chunked(x, n, chunk, hk)
                         for x in (q, k, vf, g, beta))  # g: (B, Hk, R, N, C)

    gc = jnp.cumsum(g, axis=-1)
    rows = jnp.arange(chunk)
    lower = rows[:, None] >= rows[None, :]
    # decay from token j to token i of one chunk, 0 above the diagonal
    # (masked before the exp: the differences there are positive)
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    mm = v.dtype
    kk = jnp.einsum("bhrnid,bhrnjd->bhrnij", k.astype(mm), k.astype(mm),
                    preferred_element_type=f32)
    qk = jnp.einsum("bhrnid,bhrnjd->bhrnij", q.astype(mm), k.astype(mm),
                    preferred_element_type=f32)
    strict = rows[:, None] > rows[None, :]
    system = jnp.where(strict, kk * beta[..., None] * decay, 0.0) \
        + jnp.eye(chunk, dtype=f32)
    # the WY factors: (I + A) [u, w] = [beta v, beta k exp(gc)]
    rhs = jnp.concatenate(
        [vf * beta[..., None], k * (beta * jnp.exp(gc))[..., None]], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(
        system, rhs, lower=True, unit_diagonal=True)
    u, w = solved[..., :dv], solved[..., dv:]
    local = qk * decay
    q_in = (q * jnp.exp(gc)[..., None]).astype(mm)    # reads entering state
    k_out = (k * jnp.exp(gc[..., -1:] - gc)[..., None]).astype(mm)
    g_end = jnp.exp(gc[..., -1])                      # (B, Hk, R, N)

    def step(state, xs):
        u_i, w_i, local_i, q_i, k_i, g_i = xs
        v_new = u_i - jnp.einsum("bhrck,bhrkv->bhrcv", w_i, state)
        out = jnp.einsum("bhrck,bhrkv->bhrcv", q_i, state,
                         preferred_element_type=f32) \
            + jnp.einsum("bhrij,bhrjv->bhriv", local_i, v_new)
        state = state * g_i[..., None, None] \
            + jnp.einsum("bhrck,bhrcv->bhrkv", k_i, v_new,
                         preferred_element_type=f32)
        return state, out

    _, out = jax.lax.scan(
        step, jnp.zeros((b, hk, rep, dk, dv), f32),
        tuple(jnp.moveaxis(x, 3, 0)
              for x in (u, w, local, q_in, k_out, g_end)))
    out = jnp.moveaxis(out, 0, 3).reshape(b, hv, n * chunk, dv)
    return out[:, :, :t].transpose(0, 2, 1, 3).astype(v.dtype)
