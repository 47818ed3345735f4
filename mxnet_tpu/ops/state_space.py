"""The selective state-space scan of Mamba-2 (Dao & Gu, arXiv:2405.21060),
in chunks.

Per head, with a state ``S`` (head_dim x state, float32, from zero) and,
for each token, a step ``dt_t >= 0``:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t

``A < 0`` and ``D`` are one number a head; ``B`` and ``C`` come in
groups, each serving ``heads / groups`` heads (head h reads group
``h // (heads / groups)``).

:func:`mamba_chunk_scan` computes this in the chunked form (the
upstream ``ssd_minimal`` / ``mamba_chunk_scan_combined``): inside a
chunk of ``chunk`` tokens the decays are a mask ``exp(cumsum(dt A))``
and the outputs a masked product ``(C B^T . decay . dt) x``; the state
enters each chunk once, read by ``C``, and leaves it once, written by
the chunk's ``B`` and ``x``. A sequence of T tokens is then T / chunk
sequential steps of matrix products instead of T rank-one updates.

One ``lax.scan`` over the chunks, in ``jax.numpy`` on every backend: the
state and every decay are float32; the operands of the products keep
``x``'s dtype (under a bf16 policy what the MXU takes of them anyway),
accumulated in float32. The scan's body is checkpointed, so its
backward pass keeps the state entering each chunk and recomputes the
chunk's own (chunk x chunk) matrices, one chunk at a time.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("mamba_chunk_scan")
def mamba_chunk_scan(x, dt, A, B, C, D, chunk=128):
    """``x`` (Bt, T, H, P), ``dt`` (Bt, T, H) (after its softplus),
    ``A`` and ``D`` (H,), ``B`` and ``C`` (Bt, T, G, N) with G dividing
    H -> ``y`` (Bt, T, H, P) in ``x``'s dtype, by the recurrence of the
    module's docstring. T need not be a multiple of ``chunk``: padded
    tokens have ``dt`` 0, so they neither decay nor write the state.
    Differentiable in every input."""
    f32 = jnp.float32
    x, dt, A, B, C, D = (jnp.asarray(a) for a in (x, dt, A, B, C, D))
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"mamba_chunk_scan: {h} heads in {g} groups")
    r, mm = h // g, x.dtype
    dt = dt.astype(f32)
    a = dt * A.astype(f32)                  # the log of each token's decay
    pad = (-t) % chunk
    xs = [x, dt, a, B, C]
    if pad:
        xs = [jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
              for v in xs]
    nc = (t + pad) // chunk

    def chunked(v, per_head):
        """(Bt, T', ...) -> (chunks, Bt, chunk, G[, R], ...)."""
        shape = (b, nc, chunk, g, r) + v.shape[3:] if per_head \
            else (b, nc, chunk) + v.shape[2:]
        return jnp.moveaxis(v.reshape(shape), 1, 0)

    xc, dtc, ac = (chunked(v, True) for v in xs[:3])
    bc, cc = (chunked(v, False) for v in xs[3:])
    rows = jnp.arange(chunk)
    lower = (rows[:, None] >= rows[None, :])[None, :, :, None, None]

    def one_chunk(state, inputs):
        """state (Bt, G, R, P, N) f32 entering the chunk -> (state
        leaving it, the chunk's y (Bt, L, G, R, P) f32)."""
        x_c, dt_c, a_c, b_c, c_c = inputs
        acs = jnp.cumsum(a_c, axis=1)                       # (Bt, L, G, R)
        # decay from token s to token l of the chunk, 0 above the
        # diagonal (masked before the exp: the differences there are
        # positive)
        decay = jnp.exp(jnp.where(lower, acs[:, :, None] - acs[:, None, :],
                                  -jnp.inf))                # (Bt, L, S, G, R)
        cb = jnp.einsum("blgn,bsgn->blsg", c_c.astype(mm), b_c.astype(mm),
                        preferred_element_type=f32)
        mixed = cb[..., None] * decay * dt_c[:, None]
        y = jnp.einsum("blsgr,bsgrp->blgrp", mixed.astype(mm),
                       x_c.astype(mm), preferred_element_type=f32)
        y = y + jnp.einsum("blgn,bgrpn->blgrp", c_c.astype(mm),
                           state.astype(mm), preferred_element_type=f32) \
            * jnp.exp(acs)[..., None]
        last = acs[:, -1]                                   # (Bt, G, R)
        written = x_c.astype(f32) \
            * (jnp.exp(last[:, None] - acs) * dt_c)[..., None]
        state = state * jnp.exp(last)[..., None, None] + jnp.einsum(
            "bsgn,bsgrp->bgrpn", b_c.astype(mm), written.astype(mm),
            preferred_element_type=f32)
        return state, y

    _, y = jax.lax.scan(
        jax.checkpoint(one_chunk, prevent_cse=False),
        jnp.zeros((b, g, r, p, n), f32), (xc, dtc, ac, bc, cc))
    y = jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, p)[:, :t]
    return (y + D.astype(f32)[:, None] * x.astype(f32)).astype(mm)
