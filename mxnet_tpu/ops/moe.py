"""A sparse expert layer's two parts: the router over all experts, and
the grouped matrix products over the experts this device holds.

An expert-parallel group divides a layer's experts over its devices.
Every device routes over ALL the experts (the router keeps its
published width and its experts per token) and computes the part of the
result that its own experts give, for the tokens routed to them; the
exchange between devices adds the parts up. ``moe_experts`` is that
part: told the first expert it holds (it holds as many as its weights
have rows), it sorts the (token, expert) assignments that fall on its
experts, runs grouped matrix products over the sorted rows with the
per-expert counts as group sizes (:func:`_grouped`), and adds the
weighted rows back to their tokens. No token is dropped for any routing: there is no capacity
factor. Shapes are static, so the sorted rows are taken as many at a
time as there are tokens: one such block serves while the assignments
held fit it (the usual case: a ``lax.cond`` on the count), and a
``lax.scan`` over the blocks of the worst case (every choice of every
token held here), each recomputed in the backward pass and skipped
when it holds no assignment, otherwise. Both are compiled, one runs,
and neither keeps more than a block's intermediates. The scan's
per-block checkpoint encloses the skip, so what the scan keeps for the
backward pass is what the checkpoint closes over (the tokens, the
expert weights, the sorted order), loop-invariant and kept once, never
a copy per block: a ``lax.cond``'s branches all return the residuals
of every branch, so per-block copies would be zeros that the single
block, where it runs, writes and nothing reads.

The grouped products, ``jax.lax.ragged_dot``'s mathematics: on a TPU,
for a block whose rows lie on the sublane grid
(``tune.schedule.grouped_mm_shape_supported``), the Pallas kernels
``grouped_matmul`` / ``grouped_matmul_t`` of
``ops/grouped_matmul_kernels.py`` under one ``jax.custom_vjp``, tiles
per shape from the schedule table: their grid visits only the row tiles
that hold a group's rows. Everywhere else ``jax.lax.ragged_dot``
itself. Either leaves the rows past the assignments held undefined,
and ``_block_of_rows`` selects them away.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("moe_router", num_outputs=2)
def moe_router(data, weight, bias=None, top_k=1, renormalize=True,
               score_func="softmax", scale=1.0):
    """``data`` (..., d), ``weight`` (experts, d) -> (weights (..., k)
    float32, experts (..., k) int32): logits over all experts and their
    scores in float32 (``score_func`` 'softmax' over the experts, or
    'sigmoid' of each), the ``top_k`` largest, their weights
    renormalised to sum to one when ``renormalize``, times ``scale``.
    ``bias`` (experts,) moves the choice and not the weights: the
    ``top_k`` are taken of ``scores + bias``, the weights are the scores
    at those experts without it (a per-expert balancing state that
    carries no gradient)."""
    if score_func not in ("softmax", "sigmoid"):
        raise ValueError(f"unknown score_func {score_func!r}")
    logits = jnp.einsum("...d,ed->...e", data.astype(jnp.float32),
                        weight.astype(jnp.float32))
    if score_func == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    else:
        probs = jax.nn.sigmoid(logits)
    if bias is None:
        weights, experts = jax.lax.top_k(probs, int(top_k))
    else:
        _, experts = jax.lax.top_k(
            probs + jax.lax.stop_gradient(bias.astype(jnp.float32)),
            int(top_k))
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return weights, experts.astype(jnp.int32)


def _on_tpu(x):
    """Whether a computation on ``x`` lands on a TPU: where ``x`` lives;
    a tracer has no device and lands on jax's default backend. A copy of
    ``ops.linear_attention``'s: graftlint's trace-safety pass (TS001)
    proves a helper static only inside its own module."""
    from .pallas_kernels import pallas_available

    if isinstance(x, jax.core.Tracer):
        return pallas_available()
    return next(iter(x.devices())).platform == "tpu"


def _grouped(lhs, rhs, sizes):
    """``jax.lax.ragged_dot(lhs, rhs, sizes)``: the kernels where the
    computation lands on a TPU and the block's rows lie on their grid,
    ``ragged_dot`` everywhere else (the module's docstring)."""
    from ..tune import schedule

    if _on_tpu(lhs) and schedule.grouped_mm_shape_supported(lhs.shape[0]):
        from .grouped_matmul_kernels import grouped_matmul_kernels

        return grouped_matmul_kernels(lhs, rhs, sizes)
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def _block_of_rows(x, flat_w, order, offsets, gate_up, down, top_k, block,
                   activation="swiglu"):
    """What the sorted assignments [block * tokens, (block + 1) *
    tokens) add to the result: the experts' MLPs over those rows
    (``activation``: :func:`moe_experts`), weighted and added back per
    token, (tokens, d) float32."""
    n_rows = x.shape[0]
    lo = block * n_rows
    take = jax.lax.dynamic_slice_in_dim(order, lo, n_rows)
    token = take // top_k
    # of each expert's run of sorted rows, the part inside this block
    bounds = jnp.clip(offsets, lo, lo + n_rows)
    sizes = bounds[1:] - bounds[:-1]
    valid = (lo + jnp.arange(n_rows) < offsets[-1])[:, None]
    # rows past the assignments held belong to no group: what the
    # grouped product leaves there is not defined (on the chip, whatever
    # the buffer held), so they are zeroed going in (which zeroes their
    # gradient coming back) and going out
    xs = jnp.where(valid, x[token], 0)
    h = _grouped(xs, gate_up, sizes)
    if activation == "relu2":
        act = jnp.square(jax.nn.relu(h.astype(jnp.float32))).astype(x.dtype)
    else:
        inner = down.shape[1]
        act = (jax.nn.silu(h[:, :inner].astype(jnp.float32))
               * h[:, inner:].astype(jnp.float32)).astype(x.dtype)
    ys = _grouped(act, down, sizes)
    # zeroed BEFORE the weights multiply them: selected away afterwards,
    # a row that holds a NaN still gives its weight NaN x 0 = NaN in the
    # backward pass, and through it the router and every layer before
    ys = jnp.where(valid, ys, 0).astype(jnp.float32) * flat_w[take][:, None]
    return jnp.zeros(x.shape, jnp.float32).at[token].add(ys)


ACTIVATIONS = ("swiglu", "relu2")


@register("moe_experts", mutate=(5,))
def moe_experts(data, weights, experts, gate_up, down, counts,
                first_expert=0, activation="swiglu"):
    """``data`` (..., d); ``weights`` / ``experts`` (..., k) from
    ``moe_router``; ``gate_up`` (held, d, 2 x inner: gate then up) and
    ``down`` (held, inner, d) of the experts
    [first_expert, first_expert + held). Returns
    ``sum_{e chosen and held} w_e * down_e(silu(gate_e x) * up_e x)``
    and moves ``counts`` (held + 1, float32): the assignments to each
    held expert in this call (every one of them is computed), and the
    tokens that chose no held expert. ``activation='relu2'`` takes
    ungated experts (``nemotron_h``'s): ``gate_up`` is then the up
    matrix alone, (held, d, inner), and each expert is
    ``down_e(relu(up_e x)^2)``."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown activation {activation!r}; known: "
                         f"{ACTIVATIONS}")
    held, d = gate_up.shape[0], data.shape[-1]
    top_k = experts.shape[-1]
    x = data.reshape(-1, d)
    tokens = x.shape[0]
    local = experts.reshape(tokens, top_k) - first_expert
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)        # held first, by expert
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    flat_w = weights.reshape(-1).astype(jnp.float32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
    n_held = offsets[-1]

    def block_of_rows(block):
        return _block_of_rows(x, flat_w, order, offsets, gate_up, down,
                              top_k, block, activation)

    def every_block():
        # every choice of every token held here is min(k, held) blocks;
        # the checkpoint encloses the skip, so the scan keeps what it
        # closes over once, not a copy per block
        @jax.checkpoint
        def part(block):
            return jax.lax.cond(
                block * tokens < n_held, block_of_rows,
                lambda _: jnp.zeros(x.shape, jnp.float32), block)

        def one(total, block):
            return total + part(block), None

        return jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                            jnp.arange(min(top_k, held)))[0]

    out = jax.lax.cond(n_held <= tokens, lambda: block_of_rows(0),
                       every_block)
    out = out.astype(x.dtype)
    idle = jnp.sum(~jnp.any(here, axis=-1))
    new_counts = jnp.concatenate([sizes, idle[None]]).astype(counts.dtype)
    return out.reshape(data.shape), new_counts
