"""Nemotron-H (``model_type: nemotron_h``), plain float32 reference:
forward and loss, at whatever sizes the weights have.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunks, no tiles,
no mixed precision, nothing imported from the program. The layer
equations (nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16's
``config.json`` and the ``transformers`` ``modeling_nemotron_h.py`` it
configures):

- RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``;
- tokens -> ``embed`` -> layers -> RMSNorm (``norm_f``) -> the untied
  head;
- every layer ``h += mixer(norm(h))``, the mixer one of three kinds
  (``layer_types``):
- ``mamba``, the Mamba-2 mixer: ``in_proj`` laid [z | xBC | dt] as the
  modeling code splits it; a causal depthwise convolution of the
  weight's taps with its bias over xBC, then SiLU; xBC split into x
  (heads of ``mamba_head_dim``), B and C (``n_groups`` of the state
  size), head h reading group ``h // (heads / groups)``;
  ``dt = softplus(dt + dt_bias)`` clamped to ``time_step_limit``,
  ``A = -exp(A_log)``; then token by token, a ``lax.scan`` over t with
  the state S (head_dim x state) of every head from zero:
  ``S = exp(dt A) S + dt x B^T``, ``y = S C + D x``; the gated RMSNorm:
  ``y * silu(z)`` normalised over ``n_groups`` groups of the channels,
  times its weight; ``out_proj``;
- ``moe``: ``s = sigmoid(router x)`` over ALL experts; the ``top_k``
  largest of ``s + expert_bias`` (``e_score_correction_bias``); their
  weights ``s`` at those, divided by their sum plus 1e-20
  (``norm_topk_prob``), times ``route_scale``
  (``routed_scaling_factor``); a loop over the experts the weights hold
  (``first_expert`` and on), each ``down(relu(up x)^2)``; what the
  absent experts would add is left out; plus the shared expert of the
  same form;
- ``attention``: q (H heads of D), k, v (KV heads of D) from three
  projections, no positions, no norm, no gate; each K/V head serves
  ``H / KV`` query heads; softmax of ``q k^T / sqrt(D)`` over every key
  ``j <= i``, as a dense masked softmax over blocks of rows; the output
  projection.

Departures from the published model, because the configuration under
test has them: ``expert_bias`` stays what the weights say (zero, or
what a traffic with balanced routing set it to before the first step;
the update that moves it in training is not built), no auxiliary loss,
no expert groups (``n_group`` = ``topk_group`` = 1), one tower trained
by next-token cross-entropy through its own head (the second tower and
the generation by diffusion over blocks that the model card describes
are not built: ``config.json`` does not define them). The modeling
code's residual in float32 and its kernels' chunked scan are the same
computation in exact arithmetic: here every step is float32.

Weights arrive as a plain tree; a dense matrix is (out, in):

    {"embed": (vocab, d),
     "layers": [{"norm": (d,), and one of
                 "mamba": {"in_w": (2 I + 2 G N + H, d), "conv_w": (I + 2 G N, K),
                           "conv_b": (I + 2 G N,), "A_log", "D", "dt_bias": (H,),
                           "norm_w": (I,), "out_w": (d, I)},
                 "moe": {"router_w": (experts, d), "expert_bias": (experts,),
                         "up": (held, d, Ie), "down": (held, Ie, d),
                         "shared_up_w": (Is, d), "shared_down_w": (d, Is)},
                 "attn": {"q_w": (H * D, d), "k_w", "v_w": (KV * D, d),
                          "o_w": (d, H * D)}}],
     "norm": (d,), "head_w": (vocab, d)}

with I = heads x ``mamba_head_dim``. ``sizes`` is a dict of what the
shapes do not give: ``layer_types``, ``heads``, ``kv_heads``,
``mamba_heads``, ``n_groups``, ``time_step_limit`` ((low, high), high
None for none), ``eps``, ``top_k``, ``route_norm``, ``route_scale``,
``first_expert``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_BLOCK = 512     # query rows a block of the masked softmax


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(x, p, sizes):
    """The Mamba-2 mixer of a (B, T, d) input."""
    b, t, _ = x.shape
    heads, groups = sizes["mamba_heads"], sizes["n_groups"]
    inner = p["norm_w"].shape[0]
    head_dim = inner // heads
    conv_dim, taps = p["conv_w"].shape
    state = (conv_dim - inner) // (2 * groups)
    proj = x @ p["in_w"].T
    z, xbc, dt = (proj[..., :inner], proj[..., inner:inner + conv_dim],
                  proj[..., inner + conv_dim:])
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, j:j + t] * p["conv_w"][:, j]
                          for j in range(taps)) + p["conv_b"])
    xs = xbc[..., :inner].reshape(b, t, heads, head_dim)
    bs, cs = (xbc[..., inner + i * groups * state:
                  inner + (i + 1) * groups * state].reshape(
                      b, t, groups, state) for i in (0, 1))
    bs, cs = (jnp.repeat(v, heads // groups, axis=2) for v in (bs, cs))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    low, high = sizes["time_step_limit"]
    dt = jnp.clip(dt, low, high)
    a = -jnp.exp(p["A_log"])

    def token(s, inputs):
        x_t, dt_t, b_t, c_t = inputs        # (B, H, P), (B, H), (B, H, N)
        s = jnp.exp(dt_t * a)[..., None, None] * s \
            + dt_t[..., None, None] * x_t[..., :, None] * b_t[..., None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t) \
            + p["D"][:, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((b, heads, head_dim, state), xs.dtype),
        tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, bs, cs)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, t, inner) * jax.nn.silu(z)
    y = y.reshape(b, t, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + sizes["eps"])
    return (y.reshape(b, t, inner) * p["norm_w"]) @ p["out_w"].T


def attention(x, p, sizes):
    b, t, _ = x.shape
    heads, kv = sizes["heads"], sizes["kv_heads"]
    d = p["q_w"].shape[0] // heads
    q = (x @ p["q_w"].T).reshape(b, t, heads, d).transpose(0, 2, 1, 3)
    k = (x @ p["k_w"].T).reshape(b, t, kv, d).transpose(0, 2, 1, 3)
    v = (x @ p["v_w"].T).reshape(b, t, kv, d).transpose(0, 2, 1, 3)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    block = min(ROW_BLOCK, t)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    starts = jnp.arange(0, t + pad, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / jnp.sqrt(jnp.float32(d))
        seen = (start + jnp.arange(block))[:, None] >= jnp.arange(t)[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", w, v)

    out = jax.lax.map(rows, starts)                  # (N, B, H, block, D)
    out = jnp.moveaxis(out, 0, 2).reshape(b, heads, t + pad, d)[:, :, :t]
    return out.transpose(0, 2, 1, 3).reshape(b, t, heads * d) @ p["o_w"].T


def mlp(x, up_w, down_w):
    """``down(relu(up x)^2)``, matrices (out, in)."""
    return relu2(x @ up_w.T) @ down_w.T


def route(x, p, sizes):
    """(weights, experts), both (..., top_k), over all the experts."""
    scores = jax.nn.sigmoid(x @ p["router_w"].T)
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], sizes["top_k"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["route_norm"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return weights * sizes["route_scale"], chosen


def routed(x, p, sizes):
    """The held experts' part of the routed result."""
    weights, chosen = route(x, p, sizes)
    y = jnp.zeros_like(x)
    for e in range(p["up"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == sizes["first_expert"] + e,
                                weights, 0.0), axis=-1, keepdims=True)
        y = y + w_e * (relu2(x @ p["up"][e]) @ p["down"][e])
    return y


def moe(x, p, sizes):
    return routed(x, p, sizes) + mlp(x, p["shared_up_w"], p["shared_down_w"])


def mixer(kind, x, p, sizes):
    if kind == "mamba":
        return mamba(x, p["mamba"], sizes)
    if kind == "moe":
        return moe(x, p["moe"], sizes)
    if kind == "attention":
        return attention(x, p["attn"], sizes)
    raise ValueError(f"unknown layer kind {kind!r}")


def hidden(weights, tokens, sizes):
    """``tokens`` (B, T) int -> final-norm states (B, T, d)."""
    eps = sizes["eps"]
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens]
        for p, kind in zip(weights["layers"], sizes["layer_types"],
                           strict=True):
            h = h + mixer(kind, rms_norm(h, p["norm"], eps), p, sizes)
        return rms_norm(h, weights["norm"], eps)


def logits_of(weights, tokens, sizes):
    h = hidden(weights, tokens, sizes)
    with jax.default_matmul_precision("highest"):
        return h @ weights["head_w"].T


def loss(weights, tokens, labels, sizes):
    """Mean next-token cross-entropy over every position of (B, T);
    ``jax.grad`` of it is the reference's gradient."""
    logp = jax.nn.log_softmax(logits_of(weights, tokens, sizes), axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1))


def check_outputs(weights, tokens, labels, positions, sizes):
    """What the training check compares, from one pass: the mean
    next-token cross-entropy over every position of (B, T), the logits
    (B, P, vocab) at ``positions`` (B, P) of each row, and None for the
    batch statistics this model does not keep."""
    logits = logits_of(weights, tokens, sizes)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked), jnp.take_along_axis(
        logits, positions[:, :, None], axis=1), None
