"""Trinity (``model_type: afmoe``), plain float32 reference: forward and
loss, at whatever sizes the weights have.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no tiles, no mixed
precision, nothing imported from the program. The layer equations
(arcee-ai/Trinity-Mini's ``config.json`` and the ``transformers``
``modeling_afmoe.py`` they configure):

- RMSNorm ``x * rsqrt(mean(x^2) + eps) * w``;
- tokens -> ``embed`` times ``embed_scale`` (``sqrt(hidden_size)`` under
  ``mup_enabled``) -> layers -> RMSNorm -> the untied head;
- layer: ``h += norm_b(attn(norm_a(h))); h += norm_d(mlp(norm_c(h)))``
  (``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``);
- attention: q (H heads of D), k, v (KV heads of D) and the gate (H x D)
  from four projections; RMSNorm over the head on q and on k; in a
  ``sliding_attention`` layer rotary on the whole head (half-split
  pairing) and a query sees the keys ``0 <= i - j < window``, itself
  counted; in a ``full_attention`` layer no rotary and every key
  ``j <= i``; each K/V head serves ``H / KV`` query heads; softmax of
  ``q k^T / sqrt(D)`` as a dense masked softmax over blocks of rows;
  the result times ``sigmoid(gate)``; the output projection;
- a dense layer's MLP: ``down(silu(gate x) * up x)``;
- an expert layer: ``s = sigmoid(router x)`` over ALL experts; the
  ``top_k`` largest of ``s + expert_bias``; their weights ``s`` at
  those, divided by their sum (``route_norm``), times ``route_scale``; a
  loop over the experts the weights hold (``first_expert`` and on),
  each a SiLU-gated MLP; what the absent experts would add is left out;
  plus the shared expert, ungated.

Departures from the published model, because the configuration under
test has them: ``expert_bias`` stays what the weights say (zero, or
what a traffic with balanced routing set it to before the first step;
the update that ``load_balance_coeff`` drives is not built), no
auxiliary loss, no expert groups (``n_group`` = ``topk_group`` = 1).

Weights arrive as a plain tree; a dense matrix is (out, in):

    {"embed": (vocab, d),
     "layers": [{"norm_a", "norm_b", "norm_c", "norm_d": (d,),
                 "attn": {"q_w", "gate_w": (H * D, d),
                          "k_w", "v_w": (KV * D, d), "o_w": (d, H * D),
                          "q_norm", "k_norm": (D,)},
                 "mlp": {"gate_up_w": (2 I, d), "down_w": (d, I)}
                 or "moe": {"router_w": (experts, d),
                            "expert_bias": (experts,),
                            "gate_up": (held, d, 2 I), "down": (held, I, d),
                            "shared_gate_up_w": (2 Is, d),
                            "shared_down_w": (d, Is)}}],
     "norm": (d,), "head_w": (vocab, d)}

``sizes`` is a dict of what the shapes do not give: ``heads``,
``kv_heads``, ``layer_types`` (one of ``sliding_attention`` /
``full_attention`` a layer), ``window``, ``rope_theta``, ``eps``,
``top_k``, ``route_norm``, ``route_scale``, ``first_expert``,
``embed_scale``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_BLOCK = 512     # query rows a block of the masked softmax


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, theta):
    """x (B, H, T, D): rotate the whole head, channel i with i + D / 2."""
    t, d = x.shape[2], x.shape[3]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)           # (T, D)
    turned = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(ang) + turned * jnp.sin(ang)


def attention(x, p, sizes, window):
    """``window`` None: a full-attention layer."""
    b, t, _ = x.shape
    heads, kv = sizes["heads"], sizes["kv_heads"]
    d = p["q_norm"].shape[0]
    q = (x @ p["q_w"].T).reshape(b, t, heads, d)
    k = (x @ p["k_w"].T).reshape(b, t, kv, d)
    v = (x @ p["v_w"].T).reshape(b, t, kv, d)
    gate = x @ p["gate_w"].T
    q = rms_norm(q, p["q_norm"], sizes["eps"]).transpose(0, 2, 1, 3)
    k = rms_norm(k, p["k_norm"], sizes["eps"]).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    if window is not None:
        q = rotary(q, sizes["rope_theta"])
        k = rotary(k, sizes["rope_theta"])
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    block = min(ROW_BLOCK, t)
    pad = (-t) % block
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    starts = jnp.arange(0, t + pad, block)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(qp, start, block, axis=2)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, k) / jnp.sqrt(jnp.float32(d))
        ahead = (start + jnp.arange(block))[:, None] - jnp.arange(t)[None, :]
        seen = ahead >= 0
        if window is not None:
            seen &= ahead < window
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", w, v)

    out = jax.lax.map(rows, starts)                  # (N, B, H, block, D)
    out = jnp.moveaxis(out, 0, 2).reshape(b, heads, t + pad, d)[:, :, :t]
    out = out.transpose(0, 2, 1, 3).reshape(b, t, heads * d)
    return (out * jax.nn.sigmoid(gate)) @ p["o_w"].T


def gated_mlp(x, gate_up_w, down_w):
    """``down(silu(gate x) * up x)``, matrices (out, in), gate rows
    first."""
    h = x @ gate_up_w.T
    inner = down_w.shape[1]
    return (jax.nn.silu(h[..., :inner]) * h[..., inner:]) @ down_w.T


def route(x, p, sizes):
    """(weights, experts), both (..., top_k), over all the experts."""
    scores = jax.nn.sigmoid(x @ p["router_w"].T)
    _, chosen = jax.lax.top_k(scores + p["expert_bias"], sizes["top_k"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if sizes["route_norm"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * sizes["route_scale"], chosen


def routed(x, p, sizes):
    """The held experts' part of the routed result."""
    weights, chosen = route(x, p, sizes)
    inner = p["down"].shape[1]
    y = jnp.zeros_like(x)
    for e in range(p["gate_up"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == sizes["first_expert"] + e,
                                weights, 0.0), axis=-1, keepdims=True)
        h = x @ p["gate_up"][e]
        y = y + w_e * ((jax.nn.silu(h[..., :inner]) * h[..., inner:])
                       @ p["down"][e])
    return y


def moe(x, p, sizes):
    return routed(x, p, sizes) + gated_mlp(x, p["shared_gate_up_w"],
                                           p["shared_down_w"])


def hidden(weights, tokens, sizes):
    """``tokens`` (B, T) int -> final-norm states (B, T, d)."""
    eps = sizes["eps"]
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens] * sizes["embed_scale"]
        for p, kind in zip(weights["layers"], sizes["layer_types"],
                           strict=True):
            window = sizes["window"] if kind == "sliding_attention" else None
            mixed = attention(rms_norm(h, p["norm_a"], eps), p["attn"],
                              sizes, window)
            h = h + rms_norm(mixed, p["norm_b"], eps)
            x = rms_norm(h, p["norm_c"], eps)
            fed = gated_mlp(x, p["mlp"]["gate_up_w"], p["mlp"]["down_w"]) \
                if "mlp" in p else moe(x, p["moe"], sizes)
            h = h + rms_norm(fed, p["norm_d"], eps)
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
