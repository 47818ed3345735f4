"""Qwen3-Next (``model_type: qwen3_next``), plain float32 reference:
forward and loss, at whatever sizes the weights have.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no chunking, no
mixed precision, nothing imported from the program. The layer
equations (Qwen3-Next-80B-A3B-Instruct's ``config.json`` and the
``transformers`` ``modeling_qwen3_next.py`` they configure):

- zero-centred RMSNorm ``x * rsqrt(mean(x^2) + eps) * (1 + w)``; the
  gated RMSNorm of the DeltaNet output, per value head,
  ``w * x * rsqrt(mean(x^2) + eps) * silu(z)``;
- layer: ``h += mixer(norm1(h)); h += moe(norm2(h))``; the mixer is
  full attention in every ``full_attention_interval``-th layer (a layer
  whose weights have the key ``attn``), else Gated DeltaNet;
- gated attention: per head ``q_w`` gives the query then the gate;
  zero-centred RMSNorm over the head on q and k; rotary (half-split
  pairing) on the first ``rotary_dim`` channels; each K/V head serves
  ``heads / kv_heads`` query heads; causal softmax of
  ``q k^T / sqrt(D)``, as a masked softmax over blocks of rows; the
  result times ``sigmoid(gate)``; the output projection;
- Gated DeltaNet as the **token recurrence** (a ``lax.scan`` over
  tokens): ``qkvz_w`` gives q, k (Hk heads of Dk), v, z (Hv of Dv),
  ``ba_w`` gives b, a (Hv each); [q, k, v] pass a causal depthwise
  convolution without bias, then SiLU; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) * softplus(a + dt_bias)``; q, k L2-normalised
  (eps 1e-6), q scaled by ``Dk^-0.5``, each q/k head serves Hv / Hk
  value heads; per value head, state S (Dk x Dv) from zero:
  ``S = exp(g_t) S; S += k_t (beta_t (v_t - S^T k_t))^T; o_t = S^T q_t``;
- expert layer: router logits over ALL experts, softmax, the ``top_k``
  largest renormalised over themselves; a loop over the experts the
  weights hold (``first_expert`` and on), each a SiLU-gated MLP; what
  the absent experts would add is left out; plus the shared expert
  behind ``sigmoid(shared_gate x)``.

Departures from the published model, because the configuration under
test has them: no multi-token-prediction head, no auxiliary loss; the
channels of ``qkvz_w`` are laid [q | k | v | z] and of ``ba_w`` [b | a]
(the checkpoint interleaves them by key head).

Weights arrive as a plain tree; a dense matrix is (out, in):

    {"embed": (vocab, d),
     "layers": [{"norm1": (d,), "norm2": (d,),
                 "attn": {"q_w": (H * 2D, d), "k_w", "v_w": (KV * D, d),
                          "o_w": (d, H * D), "q_norm", "k_norm": (D,)}
                 or "deltanet": {"qkvz_w": (2 Hk Dk + 2 Hv Dv, d),
                                 "ba_w": (2 Hv, d),
                                 "conv_w": (2 Hk Dk + Hv Dv, K),
                                 "A_log", "dt_bias": (Hv,), "norm": (Dv,),
                                 "out_w": (d, Hv * Dv)},
                 "moe": {"router_w": (experts, d),
                         "gate_up": (held, d, 2 I), "down": (held, I, d),
                         "shared_gate_up_w": (2 Is, d),
                         "shared_down_w": (d, Is), "shared_gate_w": (1, d)}}],
     "norm": (d,), "head_w": (vocab, d)}

``sizes`` is a dict of the counts the shapes do not give: ``heads``,
``kv_heads``, ``key_heads``, ``value_heads``, ``rotary_dim``,
``rope_theta``, ``eps``, ``top_k``, ``first_expert``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROW_BLOCK = 512     # query rows a block of the masked softmax


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def rotary(x, rotary_dim, theta):
    """x (B, H, T, D): rotate the first ``rotary_dim`` channels."""
    t = x.shape[2]
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)           # (T, rotary_dim)
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    half = rotary_dim // 2
    turned = jnp.concatenate([-rot[..., half:], rot[..., :half]], axis=-1)
    return jnp.concatenate(
        [rot * jnp.cos(ang) + turned * jnp.sin(ang), rest], axis=-1)


def attention(x, p, sizes):
    b, t, _ = x.shape
    heads, kv = sizes["heads"], sizes["kv_heads"]
    d = p["q_norm"].shape[0]
    qg = (x @ p["q_w"].T).reshape(b, t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, t, heads * d)
    k = (x @ p["k_w"].T).reshape(b, t, kv, d)
    v = (x @ p["v_w"].T).reshape(b, t, kv, d)
    q = rms_norm(q, p["q_norm"], sizes["eps"]).transpose(0, 2, 1, 3)
    k = rms_norm(k, p["k_norm"], sizes["eps"]).transpose(0, 2, 1, 3)
    v = v.transpose(0, 2, 1, 3)
    q = rotary(q, sizes["rotary_dim"], sizes["rope_theta"])
    k = rotary(k, sizes["rotary_dim"], sizes["rope_theta"])
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
    out = out.transpose(0, 2, 1, 3).reshape(b, t, heads * d)
    return (out * jax.nn.sigmoid(gate)) @ p["o_w"].T


def causal_conv(x, w):
    """x (B, T, C), w (C, K): y[t] = sum_j w[:, j] x[t - (K - 1) + j]."""
    k = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * w[:, j] for j in range(k))


def delta_rule_recurrent(q, k, v, g, beta):
    """q, k (B, T, H, Dk), v (B, T, H, Dv), g, beta (B, T, H) ->
    (B, T, H, Dv): one state update a token."""
    b, _, h, dk = k.shape
    dv = v.shape[-1]

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        write = (v_t - read) * beta_t[..., None]
        state = state + k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _, out = jax.lax.scan(
        token, jnp.zeros((b, h, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def deltanet(x, p, sizes):
    b, t, _ = x.shape
    hk, hv = sizes["key_heads"], sizes["value_heads"]
    dv = p["norm"].shape[0]
    dk = (p["conv_w"].shape[0] - hv * dv) // (2 * hk)
    qkvz = x @ p["qkvz_w"].T
    conv_c = 2 * hk * dk + hv * dv
    mixed = jax.nn.silu(causal_conv(qkvz[..., :conv_c], p["conv_w"]))
    z = qkvz[..., conv_c:].reshape(b, t, hv, dv)
    q = mixed[..., :hk * dk].reshape(b, t, hk, dk)
    k = mixed[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk)
    v = mixed[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    ba = x @ p["ba_w"].T
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                                 + 1e-6)

    q = jnp.repeat(unit(q) * dk ** -0.5, hv // hk, axis=2)
    k = jnp.repeat(unit(k), hv // hk, axis=2)
    o = delta_rule_recurrent(q, k, v, g, beta)
    o = p["norm"] * o * jax.lax.rsqrt(
        jnp.mean(o * o, axis=-1, keepdims=True) + sizes["eps"]) \
        * jax.nn.silu(z)
    return o.reshape(b, t, hv * dv) @ p["out_w"].T


def routed(x, p, sizes):
    """The held experts' part of the routed result."""
    probs = jax.nn.softmax(x @ p["router_w"].T, axis=-1)
    weights, chosen = jax.lax.top_k(probs, sizes["top_k"])
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    inner = p["down"].shape[1]
    y = jnp.zeros_like(x)
    for e in range(p["gate_up"].shape[0]):
        w_e = jnp.sum(jnp.where(chosen == sizes["first_expert"] + e,
                                weights, 0.0), axis=-1, keepdims=True)
        h = x @ p["gate_up"][e]
        y = y + w_e * ((jax.nn.silu(h[..., :inner]) * h[..., inner:])
                       @ p["down"][e])
    return y


def shared(x, p):
    h = x @ p["shared_gate_up_w"].T
    inner = p["shared_down_w"].shape[1]
    y = (jax.nn.silu(h[..., :inner]) * h[..., inner:]) @ p["shared_down_w"].T
    return jax.nn.sigmoid(x @ p["shared_gate_w"].T) * y


def moe(x, p, sizes):
    return routed(x, p, sizes) + shared(x, p)


def hidden(weights, tokens, sizes):
    """``tokens`` (B, T) int -> final-norm states (B, T, d)."""
    with jax.default_matmul_precision("highest"):
        h = weights["embed"][tokens]
        for p in weights["layers"]:
            x = rms_norm(h, p["norm1"], sizes["eps"])
            h = h + (attention(x, p["attn"], sizes) if "attn" in p
                     else deltanet(x, p["deltanet"], sizes))
            h = h + moe(rms_norm(h, p["norm2"], sizes["eps"]), p["moe"],
                        sizes)
        return rms_norm(h, weights["norm"], sizes["eps"])


def check_outputs(weights, tokens, labels, positions, sizes):
    """What the training check compares, from one pass: the mean
    next-token cross-entropy over every position of (B, T), the logits
    (B, P, vocab) at ``positions`` (B, P) of each row, and None for the
    batch statistics this model does not keep."""
    h = hidden(weights, tokens, sizes)
    with jax.default_matmul_precision("highest"):
        logits = h @ weights["head_w"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked), jnp.take_along_axis(
        logits, positions[:, :, None], axis=1), None
