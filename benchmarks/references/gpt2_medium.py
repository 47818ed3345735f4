"""GPT-2 (Radford et al. 2019), plain float32 reference: forward and loss.

The decoder of ``openai/gpt-2`` ``src/model.py`` at whatever sizes the
weights have: token + learned position embeddings, pre-norm blocks
(LayerNorm eps 1e-5 -> causal multi-head attention -> residual;
LayerNorm -> 4x MLP with tanh-GELU -> residual), a final LayerNorm and
a linear head. Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernels, no cache, no
mixed precision, nothing imported from the program.

Departure from the published model, because the configuration under
test has it: the head is its own matrix with a bias (GPT-2 ties it to
the token embedding and has no bias).

Weights arrive as a plain tree; a dense matrix is (out, in), as the
program stores it:

    {"wte": (vocab, d), "wpe": (positions, d),
     "blocks": [{"ln1_g", "ln1_b", "qkv_w": (3d, d), "qkv_b",
                 "out_w": (d, d), "out_b", "ln2_g", "ln2_b",
                 "fc_w": (4d, d), "fc_b", "proj_w": (d, 4d), "proj_b"}],
     "lnf_g", "lnf_b", "head_w": (vocab, d), "head_b"}

``qkv_w`` rows are all of q, then all of k, then all of v; inside each,
head h owns rows [h * d_head, (h + 1) * d_head).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _attention(x, p, n_head):
    b, t, d = x.shape
    qkv = x @ p["qkv_w"].T + p["qkv_b"]
    q, k, v = (a.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)
               for a in jnp.split(qkv, 3, axis=-1))
    s = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(jnp.float32(d // n_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    out = (w @ v).transpose(0, 2, 1, 3).reshape(b, t, d)
    return out @ p["out_w"].T + p["out_b"]


def hidden(weights, tokens, n_head):
    """``tokens`` (B, T) int -> final-LayerNorm states (B, T, d)."""
    with jax.default_matmul_precision("highest"):
        t = tokens.shape[1]
        h = weights["wte"][tokens] + weights["wpe"][jnp.arange(t)]
        for p in weights["blocks"]:
            h = h + _attention(_ln(h, p["ln1_g"], p["ln1_b"]), p, n_head)
            m = _ln(h, p["ln2_g"], p["ln2_b"])
            m = jax.nn.gelu(m @ p["fc_w"].T + p["fc_b"], approximate=True)
            h = h + m @ p["proj_w"].T + p["proj_b"]
        return _ln(h, weights["lnf_g"], weights["lnf_b"])


def logits_at(weights, tokens, positions, n_head):
    """Logits (B, P, vocab) at ``positions`` (B, P) of each row only, so a
    check of a few positions does not build T x vocab logits."""
    h = hidden(weights, tokens, n_head)
    picked = jnp.take_along_axis(h, positions[:, :, None], axis=1)
    with jax.default_matmul_precision("highest"):
        return picked @ weights["head_w"].T + weights["head_b"]


def check_outputs(weights, tokens, labels, positions, n_head):
    """What the training check compares, from one pass: the mean
    next-token cross-entropy over every position of (B, T), the logits
    (B, P, vocab) at ``positions`` (B, P) of each row, and None for the
    batch statistics this model does not keep."""
    h = hidden(weights, tokens, n_head)
    with jax.default_matmul_precision("highest"):
        logits = h @ weights["head_w"].T + weights["head_b"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, labels.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.mean(picked), jnp.take_along_axis(
        logits, positions[:, :, None], axis=1), None
