"""Qwen3-Next (``model_type: qwen3_next``) for the model zoo: a decoder
whose layers mix tokens by linear attention three times in four and by
softmax attention the fourth, and end, every one, in a sparse expert
layer (docs/parallel.md).

Layer i: ``h += mixer(norm1(h)); h += moe(norm2(h))``, the mixer a
``contrib.nn.GatedAttention`` where ``(i + 1) % full_attention_interval
== 0`` and a ``contrib.nn.GatedDeltaNet`` elsewhere; zero-centred
RMSNorm; no learned positions (rotary inside the attention layers, none
in the linear ones); a final norm and an untied head without bias. The
constructor's arguments are the keys of the published ``config.json``;
``num_hidden_layers`` is the depth that is built, and ``experts_held``
(``(first, count)`` or a ``range``; default all ``num_experts``) the
part of every layer's experts this device holds
(``contrib.nn.SparseMoE``). Not built: the multi-token-prediction head,
the router's auxiliary loss.

Parameter prefixes ``parallel.SpecLayout.param_rules`` is written
against: ``attn_q_`` / ``attn_k_`` / ``attn_v_`` / ``attn_out_``,
``linattn_qkvz_`` / ``linattn_ba_`` / ``linattn_out_``,
``moe_experts_gate_up_`` / ``moe_experts_down_`` (the expert axis
first), ``moe_shared_gate_up_`` / ``moe_shared_down_``, ``embed_`` /
``head_``. ``impl`` selects the attention kernel of the full-attention
layers ('dense' or 'flash'); ``remat`` wraps each half of every layer
(norm and mixer, norm and expert layer) in ``contrib.nn.Remat`` with
that policy.
"""
from __future__ import annotations

from .. import nn
from ..block import HybridBlock
from ..contrib import nn as contrib_nn

__all__ = ["Qwen3NextBlock", "Qwen3NextLM", "qwen3_next_lm"]


class _PreNormResidual(HybridBlock):
    """``x + inner(norm(x))``: half a layer, the unit ``remat`` wraps."""

    def __init__(self, norm, inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = norm()
            self.inner = inner()

    def hybrid_forward(self, F, x):
        return x + self.inner(self.norm(x))


class Qwen3NextBlock(HybridBlock):
    """One decoder layer: ``h += mixer(norm1(h)); h += moe(norm2(h))``.
    The mixer is ``self.attn`` whichever kind it is. ``remat`` wraps
    each half in ``contrib.nn.Remat`` on its own, so the backward pass
    holds one half's recomputed activations at a time."""

    def __init__(self, mixer, moe, units, epsilon, remat=None, **kwargs):
        super().__init__(**kwargs)

        def half(norm_prefix, inner):
            blk = _PreNormResidual(
                lambda: nn.RMSNorm(units, epsilon, zero_centered=True,
                                   prefix=norm_prefix), inner, prefix="")
            return blk if remat is None else contrib_nn.Remat(blk,
                                                              policy=remat)

        with self.name_scope():
            self.mix = half("norm1_", mixer)
            self.ffn = half("norm2_", moe)

    def _half(self, name):
        blk = getattr(self, name)
        return getattr(blk, "block", blk)       # under contrib.nn.Remat

    attn = property(lambda self: self._half("mix").inner)
    norm1 = property(lambda self: self._half("mix").norm)
    moe = property(lambda self: self._half("ffn").inner)
    norm2 = property(lambda self: self._half("ffn").norm)

    def hybrid_forward(self, F, x):
        return self.ffn(self.mix(x))


class Qwen3NextLM(HybridBlock):
    """(B, T) token ids -> (B, T, vocab_size) logits."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, full_attention_interval=4,
                 num_attention_heads=16, num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 linear_num_key_heads=16, linear_num_value_heads=32,
                 linear_key_head_dim=128, linear_value_head_dim=128,
                 linear_conv_kernel_dim=4, num_experts=512,
                 num_experts_per_tok=10, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, norm_topk_prob=True,
                 rms_norm_eps=1e-6, experts_held=None, impl="dense",
                 remat=None, **kwargs):
        super().__init__(**kwargs)
        units, eps = hidden_size, rms_norm_eps

        def attention():
            return contrib_nn.GatedAttention(
                units, num_attention_heads, num_key_value_heads, head_dim,
                rotary_dim=int(head_dim * partial_rotary_factor),
                rope_theta=rope_theta, epsilon=eps, impl=impl,
                prefix="attn_")

        def deltanet():
            return contrib_nn.GatedDeltaNet(
                units, linear_num_key_heads, linear_num_value_heads,
                linear_key_head_dim, linear_value_head_dim,
                conv_kernel=linear_conv_kernel_dim, epsilon=eps,
                prefix="linattn_")

        def moe():
            return contrib_nn.SparseMoE(
                units, moe_intermediate_size, num_experts,
                num_experts_per_tok, experts_held=experts_held,
                shared_hidden=shared_expert_intermediate_size,
                renormalize=norm_topk_prob, prefix="moe_")

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            with self.blocks.name_scope():
                for i in range(num_hidden_layers):
                    full = (i + 1) % full_attention_interval == 0
                    self.blocks.add(Qwen3NextBlock(
                        attention if full else deltanet, moe, units, eps,
                        remat=remat))
            self.norm = nn.RMSNorm(units, eps, zero_centered=True,
                                   prefix="norm_")
            self.head = nn.Dense(vocab_size, use_bias=False, flatten=False,
                                 in_units=units, prefix="head_")

    def hybrid_forward(self, F, x):
        return self.head(self.norm(self.blocks(self.embed(x))))


def qwen3_next_lm(config=None, **kwargs):
    """``Qwen3NextLM`` from the dict of a ``config.json`` (keys the
    constructor does not take -- ``model_type``,
    ``max_position_embeddings`` and the like -- are passed over) and
    keyword arguments that override it; the defaults are
    Qwen3-Next-80B-A3B's."""
    import inspect

    known = inspect.signature(Qwen3NextLM.__init__).parameters
    picked = {k: v for k, v in (config or {}).items() if k in known}
    return Qwen3NextLM(**{**picked, **kwargs})
