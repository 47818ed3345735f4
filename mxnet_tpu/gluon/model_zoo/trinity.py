"""Trinity (``model_type: afmoe``, arcee-ai) for the model zoo: a decoder
whose attention sees a window of the sequence three layers in four and
all of it the fourth, whose first layers are dense and whose others end
in a sparse expert layer routed by sigmoid scores, every half of a
layer normed going in and coming out (docs/parallel.md).

Tokens -> ``embed`` (times ``sqrt(hidden_size)`` under ``mup_enabled``)
-> layers -> RMSNorm -> an untied head; no bias anywhere. Layer i:
``h += norm_b(attn(norm_a(h))); h += norm_d(mlp(norm_c(h)))`` (the
published ``input_layernorm``, ``post_attention_layernorm``,
``pre_mlp_layernorm``, ``post_mlp_layernorm``), plain RMSNorm. The
mixer is a ``contrib.nn.GatedAttention`` with the gate's projection its
own: where ``layer_types[i]`` is ``sliding_attention`` it rotates the
whole head and sees ``sliding_window`` keys, where it is
``full_attention`` it rotates nothing and sees every earlier key. The
MLP of the first ``num_dense_layers`` layers is a ``contrib.nn.GatedMLP``
of ``intermediate_size``; of the others a ``contrib.nn.SparseMoE``:
sigmoid scores over ``num_experts``, the ``num_experts_per_tok``
largest of score + ``expert_bias`` (a state without gradient, zero at
first), their scores normalised over themselves (``route_norm``) times
``route_scale``, plus ``num_shared_experts`` shared experts as one
ungated MLP. The constructor's arguments are the keys of the published
``config.json``; ``layer_types`` is the list of the layers that are
built, ``num_dense_layers`` how many of them lead dense, and
``experts_held`` (``(first, count)`` or a ``range``; default all) the
part of every layer's experts this device holds. Not built: what moves
``expert_bias`` between steps (``load_balance_coeff``), any auxiliary
loss, expert groups (``n_group`` = ``topk_group`` = 1 in the published
configurations: no group limit).

Parameter prefixes: ``attn_q_`` / ``attn_k_`` / ``attn_v_`` /
``attn_gate_`` / ``attn_out_``, ``mlp_gate_up_`` / ``mlp_down_``,
``moe_experts_gate_up_`` / ``moe_experts_down_`` (the expert axis
first), ``moe_shared_gate_up_`` / ``moe_shared_down_``, ``embed_`` /
``head_``. ``impl`` selects the attention kernel of every layer
('dense' or 'flash'); ``remat`` wraps each half of every layer in
``contrib.nn.Remat`` with that policy.
"""
from __future__ import annotations

from .. import nn
from ..block import HybridBlock
from ..contrib import nn as contrib_nn

__all__ = ["TrinityBlock", "TrinityLM", "trinity_lm"]

LAYER_TYPES = ("sliding_attention", "full_attention")


class _SandwichResidual(HybridBlock):
    """``x + norm_out(inner(norm_in(x)))``: half a layer, the unit
    ``remat`` wraps."""

    def __init__(self, norm_in, inner, norm_out, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm_in = norm_in()
            self.inner = inner()
            self.norm_out = norm_out()

    def hybrid_forward(self, F, x):
        return x + self.norm_out(self.inner(self.norm_in(x)))


class TrinityBlock(HybridBlock):
    """One decoder layer, two sandwich halves. The mixer is ``self.attn``
    and the second half's inner block ``self.mlp`` (a ``GatedMLP`` or a
    ``SparseMoE``). ``remat`` wraps each half in ``contrib.nn.Remat`` on
    its own, so the backward pass holds one half's recomputed
    activations at a time."""

    def __init__(self, mixer, mlp, units, epsilon, remat=None, **kwargs):
        super().__init__(**kwargs)

        def norm(prefix):
            return lambda: nn.RMSNorm(units, epsilon, prefix=prefix)

        def half(norm_in, inner, norm_out):
            blk = _SandwichResidual(norm(norm_in), inner, norm(norm_out),
                                    prefix="")
            return blk if remat is None else contrib_nn.Remat(blk,
                                                              policy=remat)

        with self.name_scope():
            self.mix = half("norm1_", mixer, "norm2_")
            self.ffn = half("norm3_", mlp, "norm4_")

    def _half(self, name):
        blk = getattr(self, name)
        return getattr(blk, "block", blk)       # under contrib.nn.Remat

    attn = property(lambda self: self._half("mix").inner)
    mlp = property(lambda self: self._half("ffn").inner)
    # by the published names
    input_layernorm = property(lambda self: self._half("mix").norm_in)
    post_attention_layernorm = property(
        lambda self: self._half("mix").norm_out)
    pre_mlp_layernorm = property(lambda self: self._half("ffn").norm_in)
    post_mlp_layernorm = property(lambda self: self._half("ffn").norm_out)

    def hybrid_forward(self, F, x):
        return self.ffn(self.mix(x))


class TrinityLM(HybridBlock):
    """(B, T) token ids -> (B, T, vocab_size) logits."""

    def __init__(self, vocab_size=200192, hidden_size=2048,
                 layer_types=("sliding_attention",) * 3
                 + ("full_attention",), num_dense_layers=2,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 sliding_window=2048, rope_theta=10000.0,
                 intermediate_size=6144, moe_intermediate_size=1024,
                 num_experts=128, num_experts_per_tok=8,
                 num_shared_experts=1, score_func="sigmoid",
                 route_norm=True, route_scale=2.826, mup_enabled=True,
                 rms_norm_eps=1e-5, experts_held=None, impl="dense",
                 remat=None, **kwargs):
        super().__init__(**kwargs)
        units, eps = hidden_size, rms_norm_eps
        unknown = set(layer_types) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}; known: "
                             f"{LAYER_TYPES}")
        self._embed_scale = float(units) ** 0.5 if mup_enabled else None

        def attention(kind):
            window = kind == "sliding_attention"
            return lambda: contrib_nn.GatedAttention(
                units, num_attention_heads, num_key_value_heads, head_dim,
                rotary_dim=None if window else 0, rope_theta=rope_theta,
                epsilon=eps, impl=impl,
                window=sliding_window if window else None,
                zero_centered_norm=False, gate_proj=True, prefix="attn_")

        def dense():
            return contrib_nn.GatedMLP(units, intermediate_size,
                                       prefix="mlp_")

        def moe():
            return contrib_nn.SparseMoE(
                units, moe_intermediate_size, num_experts,
                num_experts_per_tok, experts_held=experts_held,
                shared_hidden=moe_intermediate_size * num_shared_experts,
                renormalize=route_norm, score_func=score_func,
                route_scale=route_scale, expert_bias=True,
                shared_gate=False, prefix="moe_")

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            with self.blocks.name_scope():
                for i, kind in enumerate(layer_types):
                    self.blocks.add(TrinityBlock(
                        attention(kind),
                        dense if i < num_dense_layers else moe, units, eps,
                        remat=remat))
            self.norm = nn.RMSNorm(units, eps, prefix="norm_")
            self.head = nn.Dense(vocab_size, use_bias=False, flatten=False,
                                 in_units=units, prefix="head_")

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        if self._embed_scale is not None:
            h = h * self._embed_scale
        return self.head(self.norm(self.blocks(h)))


def trinity_lm(config=None, **kwargs):
    """``TrinityLM`` from the dict of a ``config.json`` (keys the
    constructor does not take -- ``model_type``, ``num_hidden_layers``,
    ``max_position_embeddings`` and the like -- are passed over) and
    keyword arguments that override it; the defaults are Trinity-Mini's
    widths and one period of its layers."""
    import inspect

    known = inspect.signature(TrinityLM.__init__).parameters
    picked = {k: v for k, v in (config or {}).items() if k in known}
    return TrinityLM(**{**picked, **kwargs})
