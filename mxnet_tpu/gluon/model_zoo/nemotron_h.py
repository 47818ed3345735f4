"""Nemotron-H (``model_type: nemotron_h``, NVIDIA) for the model zoo: a
hybrid decoder whose layers are each one of three kinds, in the order a
pattern string gives, every one ``x + mixer(RMSNorm(x))``.

Tokens -> ``embed`` -> layers -> RMSNorm (``norm_f``) -> an untied head;
no bias anywhere but the convolution's. The published
``hybrid_override_pattern`` names a layer a character:

- ``M``, ``mamba``: a ``contrib.nn.Mamba2Mixer`` of ``mamba_num_heads``
  heads of ``mamba_head_dim``, ``n_groups`` groups of B and C of
  ``ssm_state_size``, a convolution of ``conv_kernel`` taps with a bias,
  the scan in chunks of ``chunk_size``;
- ``E``, ``moe``: a ``contrib.nn.SparseMoE`` of ungated squared-ReLU
  experts (``mlp_hidden_act`` ``relu2``) of ``moe_intermediate_size``:
  sigmoid scores over ``n_routed_experts``, the ``num_experts_per_tok``
  largest of score + ``expert_bias`` (the published
  ``e_score_correction_bias``: a state without gradient, zero at first),
  their scores normalised over themselves (``norm_topk_prob``) times
  ``routed_scaling_factor``, plus one shared expert of
  ``moe_shared_expert_intermediate_size``, ungated;
- ``*``, ``attention``: a ``contrib.nn.GroupedQueryAttention``,
  ``num_attention_heads`` queries and ``num_key_value_heads`` keys and
  values of ``head_dim``, causal, with no positions.

The modeling code's fourth kind, ``-`` (a dense squared-ReLU MLP), is in
no published pattern of this model and is not built.

The constructor's arguments are the keys of the published
``config.json``; ``layer_types`` (the kinds by name, one a layer), where
given, is the list of the layers that are built in place of the
pattern's, and ``experts_held`` (``(first, count)`` or a ``range``;
default all) the part of every expert layer's experts this device
holds. Not built: what moves ``expert_bias`` between steps, any
auxiliary loss, expert groups (``n_group`` = ``topk_group`` = 1 in the
published configuration: no group limit), a ``time_step_limit`` other
than the published ``(0, None)``.

Parameter prefixes: ``mamba_in_`` / ``mamba_out_`` and the mixer's own
(``mamba_conv_weight``, ``mamba_conv_bias``, ``mamba_A_log``,
``mamba_D``, ``mamba_dt_bias``, ``mamba_norm_weight``), ``attn_q_`` /
``attn_k_`` / ``attn_v_`` / ``attn_out_``, ``moe_experts_up_`` /
``moe_experts_down_`` (the expert axis first), ``moe_shared_up_`` /
``moe_shared_down_``, ``embed_`` / ``head_``. ``impl`` selects the
attention kernel ('dense' or 'flash'); ``remat`` wraps every layer in
``contrib.nn.Remat`` with that policy.
"""
from __future__ import annotations

from .. import nn
from ..block import HybridBlock
from ..contrib import nn as contrib_nn

__all__ = ["NemotronHBlock", "NemotronHLM", "nemotron_h_lm",
           "layer_types_of"]

PATTERN = {"M": "mamba", "E": "moe", "*": "attention"}
LAYER_TYPES = tuple(PATTERN.values())


def layer_types_of(pattern):
    """The layers' kinds by name from a ``hybrid_override_pattern``."""
    unknown = sorted(set(pattern) - set(PATTERN))
    if unknown:
        raise ValueError(f"hybrid_override_pattern holds {unknown}; known: "
                         f"{sorted(PATTERN)}")
    return tuple(PATTERN[c] for c in pattern)


class _PreNormResidual(HybridBlock):
    """``x + inner(norm(x))``: a layer, the unit ``remat`` wraps."""

    def __init__(self, norm, inner, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.norm = norm()
            self.inner = inner()

    def hybrid_forward(self, F, x):
        return x + self.inner(self.norm(x))


class NemotronHBlock(HybridBlock):
    """One layer of ``kind`` (``LAYER_TYPES``): its mixer is
    ``self.mixer``, its norm ``self.norm``. ``remat`` wraps the layer in
    ``contrib.nn.Remat``."""

    def __init__(self, kind, mixer, units, epsilon, remat=None, **kwargs):
        super().__init__(**kwargs)
        self.kind = kind
        with self.name_scope():
            blk = _PreNormResidual(
                lambda: nn.RMSNorm(units, epsilon, prefix="norm_"), mixer,
                prefix="")
            self.layer = blk if remat is None \
                else contrib_nn.Remat(blk, policy=remat)

    def _inner(self):
        return getattr(self.layer, "block", self.layer)  # under Remat

    mixer = property(lambda self: self._inner().inner)
    norm = property(lambda self: self._inner().norm)

    def hybrid_forward(self, F, x):
        return self.layer(x)


class NemotronHLM(HybridBlock):
    """(B, T) token ids -> (B, T, vocab_size) logits."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                         "EMEMEMEM*EMEMEMEME",
                 layer_types=None, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, mamba_num_heads=64,
                 mamba_head_dim=64, n_groups=8, ssm_state_size=128,
                 conv_kernel=4, chunk_size=128, time_step_limit=(0.0, None),
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_routed_experts=128, num_experts_per_tok=6,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 mlp_hidden_act="relu2", layer_norm_epsilon=1e-5,
                 experts_held=None, impl="dense", remat=None, **kwargs):
        super().__init__(**kwargs)
        if mlp_hidden_act != "relu2":
            raise ValueError(f"mlp_hidden_act {mlp_hidden_act!r}: only "
                             "'relu2' is built")
        low, high = time_step_limit
        if low > 0 or high not in (None, float("inf")):
            raise ValueError(f"time_step_limit {time_step_limit!r}: only "
                             "the published (0, None), which clamps no "
                             "softplus, is built")
        kinds = tuple(layer_types) if layer_types is not None \
            else layer_types_of(hybrid_override_pattern)
        unknown = sorted(set(kinds) - set(LAYER_TYPES))
        if unknown:
            raise ValueError(f"layer_types holds {unknown}; known: "
                             f"{LAYER_TYPES}")
        units, eps = hidden_size, layer_norm_epsilon
        mixers = {
            "mamba": lambda: contrib_nn.Mamba2Mixer(
                units, mamba_num_heads, mamba_head_dim, n_groups,
                ssm_state_size, conv_kernel=conv_kernel, chunk=chunk_size,
                epsilon=eps, dt_init=(time_step_min, time_step_max, time_step_floor),
                prefix="mamba_"),
            "moe": lambda: contrib_nn.SparseMoE(
                units, moe_intermediate_size, n_routed_experts,
                num_experts_per_tok, experts_held=experts_held,
                shared_hidden=moe_shared_expert_intermediate_size,
                renormalize=norm_topk_prob, score_func="sigmoid",
                route_scale=routed_scaling_factor, expert_bias=True,
                shared_gate=False, activation="relu2", prefix="moe_"),
            "attention": lambda: contrib_nn.GroupedQueryAttention(
                units, num_attention_heads, num_key_value_heads, head_dim,
                impl=impl, prefix="attn_"),
        }
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, units, prefix="embed_")
            self.blocks = nn.HybridSequential(prefix="blocks_")
            with self.blocks.name_scope():
                for kind in kinds:
                    self.blocks.add(NemotronHBlock(kind, mixers[kind], units,
                                                   eps, remat=remat))
            self.norm = nn.RMSNorm(units, eps, prefix="norm_")
            self.head = nn.Dense(vocab_size, use_bias=False, flatten=False,
                                 in_units=units, prefix="head_")

    def hybrid_forward(self, F, x):
        return self.head(self.norm(self.blocks(self.embed(x))))


def nemotron_h_lm(config=None, **kwargs):
    """``NemotronHLM`` from the dict of a ``config.json`` (keys the
    constructor does not take -- ``model_type``, ``num_hidden_layers``,
    ``max_position_embeddings`` and the like -- are passed over) and
    keyword arguments that override it; the defaults are the published
    Nemotron-H 30B-A3B tower's."""
    import inspect

    known = inspect.signature(NemotronHLM.__init__).parameters
    picked = {k: v for k, v in (config or {}).items() if k in known}
    return NemotronHLM(**{**picked, **kwargs})
