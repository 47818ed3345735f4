"""Trinity-Mini through the program's normal training path.

``gluon.model_zoo.trinity`` at the configuration's sizes (the published
widths; of the depth, the experts and the vocabulary, the part this
chip holds) -> ``net.initialize(Xavier)`` on the chip from the seed ->
``ShardedTrainer`` (Adam, bf16 policy, every half-layer recomputed in
the backward pass) -> ``capture``.

Also: the model's FLOPs per token from its shapes, the ring of seeded
token batches, the positions whose logits the training check compares,
the laying of the program's parameters into the plain reference's tree,
the trunk as the parts ``sharded.balance_routing`` walks (a traffic
with ``"routing": "balanced"``), and the expert layers' token counts of
the last step for the readers.
"""
from __future__ import annotations

import functools

from benchmarks import window_attention
from benchmarks.models import sharded
# at the top on purpose: a program without this model fails here, at
# once, before anything is built
from mxnet_tpu.gluon.model_zoo import trinity as zoo

_JOB = None     # the last job built in this process, for expert_tokens()


def held(config):
    """(first expert, experts) of every layer that this chip holds."""
    return int(config["deployment"]["first_expert"]), config["num_experts"]


def _layers(config):
    """(window layers, full-attention layers, dense layers, expert
    layers) that are built."""
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_layers"]:
        raise ValueError(f"layer_types lists {len(kinds)} layers, "
                         f"num_layers says {config['num_layers']}")
    window = kinds.count("sliding_attention")
    dense = config["num_dense_layers"]
    return window, len(kinds) - window, dense, len(kinds) - dense


def matmul_params(config):
    """Matrix-product parameters one token touches in a step here."""
    d = config["hidden_size"]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    attention = 2 * d * q + 2 * d * kv + d * q      # q, o; k, v; the gate
    expert = 3 * d * config["moe_intermediate_size"]
    # of a token's num_experts_per_tok choices among all the experts,
    # the expected number that falls on those held here
    routed = config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]
    moe = d * config["published"]["num_experts"] + routed * expert \
        + config["num_shared_experts"] * expert
    _, _, dense, sparse = _layers(config)
    return config["num_layers"] * attention \
        + dense * 3 * d * config["intermediate_size"] + sparse * moe \
        + d * config["vocab_size"]


def seen_pairs(t, window=None):
    """(query, key) pairs of a sequence of ``t`` with ``0 <= i - j``
    (``< window``): the count the window's rooflines use."""
    return window_attention.seen_pairs(t, t if window is None else window)


def flops_per_item(config, traffic):
    """FLOPs to train on one token at sequence length T: 6 per
    matrix-product parameter it touches here (2 forward, 4 backward;
    the routed experts at their expected share), and attention by the
    keys a query really sees (QK^T and PV, forward and twice that
    backward: 3 x 2 x 2 x heads x head_dim a key): (T + 1) / 2 in a
    full layer, ``seen_pairs(T, window) / T`` in a window layer.
    Nothing masked and nothing recomputed is counted, so a kernel that
    skips the tiles behind the window cannot flatter ``step_mfu``."""
    t = int(traffic["seq_len"])
    window, full, _, _ = _layers(config)
    keys = (full * seen_pairs(t)
            + window * seen_pairs(t, config["sliding_window"])) / t
    a_key = 3 * 2 * 2 * config["num_attention_heads"] * config["head_dim"]
    return 6 * matmul_params(config) + a_key * keys


def reference_sizes(config):
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "layer_types": tuple(config["layer_types"]),
            "window": config["sliding_window"],
            "rope_theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"],
            "top_k": config["num_experts_per_tok"],
            "route_norm": config["route_norm"],
            "route_scale": config["route_scale"],
            "first_expert": held(config)[0],
            "embed_scale": config["hidden_size"] ** 0.5
            if config["mup_enabled"] else 1.0}


def reference_weights(net):
    """The net's parameters as the plain reference's tree, read off the
    blocks themselves."""
    def w(param):
        return param.data().data_

    layers = []
    for blk in net.blocks:
        mix, mlp = blk.attn, blk.mlp
        layer = {"norm_a": w(blk.input_layernorm.weight),
                 "norm_b": w(blk.post_attention_layernorm.weight),
                 "norm_c": w(blk.pre_mlp_layernorm.weight),
                 "norm_d": w(blk.post_mlp_layernorm.weight),
                 "attn": {
                     "q_w": w(mix.q_proj.weight), "k_w": w(mix.k_proj.weight),
                     "v_w": w(mix.v_proj.weight),
                     "gate_w": w(mix.gate_proj.weight),
                     "o_w": w(mix.out_proj.weight),
                     "q_norm": w(mix.q_norm.weight),
                     "k_norm": w(mix.k_norm.weight)}}
        if hasattr(mlp, "router_weight"):
            layer["moe"] = {
                "router_w": w(mlp.router_weight),
                "expert_bias": w(mlp.expert_bias),
                "gate_up": w(mlp.experts_gate_up_weight),
                "down": w(mlp.experts_down_weight),
                "shared_gate_up_w": w(mlp.shared.gate_up.weight),
                "shared_down_w": w(mlp.shared.down.weight)}
        else:
            layer["mlp"] = {"gate_up_w": w(mlp.gate_up.weight),
                            "down_w": w(mlp.down.weight)}
        layers.append(layer)
    return {"embed": w(net.embed.weight), "layers": layers,
            "norm": w(net.norm.weight), "head_w": w(net.head.weight)}


def routing_parts(net, config):
    """The trunk in the order the tokens pass it, for
    ``sharded.balance_routing``: the embedding (scaled as ``TrinityLM``
    scales it), then each layer's two halves; the second half of an
    expert layer names its router, which sees ``pre_mlp_layernorm`` of
    the half's input."""
    def embed(tokens):
        h = net.embed(tokens)
        return h if net._embed_scale is None else h * net._embed_scale

    parts = [sharded.Part([net.embed], embed)]
    for blk in net.blocks:
        parts.append(sharded.Part([blk.mix], blk.mix))
        router = None
        if hasattr(blk.mlp, "expert_bias"):
            router = {"weight": blk.mlp.router_weight.name,
                      "bias": blk.mlp.expert_bias.name,
                      "top_k": config["num_experts_per_tok"],
                      "score_func": config["score_func"],
                      "held": held(config)}
        parts.append(sharded.Part([blk.ffn], blk.ffn,
                                  seen=blk.pre_mlp_layernorm, router=router))
    return parts


def _build_net(config, seed, impl, remat):
    import mxnet_tpu as mx

    mx.random.seed(seed)
    net = zoo.trinity_lm(
        config, num_experts=config["published"]["num_experts"],
        experts_held=held(config), impl=impl, remat=remat)
    net.initialize(mx.initializer.Xavier())     # every shape is given
    return net


def build_trainer(config, traffic, seed, devices, reference):
    global _JOB
    import jax

    train = config["train"]
    _layers(config)
    net = _build_net(config, seed, train["attention_impl"],
                     train.get("block_remat"))
    trainer, step = sharded.make_trainer(net, config, traffic, devices)
    batch, t = int(traffic["batch"]), int(traffic["seq_len"])
    vocab = config["vocab_size"]

    def make_ring(ring_seed, n):
        def gen(key):
            out = []
            for k in jax.random.split(key, n):
                toks = jax.random.randint(k, (batch, t + 1), 0, vocab)
                out.append((toks[:, :-1], toks[:, 1:]))
            return out

        return jax.jit(gen, out_shardings=trainer.batch_sharding)(
            jax.random.key(ring_seed))

    def positions(check_seed):
        """Of each row, the last position and seeded others."""
        import numpy as np

        per_row = int(train["check_positions_per_row"])
        picked = np.random.default_rng([int(check_seed), 5]).integers(
            0, t, (batch, per_row), dtype=np.int32)
        picked[:, -1] = t - 1
        return jax.device_put(picked, trainer.batch_sharding)

    _JOB = sharded.TrainJob(
        net, trainer, step, batch * t, make_ring,
        lambda: reference_weights(net),
        functools.partial(reference.check_outputs,
                          sizes=reference_sizes(config)), train, positions,
        routing_parts=lambda: routing_parts(net, config))
    return _JOB


def expert_tokens():
    """Per expert layer, in order, what its ``expert_tokens`` state
    holds after the trainer's last step: (assignments to each held
    expert, tokens that chose no held expert). None before a trainer is
    built."""
    import numpy as np

    if _JOB is None:
        return None
    aux = _JOB.trainer.aux
    out = []
    for blk in _JOB.net.blocks:
        if not hasattr(blk.mlp, "expert_tokens"):
            continue
        counts = np.asarray(aux[blk.mlp.expert_tokens.name], np.float64)
        out.append((counts[:-1], float(counts[-1])))
    return out
