"""Qwen3-Next through the program's normal training path.

``gluon.model_zoo.qwen3_next`` at the configuration's sizes (the
published widths; of the depth, the experts and the vocabulary, the
part this chip holds) -> ``net.initialize(Xavier)`` on the chip from the
seed -> ``ShardedTrainer`` (Adam, bf16 policy, every layer recomputed in
the backward pass) -> ``capture``.

Also: the model's FLOPs per token from its shapes, the ring of seeded
token batches, the positions whose logits the training check compares,
the laying of the program's parameters into the plain reference's tree,
and the expert layers' token counts of the last step for the readers.
"""
from __future__ import annotations

import functools

from benchmarks.models import sharded
# at the top on purpose: a program without this model fails here, at
# once, before anything is built
from mxnet_tpu.gluon.model_zoo import qwen3_next as zoo

_JOB = None     # the last job built in this process, for expert_tokens()


def _layers(config):
    """(linear-attention layers, full-attention layers) held here."""
    full = config["num_layers"] // config["full_attention_interval"]
    return config["num_layers"] - full, full


def held(config):
    """(first expert, experts) of every layer that this chip holds."""
    return int(config["deployment"]["first_expert"]), config["num_experts"]


def matmul_params(config):
    """Matrix-product parameters one token touches in a step here."""
    d = config["hidden_size"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    linear = d * (2 * key + 2 * value) \
        + d * 2 * config["linear_num_value_heads"] + value * d
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    full = d * 2 * q + 2 * d * kv + q * d
    expert = 3 * d * config["moe_intermediate_size"]
    # of a token's num_experts_per_tok choices among all the experts,
    # the expected number that falls on those held here
    routed = config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]
    moe = d * config["published"]["num_experts"] + routed * expert \
        + 3 * d * config["shared_expert_intermediate_size"] + d
    n_linear, n_full = _layers(config)
    return n_linear * linear + n_full * full \
        + config["num_layers"] * moe + d * config["vocab_size"]


def flops_per_item(config, traffic):
    """FLOPs to train on one token at sequence length T: 6 per
    matrix-product parameter it touches here (2 forward, 4 backward;
    the routed experts at their expected share), causal attention in
    the full-attention layers as GPT-2's count (QK^T and PV over
    (T + 1) / 2 keys a query: 3 x 2 x 2 x heads x head_dim x (T + 1) /
    2), and the delta rule's recurrence, 3 x Dk x Dv multiply-adds a
    value head a token (decay and read, write, output). Nothing
    recomputed is counted."""
    t = int(traffic["seq_len"])
    n_linear, n_full = _layers(config)
    attn = n_full * 3 * 2 * 2 * config["num_attention_heads"] \
        * config["head_dim"] * (t + 1) / 2
    rule = n_linear * 6 * 3 * config["linear_num_value_heads"] \
        * config["linear_key_head_dim"] * config["linear_value_head_dim"]
    return 6 * matmul_params(config) + attn + rule


def reference_sizes(config):
    return {"heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "key_heads": config["linear_num_key_heads"],
            "value_heads": config["linear_num_value_heads"],
            "rotary_dim": int(config["head_dim"]
                              * config["partial_rotary_factor"]),
            "rope_theta": float(config["rope_theta"]),
            "eps": config["rms_norm_eps"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": held(config)[0]}


def reference_weights(net):
    """The net's parameters as the plain reference's tree, read off the
    blocks themselves."""
    def w(param):
        return param.data().data_

    layers = []
    for blk in net.blocks:
        mix, moe = blk.attn, blk.moe
        layer = {"norm1": w(blk.norm1.weight), "norm2": w(blk.norm2.weight),
                 "moe": {"router_w": w(moe.router_weight),
                         "gate_up": w(moe.experts_gate_up_weight),
                         "down": w(moe.experts_down_weight),
                         "shared_gate_up_w": w(moe.shared.gate_up.weight),
                         "shared_down_w": w(moe.shared.down.weight),
                         "shared_gate_w": w(moe.shared_gate.weight)}}
        if hasattr(mix, "qkvz_proj"):
            layer["deltanet"] = {
                "qkvz_w": w(mix.qkvz_proj.weight),
                "ba_w": w(mix.ba_proj.weight),
                "conv_w": w(mix.conv_weight), "A_log": w(mix.A_log),
                "dt_bias": w(mix.dt_bias), "norm": w(mix.norm.weight),
                "out_w": w(mix.out_proj.weight)}
        else:
            layer["attn"] = {
                "q_w": w(mix.q_proj.weight), "k_w": w(mix.k_proj.weight),
                "v_w": w(mix.v_proj.weight), "o_w": w(mix.out_proj.weight),
                "q_norm": w(mix.q_norm.weight),
                "k_norm": w(mix.k_norm.weight)}
        layers.append(layer)
    return {"embed": w(net.embed.weight), "layers": layers,
            "norm": w(net.norm.weight), "head_w": w(net.head.weight)}


def _build_net(config, seed, impl, remat):
    import mxnet_tpu as mx

    mx.random.seed(seed)
    net = zoo.qwen3_next_lm(
        config, num_hidden_layers=config["num_layers"],
        num_experts=config["published"]["num_experts"],
        experts_held=held(config), impl=impl, remat=remat)
    net.initialize(mx.initializer.Xavier())     # every shape is given
    return net


def build_trainer(config, traffic, seed, devices, reference):
    global _JOB
    import jax

    train = config["train"]
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
                          sizes=reference_sizes(config)), train, positions)
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
        counts = np.asarray(aux[blk.moe.expert_tokens.name], np.float64)
        out.append((counts[:-1], float(counts[-1])))
    return out
