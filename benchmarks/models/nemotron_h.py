"""Nemotron-H's tower through the program's normal training path.

``gluon.model_zoo.nemotron_h`` at the configuration's sizes (the
published widths; of the depth, the experts and the vocabulary, the
part this chip holds) -> ``net.initialize(Xavier)`` on the chip from the
seed -> ``ShardedTrainer`` (Adam, bf16 policy, every layer recomputed in
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

from benchmarks.models import sharded
# at the top on purpose: a program without this model fails here, at
# once, before anything is built
from mxnet_tpu.gluon.model_zoo import nemotron_h as zoo

_JOB = None     # the last job built in this process, for expert_tokens()


def held(config):
    """(first expert, experts) of every layer that this chip holds."""
    return int(config["deployment"]["first_expert"]), config["num_experts"]


def layer_counts(config):
    """{kind: layers of that kind that are built}, from ``layer_types``
    (which ``num_layers`` has to count)."""
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_layers"]:
        raise ValueError(f"layer_types lists {len(kinds)} layers, "
                         f"num_layers says {config['num_layers']}")
    return {kind: kinds.count(kind) for kind in zoo.LAYER_TYPES}


def mamba_widths(config):
    """(heads, head_dim, groups, state, the convolved channels, the
    inner width) of a Mamba-2 mixer."""
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    g, n = config["n_groups"], config["ssm_state_size"]
    return h, p, g, n, h * p + 2 * g * n, h * p


def matmul_params(config):
    """Matrix-product parameters one token touches in a step here."""
    d = config["hidden_size"]
    h, _, _, _, conv_dim, inner = mamba_widths(config)
    mamba = d * (conv_dim + inner + h) + inner * d
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    attention = 2 * d * q + 2 * d * kv          # q, o; k, v
    expert = 2 * d * config["moe_intermediate_size"]    # up, down
    # of a token's num_experts_per_tok choices among all the experts,
    # the expected number that falls on those held here
    routed = config["num_experts_per_tok"] * config["num_experts"] \
        / config["published"]["num_experts"]
    moe = d * config["published"]["num_experts"] + routed * expert \
        + 2 * d * config["moe_shared_expert_intermediate_size"]
    n = layer_counts(config)
    return n["mamba"] * mamba + n["attention"] * attention + n["moe"] * moe \
        + d * config["vocab_size"]


def flops_per_item(config, traffic):
    """FLOPs to train on one token at sequence length T: 6 per
    matrix-product parameter it touches here (2 forward, 4 backward;
    the routed experts at their expected share), causal attention as
    GPT-2's count (QK^T and PV over (T + 1) / 2 keys a query: 3 x 2 x 2
    x heads x head_dim x (T + 1) / 2), and the state-space recurrence at
    its least, 3 x head_dim x state multiply-adds a head a token (decay,
    write, read), forward and twice that backward. What the chunked form
    computes beyond the recurrence (the masked products inside a chunk)
    and nothing recomputed is counted."""
    t = int(traffic["seq_len"])
    n = layer_counts(config)
    h, p, _, state, _, _ = mamba_widths(config)
    attn = n["attention"] * 3 * 2 * 2 * config["num_attention_heads"] \
        * config["head_dim"] * (t + 1) / 2
    scan = n["mamba"] * 6 * 3 * h * p * state
    return 6 * matmul_params(config) + attn + scan


def reference_sizes(config):
    low, high = config["time_step_limit"]
    return {"layer_types": tuple(config["layer_types"]),
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "mamba_heads": config["mamba_num_heads"],
            "n_groups": config["n_groups"],
            "time_step_limit": (float(low),
                                None if high is None else float(high)),
            "eps": config["layer_norm_epsilon"],
            "top_k": config["num_experts_per_tok"],
            "route_norm": config["norm_topk_prob"],
            "route_scale": config["routed_scaling_factor"],
            "first_expert": held(config)[0]}


def reference_weights(net):
    """The net's parameters as the plain reference's tree, read off the
    blocks themselves. The program lays ``in_proj``'s rows [xBC | z |
    dt] (the convolved channels first, read in place by the
    convolution); the reference takes the published [z | xBC | dt]."""
    import jax.numpy as jnp

    def w(param):
        return param.data().data_

    layers = []
    for blk in net.blocks:
        mix = blk.mixer
        layer = {"norm": w(blk.norm.weight)}
        if blk.kind == "mamba":
            conv_dim = mix.conv_weight.shape[0]
            inner = mix.norm_weight.shape[0]
            rows = w(mix.in_proj.weight)
            layer["mamba"] = {
                "in_w": jnp.concatenate(
                    [rows[conv_dim:conv_dim + inner], rows[:conv_dim],
                     rows[conv_dim + inner:]]),
                "conv_w": w(mix.conv_weight), "conv_b": w(mix.conv_bias),
                "A_log": w(mix.A_log), "D": w(mix.D),
                "dt_bias": w(mix.dt_bias), "norm_w": w(mix.norm_weight),
                "out_w": w(mix.out_proj.weight)}
        elif blk.kind == "moe":
            layer["moe"] = {
                "router_w": w(mix.router_weight),
                "expert_bias": w(mix.expert_bias),
                "up": w(mix.experts_up_weight),
                "down": w(mix.experts_down_weight),
                "shared_up_w": w(mix.shared.up.weight),
                "shared_down_w": w(mix.shared.down.weight)}
        else:
            layer["attn"] = {
                "q_w": w(mix.q_proj.weight), "k_w": w(mix.k_proj.weight),
                "v_w": w(mix.v_proj.weight), "o_w": w(mix.out_proj.weight)}
        layers.append(layer)
    return {"embed": w(net.embed.weight), "layers": layers,
            "norm": w(net.norm.weight), "head_w": w(net.head.weight)}


def routing_parts(net, config):
    """The trunk in the order the tokens pass it, for
    ``sharded.balance_routing``: the embedding, then each layer; an
    expert layer names its router, which sees the layer's norm of its
    input."""
    parts = [sharded.Part([net.embed], net.embed)]
    for blk in net.blocks:
        router = None
        if blk.kind == "moe":
            router = {"weight": blk.mixer.router_weight.name,
                      "bias": blk.mixer.expert_bias.name,
                      "top_k": config["num_experts_per_tok"],
                      "score_func": "sigmoid", "held": held(config)}
        parts.append(sharded.Part([blk.layer], blk.layer, seen=blk.norm,
                                  router=router))
    return parts


def _build_net(config, seed, impl, remat):
    import mxnet_tpu as mx

    mx.random.seed(seed)
    net = zoo.nemotron_h_lm(
        config, n_routed_experts=config["published"]["num_experts"],
        experts_held=held(config), impl=impl, remat=remat)
    net.initialize(mx.initializer.Xavier())     # every shape is given
    return net


def build_trainer(config, traffic, seed, devices, reference):
    global _JOB
    import jax

    train = config["train"]
    layer_counts(config)
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
        if blk.kind != "moe":
            continue
        counts = np.asarray(aux[blk.mixer.expert_tokens.name], np.float64)
        out.append((counts[:-1], float(counts[-1])))
    return out
