"""GPT-2 through the program's normal paths, training and serving.

Training: ``gluon.model_zoo.transformer.TransformerLM`` at the
configuration's sizes -> ``net.initialize(Xavier)`` on the chip from the
seed -> one eager forward that materialises the deferred shapes ->
``ShardedTrainer`` (Adam, bf16 policy) -> ``capture``.
Serving: the same net without gradient buffers -> ``DecodePredictor``
over a paged float32 K/V pool -> ``DecodeBatcher``.

Also: the model's FLOPs per token from its shapes, the ring of seeded
token batches, the positions whose logits the training check compares,
and the laying of the program's parameters into the plain reference's
tree.
"""
from __future__ import annotations

import functools

from benchmarks.models import sharded


def matmul_params(config):
    d, v = config["n_embd"], config["vocab_size"]
    inner = config.get("n_inner") or 4 * d
    per_block = 3 * d * d + d * d + 2 * d * inner
    return config["n_layer"] * per_block + d * v    # blocks + untied head


def flops_per_item(config, traffic):
    """FLOPs to train on one token at sequence length T: 6 per matmul
    parameter (2 forward, 4 backward) plus causal attention, whose
    QK^T and PV products touch (T + 1) / 2 keys per query on average:
    3 x 2 x 2 x d x (T + 1) / 2 per layer. Nothing recomputed is
    counted."""
    t = int(traffic["seq_len"])
    attn = config["n_layer"] * 3 * 2 * 2 * config["n_embd"] * (t + 1) / 2
    return 6 * matmul_params(config) + attn


def reference_weights(net, config):
    """The net's parameters as the plain reference's tree, by the
    program's own parameter names (``decode_param_names`` gives the
    canonical order: embed, pos, per block the twelve of
    ``_BLOCK_PARAM_SUFFIXES``, final norm, head)."""
    from mxnet_tpu.gluon.model_zoo import transformer as tf

    spec = tf.decode_spec(net)
    params = net.collect_params()
    flat = [params[n].data().data_
            for n in tf.decode_param_names(spec, list(params))]
    keys = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
            "ln2_g", "ln2_b", "fc_w", "fc_b", "proj_w", "proj_b")
    k = len(keys)
    blocks = [dict(zip(keys, flat[2 + i * k: 2 + (i + 1) * k]))
              for i in range(config["n_layer"])]
    return {"wte": flat[0], "wpe": flat[1], "blocks": blocks,
            "lnf_g": flat[-4], "lnf_b": flat[-3],
            "head_w": flat[-2], "head_b": flat[-1]}


def _build_net(config, seed, impl, remat, trainable):
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import transformer as tf

    mx.random.seed(seed)
    net = tf.TransformerLM(
        config["vocab_size"], config["n_embd"], config["n_head"],
        config["n_layer"], max_len=config["n_positions"], impl=impl,
        remat=remat)
    if not trainable:
        net.collect_params().setattr("grad_req", "null")
    net.initialize(mx.initializer.Xavier())
    # deferred shapes; 128 tokens is a length every attention impl takes
    probe = min(128, config["n_positions"])
    net(mx.nd.array(np.zeros((1, probe), np.int32),
                    dtype="int32")).wait_to_read()
    return net


def build_trainer(config, traffic, seed, devices, reference):
    import jax

    train = config["train"]
    net = _build_net(config, seed, train["attention_impl"],
                     train.get("block_remat"), trainable=True)
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

    return sharded.TrainJob(
        net, trainer, step, batch * t, make_ring,
        lambda: reference_weights(net, config),
        functools.partial(reference.check_outputs,
                          n_head=config["n_head"]), train, positions)


class ServeJob:
    """What ``loops/open_loop_decode.py`` drives."""

    def __init__(self, net, predictor, reference_weights, reference_fn,
                 vocab_size):
        self.net = net
        self.predictor = predictor
        self.reference_weights = reference_weights  # () -> the plain tree
        self.reference_fn = reference_fn    # (weights, tokens, positions)
        self.vocab_size = vocab_size

    def reference_logits(self, tokens, positions):
        import jax

        return jax.jit(self.reference_fn)(self.reference_weights(), tokens,
                                          positions)


def build_server(config, traffic, seed, devices, reference):
    from mxnet_tpu import serving

    net = _build_net(config, seed, "dense", None, trainable=False)
    pool = traffic["pool"]
    predictor = serving.DecodePredictor(
        net, page_size=pool["page_size"], num_pages=pool["num_pages"],
        max_seqs=pool["max_seqs"],
        prefill_buckets=tuple(pool["prefill_buckets"]),
        kv_dtype=config["serve"]["kv_dtype"], warmup=False)
    return ServeJob(
        net, predictor, lambda: reference_weights(net, config),
        functools.partial(reference.logits_at, n_head=config["n_head"]),
        config["vocab_size"])
