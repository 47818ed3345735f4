"""Train a small causal transformer LM with selectable attention kernels.

Demonstrates the round-5 Block-API attention path: the same model trains
with impl='dense' (any backend), impl='flash' (Pallas streaming kernel,
trainable via custom_vjp), or impl='ring' (sequence parallel over an
'sp' mesh axis). Reference analogue: gluonnlp transformer cells over
contrib/transformer.cc's interleaved matmuls.

Usage:
  python examples/transformer_lm.py --impl flash --seq-len 512
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx                                  # noqa: E402
from mxnet_tpu import autograd, gluon                   # noqa: E402
from mxnet_tpu.gluon import contrib, nn                 # noqa: E402


class TransformerLM(gluon.HybridBlock):
    def __init__(self, vocab, units, heads, n_layers, impl, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.embed = nn.Embedding(vocab, units)
            self.blocks = nn.HybridSequential()
            for _ in range(n_layers):
                self.blocks.add(_Layer(units, heads, impl))
            self.norm = nn.LayerNorm()
            self.head = nn.Dense(vocab, flatten=False)

    def hybrid_forward(self, F, x):
        h = self.embed(x)
        h = self.blocks(h)
        return self.head(self.norm(h))


class _Layer(gluon.HybridBlock):
    def __init__(self, units, heads, impl, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm()
            self.attn = contrib.MultiHeadAttention(units, heads, impl=impl,
                                                   causal=True)
            self.ln2 = nn.LayerNorm()
            self.ff1 = nn.Dense(units * 4, activation="relu", flatten=False)
            self.ff2 = nn.Dense(units, flatten=False)

    def hybrid_forward(self, F, x):
        x = x + self.attn(self.ln1(x))
        return x + self.ff2(self.ff1(self.ln2(x)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--impl", default="dense",
                    choices=["dense", "flash", "ring", "auto"])
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--units", type=int, default=128)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--steps", type=int, default=40)
    args = ap.parse_args()

    vocab = 64
    # the default context is the chip when there is one; impl='flash'
    # needs it (the Pallas kernel raises on a CPU device)
    ctx = mx.current_context()
    with ctx:
        model = TransformerLM(vocab, args.units, args.heads, args.layers,
                              args.impl)
        model.initialize(mx.initializer.Xavier(), ctx=ctx)
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": 3e-3})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    rng = np.random.RandomState(0)
    # learnable synthetic language: x_{t+1} = (5*x_t + 3) mod vocab
    seq = np.zeros((args.batch, args.seq_len + 1), np.int64)
    seq[:, 0] = rng.randint(0, vocab, args.batch)
    for t in range(args.seq_len):
        seq[:, t + 1] = (5 * seq[:, t] + 3) % vocab
    x = mx.nd.array(seq[:, :-1].astype(np.float32), ctx=ctx)
    y = mx.nd.array(seq[:, 1:].astype(np.float32), ctx=ctx)

    t0 = time.time()
    for step in range(args.steps):
        with autograd.record():
            logits = model(x)
            loss = loss_fn(logits, y).mean()
        loss.backward()
        trainer.step(1)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss.asnumpy()):.4f} "
                  f"({time.time() - t0:.1f}s)")
    final = float(loss.asnumpy())
    print(f"final loss ({args.impl}): {final:.4f}")
    assert final < 1.0, "LM did not learn"


if __name__ == "__main__":
    main()
