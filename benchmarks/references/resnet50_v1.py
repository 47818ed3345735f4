"""ResNet-50 v1, plain float32 reference: forward pass and loss.

He et al. 2015 (arXiv:1512.03385), table 1, the 50-layer column, as
MXNet's model zoo builds it ("v1": the stride of a down-sampling
bottleneck sits on its first 1x1 convolution; the two 1x1 convolutions
of a bottleneck carry a bias, the 3x3 and the shortcut do not). Straight
``jax.numpy`` in float32 under ``default_matmul_precision("highest")``:
no kernels, no layout tricks, no mixed precision, nothing imported from
the program. Batch normalisation is in training mode (statistics of the
batch, biased variance), as the training step runs it.

Departure from the paper, because the configuration under test has it:
the stem is an 8x8 / stride 2 convolution (padding 4 before, 3 after),
which is what a space-to-depth(2) input followed by a 4x4 / stride 1
convolution computes; the paper's 7x7 is the special case whose first
row and column of taps are zero.

Weights arrive as a plain tree (see ``benchmarks/models/resnet50_v1.py``
for how the program's parameters are laid into it):

    {"stem": {"w": (8, 8, 3, 64) HWIO, "gamma", "beta"},
     "stages": [[block, ...] x 4],      3, 4, 6, 3 blocks
     "fc": {"w": (classes, 2048), "b"}}
    block = {"w1", "b1", "g1", "be1",   1x1 (HWIO), bias, BN scale / shift
             "w2", "g2", "be2",         3x3
             "w3", "b3", "g3", "be3",   1x1
             "wd", "gd", "bed"}         shortcut 1x1 + BN (first block only)
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _bn(x, gamma, beta, seen):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    seen.append((mean, var))
    return (x - mean) * lax.rsqrt(var + BN_EPS) * gamma + beta


def _bottleneck(x, p, stride, seen):
    same = ((0, 0), (0, 0))
    y = jax.nn.relu(_bn(_conv(x, p["w1"], stride, same) + p["b1"],
                        p["g1"], p["be1"], seen))
    y = jax.nn.relu(_bn(_conv(y, p["w2"], 1, ((1, 1), (1, 1))),
                        p["g2"], p["be2"], seen))
    y = _bn(_conv(y, p["w3"], 1, same) + p["b3"], p["g3"], p["be3"], seen)
    if "wd" in p:
        x = _bn(_conv(x, p["wd"], stride, same), p["gd"], p["bed"], seen)
    return jax.nn.relu(y + x)


def logits(weights, images, seen=None):
    """``images`` (N, 3, H, W) float32 -> (N, classes) float32. ``seen``,
    a list, gets each BatchNorm layer's (mean, variance) of the batch in
    the order they run: stem; per block the three of the body, then the
    shortcut's."""
    seen = [] if seen is None else seen
    with jax.default_matmul_precision("highest"):
        x = jnp.transpose(images.astype(jnp.float32), (0, 2, 3, 1))
        stem = weights["stem"]
        x = _conv(x, stem["w"], 2, ((4, 3), (4, 3)))
        x = jax.nn.relu(_bn(x, stem["gamma"], stem["beta"], seen))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1),
                              ((0, 0), (1, 1), (1, 1), (0, 0)))
        for s, stage in enumerate(weights["stages"]):
            for b, block in enumerate(stage):
                x = _bottleneck(x, block, 2 if (b == 0 and s > 0) else 1,
                                seen)
        x = jnp.mean(x, axis=(1, 2))
        return x @ weights["fc"]["w"].T + weights["fc"]["b"]


def check_outputs(weights, images, labels):
    """What the training check compares, from one pass: the mean softmax
    cross-entropy against integer ``labels`` (N,), the logits it was
    taken from, and every BatchNorm channel's mean and variance of the
    batch, layer after layer, as two vectors."""
    seen = []
    out = logits(weights, images, seen)
    picked = jnp.take_along_axis(
        jax.nn.log_softmax(out, axis=-1),
        labels.astype(jnp.int32)[:, None], axis=-1)
    return (-jnp.mean(picked), out,
            (jnp.concatenate([m for m, _ in seen]),
             jnp.concatenate([v for _, v in seen])))
