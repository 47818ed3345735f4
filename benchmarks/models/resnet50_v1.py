"""ResNet-50 v1 through the program's normal training path.

``gluon.model_zoo.vision.resnet50_v1(layout="NHWC", stem="s2d")`` ->
``net.initialize(Xavier)`` on the chip from the seed -> the eager
forward that materialises the deferred shapes -> ``ShardedTrainer`` ->
``capture``. Also: the model's FLOPs per image from its shapes, the ring
of seeded batches made on the device, the laying of the program's
parameters into the plain reference's tree, and the reading of the
batch's BatchNorm statistics out of the moving ones.
"""
from __future__ import annotations

from benchmarks.models import sharded


def flops_per_item(config, traffic):
    """FLOPs to train on one image: 3 x the forward pass (the backward
    pass costs two forwards), 2 FLOPs per multiply-add, convolutions and
    the classifier only, from the shapes of table 1 with the paper's 7x7
    stem. He et al.'s "3.8 x 10^9 FLOPs" counts multiply-adds, so this
    is about twice 3 x that figure."""
    size = config["image_size"]
    hw = size // 2                                   # stem, stride 2
    macs = 7 * 7 * 3 * config["stem_channels"] * hw * hw
    hw //= 2                                         # max-pool, stride 2
    c_in = config["stem_channels"]
    for s, (blocks, c_out) in enumerate(zip(config["stage_blocks"],
                                            config["stage_channels"])):
        mid = c_out // 4
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            out = hw // stride
            macs += c_in * mid * out * out           # 1x1, carries the stride
            macs += 9 * mid * mid * out * out        # 3x3
            macs += mid * c_out * out * out          # 1x1
            if b == 0:
                macs += c_in * c_out * out * out     # shortcut 1x1
            c_in, hw = c_out, out
    macs += c_in * config["num_classes"]
    return 3 * 2 * macs


def reference_weights(net):
    """The net's parameters as the plain reference's tree: OHWI kernels
    to HWIO, and the space-to-depth stem's (64, 4, 4, 12) kernel unfolded
    to the 8x8 / stride 2 kernel over 3 channels it stands for (s2d
    channel dy*6 + dx*3 + c at tap (u, v) is tap (2u+dy, 2v+dx) of
    channel c)."""
    import jax.numpy as jnp

    def arr(p):
        return p.data().data_

    def hwio(conv):
        return jnp.transpose(arr(conv.weight), (1, 2, 3, 0))

    def bn(layer):
        return arr(layer.gamma), arr(layer.beta)

    feats = list(net.features)
    w4 = arr(feats[0].weight)                        # (O, 4, 4, 12)
    o = w4.shape[0]
    w8 = w4.reshape(o, 4, 4, 2, 2, 3)                # (O, u, v, dy, dx, c)
    w8 = jnp.transpose(w8, (1, 3, 2, 4, 5, 0))       # (u, dy, v, dx, c, O)
    gamma, beta = bn(feats[1])
    tree = {"stem": {"w": w8.reshape(8, 8, 3, o), "gamma": gamma,
                     "beta": beta}, "stages": []}
    for stage in feats[4:8]:
        blocks = []
        for blk in stage:
            body = list(blk.body)
            p = {"w1": hwio(body[0]), "b1": arr(body[0].bias),
                 "w2": hwio(body[3]),
                 "w3": hwio(body[6]), "b3": arr(body[6].bias)}
            p["g1"], p["be1"] = bn(body[1])
            p["g2"], p["be2"] = bn(body[4])
            p["g3"], p["be3"] = bn(body[7])
            if blk.downsample is not None:
                down = list(blk.downsample)
                p["wd"] = hwio(down[0])
                p["gd"], p["bed"] = bn(down[1])
            blocks.append(p)
        tree["stages"].append(blocks)
    tree["fc"] = {"w": arr(net.output.weight), "b": arr(net.output.bias)}
    return tree


def batch_statistics(net, momentum):
    """(moving statistics after one training-mode forward) -> every
    BatchNorm channel's mean and variance of that batch, as two vectors
    in the reference's order (stem; per block the body's three, then the
    shortcut's). The net starts from mean 0 and variance 1, and a layer
    moves them to momentum x old + (1 - momentum) x the batch's."""
    import jax.numpy as jnp

    feats = list(net.features)
    layers = [feats[1]]
    for stage in feats[4:8]:
        for blk in stage:
            body = list(blk.body)
            layers += [body[1], body[4], body[7]]
            if blk.downsample is not None:
                layers.append(list(blk.downsample)[1])
    named = [(bn.running_mean.name, bn.running_var.name) for bn in layers]
    m = float(momentum)

    def read(moved):
        return (jnp.concatenate([moved[mean] / (1 - m)
                                 for mean, _ in named]),
                jnp.concatenate([(moved[var] - m) / (1 - m)
                                 for _, var in named]))

    return read


def build_trainer(config, traffic, seed, devices, reference):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    size, classes = config["image_size"], config["num_classes"]
    mx.random.seed(seed)
    net = vision.resnet50_v1(layout="NHWC", stem="s2d", classes=classes)
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 3, size, size))).wait_to_read()   # deferred shapes
    trainer, step = sharded.make_trainer(net, config, traffic, devices)
    batch = int(traffic["batch"])

    def make_ring(ring_seed, n):
        def gen(key):
            out = []
            for k in jax.random.split(key, n):
                kx, ky = jax.random.split(k)
                x = jax.random.uniform(kx, (batch, 3, size, size),
                                       jnp.float32)
                y = jax.random.randint(ky, (batch,), 0, classes)
                out.append((x, y.astype(jnp.float32)))
            return out

        return jax.jit(gen, out_shardings=trainer.batch_sharding)(
            jax.random.key(ring_seed))

    return sharded.TrainJob(
        net, trainer, step, batch, make_ring, lambda: reference_weights(net),
        lambda w, x, y, positions: reference.check_outputs(w, x, y),
        config["train"],
        statistics=batch_statistics(net, config["bn_momentum"]))
