"""Framework-vs-replica performance harness (VERDICT r4 item 1a).

Builds the SAME ResNet-50 v1 (NHWC + space-to-depth stem) train step two
ways — through the framework (gluon net -> ShardedTrainer) and as a
hand-written pure-jax replica — compiles both, and reports:

- instruction-category counts from the optimized HLO (fusions, copies,
  convolutions) to localize trace-structure divergence,
- cost_analysis() bytes-accessed (the HBM-roofline predictor),
- measured img/s for both (data-dependency-chained timing loop).

Usage: python tools/perf_replica.py [--bs 256] [--iters 30] [--dump-hlo]
"""
from __future__ import annotations

import argparse
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --------------------------------------------------------------- replica

def replica_init(rng, dtype=np.float32):
    """Parameters for resnet50_v1(layout='NHWC', stem='s2d').
    Weight layout HWIO (jax native for NHWC convs)."""
    params = {}
    aux = {}

    def conv(name, kh, kw, cin, cout, bias=False):
        fan = kh * kw * cin
        params[name + ".weight"] = (
            rng.randn(kh, kw, cin, cout) * np.sqrt(2.0 / fan)
        ).astype(dtype)
        if bias:
            params[name + ".bias"] = np.zeros(cout, dtype)

    def bn(name, c):
        params[name + ".gamma"] = np.ones(c, dtype)
        params[name + ".beta"] = np.zeros(c, dtype)
        aux[name + ".mean"] = np.zeros(c, dtype)
        aux[name + ".var"] = np.ones(c, dtype)

    conv("stem", 4, 4, 12, 64)
    bn("stem_bn", 64)
    channels = [64, 256, 512, 1024, 2048]
    layers = [3, 4, 6, 3]
    for st, (n, cout) in enumerate(zip(layers, channels[1:])):
        cin = channels[st]
        for b in range(n):
            p = f"s{st}b{b}"
            c_in = cin if b == 0 else cout
            mid = cout // 4
            conv(p + ".c1", 1, 1, c_in, mid, bias=True)
            bn(p + ".bn1", mid)
            conv(p + ".c2", 3, 3, mid, mid)
            bn(p + ".bn2", mid)
            conv(p + ".c3", 1, 1, mid, cout, bias=True)
            bn(p + ".bn3", cout)
            if b == 0:
                conv(p + ".ds", 1, 1, c_in, cout)
                bn(p + ".dsbn", cout)
    params["fc.weight"] = (rng.randn(1000, 2048) *
                           np.sqrt(1.0 / 2048)).astype(dtype)
    params["fc.bias"] = np.zeros(1000, dtype)
    return params, aux


def replica_fwd(params, aux, x, momentum=0.9, eps=1e-3):
    """bf16 forward matching the framework's traced computation: f32
    single-pass BN stats, scale/shift fold in compute dtype."""
    import jax
    import jax.numpy as jnp

    new_aux = {}

    def conv(name, x, stride=1, pad="SAME"):
        w = params[name + ".weight"]
        out = jax.lax.conv_general_dilated(
            x, w, (stride, stride), pad,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if name + ".bias" in params:
            out = out + params[name + ".bias"]
        return out

    def bnorm(name, x):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=(0, 1, 2))
        var = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=(0, 1, 2)) - jnp.square(mean),
            0.0)
        mm, mv = aux[name + ".mean"], aux[name + ".var"]
        new_aux[name + ".mean"] = (mm.astype(jnp.float32) * momentum +
                                   mean * (1 - momentum)).astype(mm.dtype)
        new_aux[name + ".var"] = (mv.astype(jnp.float32) * momentum +
                                  var * (1 - momentum)).astype(mv.dtype)
        g = params[name + ".gamma"].astype(jnp.float32)
        b = params[name + ".beta"].astype(jnp.float32)
        inv = jax.lax.rsqrt(var + eps) * g
        shift = b - mean * inv
        return x * inv.astype(x.dtype) + shift.astype(x.dtype)

    # input preamble: s2d + NHWC transpose (graph edge, like the zoo)
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // 2, 2, w // 2, 2)
    x = x.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * 4, h // 2, w // 2)
    x = x.transpose(0, 2, 3, 1)  # NCHW -> NHWC

    x = conv("stem", x, 1, ((2, 1), (2, 1)))
    x = jax.nn.relu(bnorm("stem_bn", x))
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))

    layers = [3, 4, 6, 3]
    for st, n_blocks in enumerate(layers):
        stride = 1 if st == 0 else 2
        for b in range(n_blocks):
            p = f"s{st}b{b}"
            s = stride if b == 0 else 1
            res = x
            y = jax.nn.relu(bnorm(p + ".bn1", conv(p + ".c1", x, s)))
            y = jax.nn.relu(bnorm(p + ".bn2", conv(p + ".c2", y, 1)))
            y = bnorm(p + ".bn3", conv(p + ".c3", y, 1))
            if b == 0:
                res = bnorm(p + ".dsbn", conv(p + ".ds", x, s))
            x = jax.nn.relu(y + res)

    x = jnp.mean(x, axis=(1, 2))
    out = x @ params["fc.weight"].T + params["fc.bias"]
    return out, new_aux


def build_replica_step(lr=0.1, momentum=0.9):
    import jax
    import jax.numpy as jnp

    def compute_loss(params, aux, x, y):
        cp = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        out, new_aux = replica_fwd(cp, aux, x.astype(jnp.bfloat16))
        out = out.astype(jnp.float32)
        logp = jax.nn.log_softmax(out, axis=-1)
        nll = -jnp.take_along_axis(
            logp, y.astype(jnp.int32)[:, None], axis=-1)[:, 0]
        return nll.mean(), new_aux

    def step(params, aux, opt_state, x, y):
        (loss, new_aux), grads = jax.value_and_grad(
            compute_loss, has_aux=True)(params, aux, x, y)
        new_p, new_m = {}, {}
        for k, g in grads.items():
            mom = momentum * opt_state[k] - lr * g
            new_m[k] = mom
            new_p[k] = params[k] + mom
        return new_p, new_aux, new_m, loss

    return jax.jit(step, donate_argnums=(0, 1, 2))


# ------------------------------------------------------------- framework

def build_framework(bs):
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    net = vision.resnet50_v1(layout="NHWC", stem="s2d")
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 3, 224, 224)))
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
        dtype="bfloat16")
    trainer._build("fused")
    return trainer


# ------------------------------------------------------------ measurement

def hlo_stats(txt):
    out = {}
    for kind in ("fusion", "copy", "convolution", "transpose", "reduce",
                 "custom-call", "copy-start"):
        out[kind] = len(re.findall(rf"= \S+ {kind}\(", txt))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bs", type=int, default=256)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--dump-hlo", action="store_true")
    ap.add_argument("--skip-framework", action="store_true")
    ap.add_argument("--skip-replica", action="store_true")
    args = ap.parse_args()

    import jax

    rng = np.random.RandomState(0)
    bs = args.bs
    x = rng.rand(bs, 3, 224, 224).astype(np.float32)
    y = (rng.rand(bs) * 1000).astype(np.float32)

    results = {}

    if not args.skip_replica:
        params, aux = replica_init(rng)
        params = jax.device_put(params)
        aux = jax.device_put(aux)
        opt = jax.device_put({k: np.zeros_like(v)
                              for k, v in params.items()})
        step = build_replica_step()
        xd, yd = jax.device_put(x), jax.device_put(y)
        lowered = step.lower(params, aux, opt, xd, yd)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        txt = compiled.as_text()
        print(f"replica: bytes={ca.get('bytes accessed', 0) / 1e9:.1f}GB "
              f"{hlo_stats(txt)}", file=sys.stderr)
        if args.dump_hlo:
            open("/tmp/replica_hlo.txt", "w").write(txt)
        for _ in range(2):
            params, aux, opt, loss = step(params, aux, opt, xd, yd)
        loss.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            params, aux, opt, loss = step(params, aux, opt, xd, yd)
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        results["replica"] = bs * args.iters / dt
        print(f"replica: {results['replica']:.1f} img/s", file=sys.stderr)

    if not args.skip_framework:
        trainer = build_framework(bs)
        xd = jax.device_put(x, trainer._batch_sharding)
        yd = jax.device_put(y, trainer._batch_sharding)
        lowered = trainer._step.lower(trainer.params, trainer.aux,
                                      trainer.opt_state, xd, yd)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        txt = compiled.as_text()
        print(f"framework: bytes={ca.get('bytes accessed', 0) / 1e9:.1f}GB "
              f"{hlo_stats(txt)}", file=sys.stderr)
        if args.dump_hlo:
            open("/tmp/framework_hlo.txt", "w").write(txt)
        for _ in range(2):
            loss = trainer.step(xd, yd)
        loss.block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            loss = trainer.step(xd, yd)
        loss.block_until_ready()
        dt = time.perf_counter() - t0
        results["framework"] = bs * args.iters / dt
        print(f"framework: {results['framework']:.1f} img/s", file=sys.stderr)

    if len(results) == 2:
        print(f"gap: framework/replica = "
              f"{results['framework'] / results['replica']:.3f}",
              file=sys.stderr)


if __name__ == "__main__":
    main()
