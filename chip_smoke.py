#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main training path once, through the entry points a user
calls, at the full width of the flagship model:

    gluon.model_zoo.vision.resnet50_v1 (1000 classes, 224², NHWC + s2d)
      -> parallel.ShardedTrainer(dtype="bfloat16")
      -> capture.capture(trainer)
      -> steps on a device-resident batch

Weights are random, made from a seed; nothing is read from the network.
Phases (any failure -> non-zero exit, no result line):

  Device   jax.devices() must be TPU devices; versions, where the one
           compile cache lives, whether the native record loader built.
  Train    batch 256 on a {"dp": 1} mesh: 1 compile step + 5 steps; loss
           finite and moving, every array on the TPU, one captured
           executable, no eager fallback, no elastic OOM retry, a perf
           ledger entry with flops and bytes. Then ten eager
           gluon.Trainer.step calls of the MNIST MLP (eager donation).
  Kernels  the Pallas flash kernel (forward, backward, one ring hop with
           a traced offset), the gated delta rule's kernels (output and
           five gradients in bf16 against float32 autodiff of the
           ``jax.numpy`` form, beside what that form reads in bf16),
           the short convolution + SiLU kernels the same way, with each
           kernel's time alone beside the ``jax.numpy`` form's, an
           expert layer under ``Remat`` (output and gradients against
           the scan's checkpoint placed inside its skip, both routings),
           paged decode attention (bf16 and int8 KV)
           and the int8 conv / FC ops, compiled, against the XLA dense
           composition in f32-highest.
  Four chips (when >= 4 are visible) the Train phase again on {"dp": 4}
           at global batch 1024 in this same process, kvstore='tpu'
           push/pull over four devices, and one {"sp": 4} flash ring.

One process uses the chip: this script starts no process that needs it.
Last stdout line on success, with the device as jax reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Without an accelerator it exits 1 with one line and never continues on
the CPU. ``--cpu-rehearsal`` (debugging only, never the default) runs
the same code at toy sizes with kernels in interpret mode, says so on
every phase line, and prints no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

# bf16 tolerance of the repo's own attention tests
# (tests/test_ring_attention.py::test_bf16_inputs): 3e-2 absolute on
# O(1) outputs; gradients are held to the same bound relative to the
# reference's largest magnitude.
BF16_ATOL = 3e-2

FULL = {"image": 224, "batch": 256, "steps": 5,
        "flash": [(2, 12, 1024, 64), (1, 4, 8192, 128)],
        "hop": (1, 4, 1024, 64),
        # ((B, H, T, D), window): a window layer of Trinity-Mini's cell
        "window": ((1, 32, 8192, 128), 2048),
        # (B, T, key heads, value heads, Dk, Dv): Qwen3-Next's cell
        "delta_rule": (1, 8192, 16, 32, 128, 128),
        # ((B, T, W), the parts handed on, taps): the projection's output
        # of one of that cell's linear layers, [q | k | v | z]
        "conv_silu": ((1, 8192, 12288), (2048, 2048, 4096), 4),
        # (tokens, d, experts routed over, held, top k, inner): an expert
        # layer of that cell
        "moe": (8192, 2048, 512, 16, 10, 512),
        # the same, and its activation, through the grouped-product
        # kernels against ragged_dot: Nemotron-H's cell (ungated, inner
        # 1856 off the lane grid) and Trinity-Mini's
        "moe_kernels": [((8192, 2688, 128, 8, 6, 1856), "relu2"),
                        ((8192, 2048, 128, 8, 8, 1024), "swiglu")],
        "decode": {"b": 8, "h": 12, "d": 64, "page": 16, "pages": 256},
        # ResNet-18 stage-2 3x3 conv and the classifier, batch 128
        "conv": {"data": (128, 128, 28, 28), "weight": (128, 128, 3, 3)},
        "fc": {"data": (128, 512), "weight": (1000, 512)},
        "ring_t": 4096}
REHEARSAL = {"image": 64, "batch": 8, "steps": 2,
             "flash": [(1, 2, 256, 64)],
             "hop": (1, 2, 128, 64),
             "window": ((1, 2, 256, 64), 100),
             "delta_rule": (1, 150, 1, 2, 128, 128),
             "conv_silu": ((1, 70, 640), (128, 256, 128), 4),
             "moe": (256, 64, 256, 16, 10, 32),
             "moe_kernels": [((256, 96, 32, 8, 6, 48), "relu2"),
                             ((256, 64, 32, 8, 8, 32), "swiglu")],
             "decode": {"b": 2, "h": 2, "d": 32, "page": 8, "pages": 8},
             "conv": {"data": (2, 8, 8, 8), "weight": (8, 8, 3, 3)},
             "fc": {"data": (4, 32), "weight": (16, 32)},
             "ring_t": 512}


class SmokeFailure(AssertionError):
    pass


def check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def log(msg):
    print(msg, flush=True)


class CompileCounter:
    """jax's own persistent-cache counters: compile requests that could
    use the cache, and how many of them it answered."""

    def __init__(self):
        import jax

        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def line(self):
        return (f"compile cache: {self.requests} requests, {self.hits} "
                f"hits, {self.requests - self.hits} compiled")


# ------------------------------------------------------------------ Device

def device_phase(rehearsal):
    import jax
    import jaxlib

    import mxnet_tpu as mx
    from mxnet_tpu.io.record_pipeline import native_available
    from mxnet_tpu.observability import perf
    from mxnet_tpu.tune import schedule

    dev = perf.device_record()
    if dev["platform"] != "tpu" and not rehearsal:
        sys.exit(f"chip_smoke: no chip — jax.devices() reports "
                 f"{dev['count']} {dev['platform']} device(s) (JAX_PLATFORMS="
                 f"{os.environ.get('JAX_PLATFORMS')!r}); not continuing "
                 "on the CPU")
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not installed"
    log(f"device: platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']} | jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu_version} | JAX_PLATFORMS="
        f"{os.environ.get('JAX_PLATFORMS')!r}")
    log(f"compile cache dir: {jax.config.jax_compilation_cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')!r})")
    log(f"default context: {mx.current_context()} | native record loader: "
        f"{native_available()} | schedule backend: "
        f"{schedule.resolve_backend()}")
    check(rehearsal or mx.current_context().device_type == "tpu",
          "default context is not the chip")
    return dev


# ------------------------------------------------------------------- Train

def build_net(image, tag):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision

    t0 = time.perf_counter()
    mx.random.seed(0)
    net = vision.resnet50_v1(layout="NHWC", stem="s2d")
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 3, image, image))).wait_to_read()  # materialize
    log(f"{tag} resnet50_v1 initialize + eager materializing forward: "
        f"{time.perf_counter() - t0:.1f} s")
    return net


def make_batch(batch, image):
    import numpy as np

    rng = np.random.RandomState(0)
    x = rng.rand(batch, 3, image, image).astype(np.float32)
    y = (rng.rand(batch) * 1000).astype(np.float32)
    return x, y


def on_platform(tree, platform):
    import jax

    return all(d.platform == platform
               for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


def train_phase(net, n_chips, batch, sz, platform, tag):
    """ShardedTrainer + capture on a {"dp": n_chips} mesh; returns the
    trainer, the device-resident batch and the first-step loss."""
    import math

    import jax

    from mxnet_tpu import capture, gluon, parallel
    from mxnet_tpu.observability import perf
    from mxnet_tpu.resilience import elastic

    mesh = parallel.create_mesh({"dp": n_chips}, jax.devices()[:n_chips])
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
        dtype="bfloat16")
    step = capture.capture(trainer)
    x, y = make_batch(batch, sz["image"])
    xd = jax.device_put(x, trainer.batch_sharding)
    yd = jax.device_put(y, trainer.batch_sharding)

    capture.reset_stats()
    oom0 = elastic.stats()["elastic_oom_events"]
    ledger0 = set(perf.ledger())
    t0 = time.perf_counter()
    loss = step(xd, yd)
    loss.block_until_ready()
    compile_s = time.perf_counter() - t0
    losses, step_s = [float(loss)], []
    for _ in range(sz["steps"]):
        t0 = time.perf_counter()
        loss = step(xd, yd)
        loss.block_until_ready()
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
    log(f"{tag} dp={n_chips} batch={batch}: compile+first step "
        f"{compile_s:.1f} s; steps "
        f"{' '.join(f'{s * 1e3:.1f}' for s in step_s)} ms "
        f"(wall around block_until_ready, {batch / min(step_s):.0f} img/s "
        f"best); loss {' '.join(f'{v:.4f}' for v in losses)}")

    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(len(set(losses)) == len(losses), f"loss does not move: {losses}")
    state = (trainer.params, trainer.aux, trainer.opt_state, xd, yd, loss)
    check(on_platform(state, platform),
          f"an array of the train phase is not on the {platform}")
    s = capture.stats()
    check(s["capture_fallback_eager"] == 0 and s["capture_retraces"] == 0
          and s["capture_misses"] == 1,
          f"capture did not stay on one executable: {s}")
    check(elastic.stats()["elastic_oom_events"] == oom0,
          "the step did not fit and was re-run as microbatches")
    entries = [e for key, e in perf.ledger().items()
               if key not in ledger0 and e["label"] == "sharded_step"]
    check(len(entries) == 1 and entries[0]["flops"]
          and entries[0]["bytes_accessed"],
          f"perf ledger has no flops/bytes for this sharded_step: {entries}")
    e = entries[0]
    log(f"{tag} ledger sharded_step: {e['flops'] / 1e12:.2f} TFLOP, "
        f"{e['bytes_accessed'] / 1e9:.1f} GB accessed, peak HBM "
        f"{e['peak_hbm_bytes'] / 1e9:.2f} GB, compile "
        f"{e['compile_ms'] / 1e3:.1f} s (XLA cost analysis); capture "
        f"{ {k: v for k, v in s.items() if v} }")
    return trainer, (xd, yd), losses[0]


def eager_phase(kvstore, tag):
    """Ten eager gluon.Trainer.step calls of the MNIST MLP
    (examples/train_mnist.py's 128-64-10) on the default context: eager
    donation is on for non-CPU devices, so a buffer deleted under a live
    reader shows here."""
    import math

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon

    ctx = mx.current_context()
    mx.random.seed(1)
    net = gluon.nn.HybridSequential(prefix="smoke_mlp_")
    with net.name_scope():
        net.add(gluon.nn.Dense(128, activation="relu"),
                gluon.nn.Dense(64, activation="relu"), gluon.nn.Dense(10))
    net.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9},
                            kvstore=kvstore)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    centers = rng.rand(10, 784).astype(np.float32)
    losses = []
    for _ in range(10):
        label = rng.randint(0, 10, 128)
        data = centers[label] + rng.randn(128, 784).astype(np.float32) * 0.15
        with autograd.record():
            loss = loss_fn(net(mx.nd.array(data)),
                           mx.nd.array(label.astype(np.float32)))
        loss.backward()
        trainer.step(128)
        losses.append(float(loss.mean().asnumpy()))
    log(f"{tag} eager MLP x10 on {ctx} kvstore={kvstore!r}: loss "
        f"{losses[0]:.4f} -> {losses[-1]:.4f}")
    check(all(math.isfinite(v) for v in losses) and losses[-1] < losses[0],
          f"eager MLP did not train: {losses}")
    for p in net.collect_params().values():
        check(p.data().context == ctx and p.grad().context == ctx,
              f"{p.name} left {ctx}")


# ----------------------------------------------------------------- Kernels

def dense_attention(q, k, v, causal, q_off=0, k_off=0, window=None):
    """The XLA dense composition in f32-highest — the reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.default_matmul_precision("highest"):
        q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
        if causal:
            ahead = (q_off + jnp.arange(q.shape[2]))[:, None] \
                - (k_off + jnp.arange(k.shape[2]))[None, :]
            seen = ahead >= 0
            if window is not None:
                seen &= ahead < window
            s = jnp.where(seen, s, -1e30)
        return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


def max_err(a, b):
    import jax.numpy as jnp

    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def window_check(shape, window, interpret, tag):
    """The flash kernels under a window in bf16, output and dq / dk /
    dv, against float32 autodiff of the dense masked softmax at highest
    precision, four heads at a time so that the dense scores fit
    (heads are independent under a loss that is a sum over them):
    largest difference over the largest entry."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.pallas_kernels import flash_attention_with_grad

    rs = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rs.randn(*shape) * 0.5, jnp.bfloat16)
               for _ in range(3))
    weight = jnp.asarray(rs.randn(*shape), jnp.float32)

    def both(fn):
        def run(q, k, v, w):
            def loss(q, k, v):
                out = fn(q, k, v)
                return jnp.sum(out.astype(jnp.float32) * w), out

            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
            return (out,) + grads

        return jax.jit(run)

    t0 = time.perf_counter()
    got = jax.block_until_ready(both(
        lambda q, k, v: flash_attention_with_grad(
            q, k, v, causal=True, window=window, interpret=interpret))(
                q, k, v, weight))
    dt = time.perf_counter() - t0
    dense = both(lambda q, k, v: dense_attention(q, k, v, True,
                                                 window=window))
    errs = [0.0] * 4
    for h in range(0, shape[1], 4):
        want = dense(*(x[:, h:h + 4] for x in (q, k, v, weight)))
        for i, (x, w) in enumerate(zip(got, want)):
            errs[i] = max(errs[i], max_err(x[:, h:h + 4], w)
                          / max(1.0, float(jnp.max(jnp.abs(w)))))
    log(f"{tag} flash fwd+bwd {shape} bf16 window {window}, against "
        "float32 dense masked autodiff, largest err/scale: " + " ".join(
            f"{n} {e:.4f}" for n, e in zip(("o", "dq", "dk", "dv"), errs))
        + f" (tol {BF16_ATOL}); compile+run {dt:.1f} s")
    check(max(errs) <= BF16_ATOL,
          f"flash under window {window} at {shape} outside bf16 "
          f"tolerance: {errs}")


def delta_rule_check(shape, interpret, tag):
    """The gated delta rule's kernels in bf16, output and all five
    gradients, against float32 autodiff of the ``jax.numpy`` chunked
    form at highest precision: largest difference over the largest
    entry, beside what the ``jax.numpy`` form itself reads in bf16 (the
    scan the kernels replace on the chip)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.delta_rule_kernels import gated_delta_rule_kernels
    from mxnet_tpu.ops.linear_attention import _chunked_delta_rule

    b, t, hk, hv, dk, dv = shape
    rs = np.random.RandomState(3)

    def draw(*dims):
        return jnp.asarray(rs.randn(*dims), jnp.float32)

    # as the mixer hands them over: SiLU outputs, decays by head
    q, k, v = (jax.nn.silu(draw(b, t, h, d))
               for h, d in ((hk, dk), (hk, dk), (hv, dv)))
    g = -jnp.asarray(rs.uniform(1e-6, 16, hv), jnp.float32) \
        * jax.nn.softplus(draw(b, t, hv) + 1.0)
    exact = (q, k, v, g, jax.nn.sigmoid(draw(b, t, hv)))
    half = tuple(x.astype(jnp.bfloat16) for x in exact[:3]) + exact[3:]
    weight = draw(b, t, hv, dv)

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * weight), out

        def run(*a):
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*a)
            return (out,) + grads

        return jax.jit(run)

    def scan(*a):
        return _chunked_delta_rule(*a, 64)

    def kernels(*a):
        return gated_delta_rule_kernels(*a, interpret=interpret)

    with jax.default_matmul_precision("highest"):
        want = both(scan)(*exact)
    t0 = time.perf_counter()
    got = jax.block_until_ready(both(kernels)(*half))
    dt = time.perf_counter() - t0
    names = ("o", "dq", "dk", "dv", "dg", "dbeta")
    errs = {}

    def rms(x):
        return float(jnp.sqrt(jnp.mean(jnp.square(x.astype(jnp.float32)))))

    for label, result in (("kernels", got), ("scan", both(scan)(*half))):
        errs[label] = [max_err(x, w) / float(jnp.max(jnp.abs(w)))
                       for x, w in zip(result, want)]
        spread = [rms(x.astype(jnp.float32) - w) / rms(w)
                  for x, w in zip(result, want)]
        log(f"{tag} gated delta rule {shape} bf16, {label} against float32 "
            "autodiff, largest err/largest entry (rms err/rms): " + " ".join(
                f"{n} {e:.4f} ({r:.4f})"
                for n, e, r in zip(names, errs[label], spread)))
    log(f"{tag} gated delta rule kernels compile+run {dt:.1f} s")
    check(max(errs["kernels"]) <= BF16_ATOL,
          f"gated delta rule kernels {shape} outside bf16 tolerance: "
          f"{errs['kernels']}")


def conv_silu_check(shape, parts, taps, interpret, tag):
    """The short convolution + SiLU kernels in bf16, the parts and the
    gradients of input and weight, against float32 autodiff of the
    ``jax.numpy`` form ``silu(causal_conv1d(x, w))``: largest difference
    over the largest entry, beside what the ``jax.numpy`` form itself
    reads in bf16 (what the kernels replace on the chip); then each
    kernel's time alone (a layer: a call a part), and that form's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops.conv_silu_kernels import causal_conv_silu_kernels
    from mxnet_tpu.ops.linear_attention import causal_conv1d

    b, t, width = shape
    channels = sum(parts)
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(b, t, width), jnp.float32)
    w = jnp.asarray(rs.uniform(-1, 1, (channels, taps)) * taps ** -0.5,
                    jnp.float32)
    douts = tuple(jnp.asarray(rs.randn(b, t, n), jnp.float32)
                  for n in parts + (width - channels,))

    def form(x, w):
        mixed = jax.nn.silu(causal_conv1d(x[..., :channels], w))
        ends = np.cumsum(parts)
        return tuple(mixed[..., e - n:e] for e, n in zip(ends, parts)) \
            + (x[..., channels:],)

    def kernels(x, w):
        return causal_conv_silu_kernels(x, w, parts, interpret=interpret)

    def forward(fn):
        return jax.jit(lambda x, w: fn(x, w)[:-1])

    def backward(fn):
        # the forward's results are dead here: only the backward runs
        return jax.jit(lambda x, w, d: jax.vjp(fn, x, w)[1](d))

    def both(fn, x, w, d):
        return forward(fn)(x, w) + backward(fn)(x, w, d)

    def half(*arrays):
        return tuple(a.astype(jnp.bfloat16) for a in arrays)

    want = both(form, x, w, douts)
    xh, wh = half(x, w)
    dh = half(*douts)
    t0 = time.perf_counter()
    got = jax.block_until_ready(both(kernels, xh, wh, dh))
    dt = time.perf_counter() - t0
    names = [f"part{n}" for n in range(len(parts))] + ["dx", "dw"]
    errs = {}
    for label, result in (("kernels", got), ("jax.numpy",
                                             both(form, xh, wh, dh))):
        errs[label] = [max_err(a, e) / float(jnp.max(jnp.abs(e)))
                       for a, e in zip(result, want)]
        log(f"{tag} causal conv + SiLU {shape} parts {parts} {taps} taps "
            f"bf16, {label} against float32 autodiff, largest err/largest "
            "entry: " + " ".join(f"{n} {e:.4f}"
                                 for n, e in zip(names, errs[label])))
    log(f"{tag} causal conv + SiLU kernels compile+run {dt:.1f} s")
    check(max(errs["kernels"]) <= BF16_ATOL,
          f"causal conv + SiLU kernels {shape} outside bf16 tolerance: "
          f"{errs['kernels']}")

    if interpret:       # a time is the chip's or it is not written
        return
    times = {label: (wall_ms(forward(fn), xh, wh),
                     wall_ms(backward(fn), xh, wh, dh))
             for label, fn in (("kernels", kernels), ("jax.numpy", form))}
    log(f"{tag} causal conv + SiLU, a layer alone, ms forward / backward "
        "(wall time over back-to-back calls): " + "; ".join(
            f"{label} {f:.3f} / {bw:.3f}"
            for label, (f, bw) in times.items()))


def wall_ms(fn, *args, calls=30):
    """Milliseconds a call of ``fn`` over back-to-back calls, after one
    call that compiles it."""
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def _moe_experts_skip_outside(data, weights, experts, gate_up, down):
    """``ops.moe.moe_experts``'s result (``first_expert`` 0) with the
    scan's per-block checkpoint inside the per-block skip instead of
    around it: the arrangement whose backward pass stacks the scan's
    loop-invariant inputs once per block, which the single block then
    fills with zeros. The same arithmetic in the same order."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.moe import _block_of_rows

    held, d = gate_up.shape[0], data.shape[-1]
    top_k = experts.shape[-1]
    x = data.reshape(-1, d)
    tokens = x.shape[0]
    local = experts.reshape(tokens, top_k)
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                    dtype=jnp.int32)
    flat_w = weights.reshape(-1).astype(jnp.float32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(sizes)])
    n_held = offsets[-1]

    def block_of_rows(block):
        return _block_of_rows(x, flat_w, order, offsets, gate_up, down,
                              top_k, block)

    def every_block():
        def one(total, block):
            part = jax.lax.cond(
                block * tokens < n_held, jax.checkpoint(block_of_rows),
                lambda _: jnp.zeros(x.shape, jnp.float32), block)
            return total + part, None

        return jax.lax.scan(one, jnp.zeros(x.shape, jnp.float32),
                            jnp.arange(min(top_k, held)))[0]

    out = jax.lax.cond(n_held <= tokens, lambda: block_of_rows(0),
                       every_block)
    return out.astype(x.dtype).reshape(data.shape)


def moe_remat_check(shape, interpret, tag):
    """One expert layer's router and grouped products in bf16 under
    ``Remat``'s default policy, as a half-layer of Qwen3-Next runs them:
    the output and the gradients of the tokens, the router weight and
    both expert weights, against the same layer with the scan's
    checkpoint inside its skip (``_moe_experts_skip_outside``), on the
    same seed, for a free routing (the single block runs) and one that
    holds every choice (the scan runs): largest difference over the
    largest entry; then each form's step alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import remat
    from mxnet_tpu.ops.moe import moe_experts, moe_router

    tokens, d, n_experts, held, top_k, inner = shape
    rs = np.random.RandomState(6)

    def draw(*dims, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rs.randn(*dims) * scale, dtype)

    x = draw(tokens, d)
    router_w = draw(n_experts, d, scale=d ** -0.5, dtype=jnp.float32)
    gate_up = draw(held, d, 2 * inner, scale=d ** -0.5)
    down = draw(held, inner, d, scale=inner ** -0.5)
    weight = draw(tokens, d, dtype=jnp.float32)
    counts = jnp.zeros(held + 1, jnp.float32)
    names = ("out", "dx", "drouter", "dgate_up", "ddown")

    def step(experts_of, bias):
        def layer(x, router_w, gate_up, down):
            weights, experts = moe_router(x, router_w, bias, top_k=top_k)
            return experts_of(x, weights, experts, gate_up, down)

        def loss(*a):
            out = layer(*a)
            return jnp.sum(out.astype(jnp.float32) * weight), out

        grad = jax.value_and_grad(
            jax.checkpoint(loss, policy=remat.resolve_policy(None)),
            argnums=(0, 1, 2, 3), has_aux=True)

        def run(*a):
            (_, out), grads = grad(*a)
            return (out,) + grads

        return jax.jit(run)

    forms = {"change": lambda *a: moe_experts(*a, counts)[0],
             "skip outside": _moe_experts_skip_outside}
    routings = {"free routing": None,
                "every choice held": jnp.zeros(n_experts, jnp.float32)
                .at[:held].set(30.0)}
    for routing, bias in routings.items():
        steps = {label: step(fn, bias) for label, fn in forms.items()}
        got, want = (steps[label](x, router_w, gate_up, down)
                     for label in forms)
        n_held = int(jnp.sum(moe_router(x, router_w, bias,
                                        top_k=top_k)[1] < held))
        errs = [max_err(a, b) / max(float(jnp.max(jnp.abs(
            b.astype(jnp.float32)))), 1e-30) for a, b in zip(got, want)]
        log(f"{tag} expert layer {shape} bf16 under Remat, {routing} "
            f"({n_held} held assignments, {tokens} tokens): the change "
            "against the checkpoint inside the skip, largest err/largest "
            "entry: " + " ".join(f"{n} {e:.2e}" for n, e in zip(names, errs)))
        check(max(errs) <= BF16_ATOL,
              f"expert layer under Remat, {routing}, outside bf16 "
              f"tolerance: {errs}")
        if not interpret:   # a time is the chip's or it is not written
            times = {label: wall_ms(fn, x, router_w, gate_up, down)
                     for label, fn in steps.items()}
            log(f"{tag} expert layer, {routing}, ms a forward + "
                "backward alone (wall time over back-to-back calls): "
                + "; ".join(f"{k} {v:.3f}" for k, v in times.items()))


def moe_kernels_check(shape, activation, interpret, tag):
    """One expert layer's router and grouped products in bf16 under
    ``Remat``'s default policy, the grouped products as the Pallas
    kernels (``ops/grouped_matmul_kernels.py``) against XLA's
    ``jax.lax.ragged_dot`` (what the kernels replace on the chip), on
    the same seed and a free routing: the output and the gradients of
    the tokens, the router weight and both expert weights, largest
    difference over the largest entry; then each form's step alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import remat
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops.grouped_matmul_kernels import grouped_matmul_kernels

    tokens, d, n_experts, held, top_k, inner = shape
    rs = np.random.RandomState(7)

    def draw(*dims, scale=1.0, dtype=jnp.bfloat16):
        return jnp.asarray(rs.randn(*dims) * scale, dtype)

    x = draw(tokens, d)
    router_w = draw(n_experts, d, scale=d ** -0.5, dtype=jnp.float32)
    up = inner if activation == "relu2" else 2 * inner
    gate_up = draw(held, d, up, scale=d ** -0.5)
    down = draw(held, inner, d, scale=inner ** -0.5)
    weight = draw(tokens, d, dtype=jnp.float32)
    counts = jnp.zeros(held + 1, jnp.float32)
    names = ("out", "dx", "drouter", "dup", "ddown")

    def step():
        """The layer's step, every function new: jax keeps the trace of
        a checkpointed function by the function, so a form must not
        reuse the other's."""
        def loss(x, router_w, gate_up, down):
            weights, experts = moe.moe_router(x, router_w, top_k=top_k)
            out = moe.moe_experts(x, weights, experts, gate_up, down,
                                  counts, activation=activation)[0]
            return jnp.sum(out.astype(jnp.float32) * weight), out

        grad = jax.value_and_grad(
            jax.checkpoint(loss, policy=remat.resolve_policy(None)),
            argnums=(0, 1, 2, 3), has_aux=True)

        def run(*a):
            (_, out), grads = grad(*a)
            return (out,) + grads

        return jax.jit(run)

    forms = {"kernels": lambda lhs, rhs, sizes: grouped_matmul_kernels(
                 lhs, rhs, sizes, interpret=interpret),
             "ragged_dot": jax.lax.ragged_dot}
    steps, results = {}, {}
    real = moe._grouped
    for label, grouped in forms.items():
        moe._grouped = grouped      # traced now: the step keeps this form
        try:
            steps[label] = step()
            results[label] = jax.block_until_ready(
                steps[label](x, router_w, gate_up, down))
        finally:
            moe._grouped = real
    got, want = results["kernels"], results["ragged_dot"]
    n_held = int(jnp.sum(moe.moe_router(x, router_w, top_k=top_k)[1]
                         < held))
    errs = [max_err(a, b) / max(float(jnp.max(jnp.abs(
        b.astype(jnp.float32)))), 1e-30) for a, b in zip(got, want)]
    log(f"{tag} expert layer {shape} {activation} bf16 under Remat "
        f"({n_held} held assignments, {tokens} tokens): the grouped-product "
        "kernels against ragged_dot, largest err/largest entry: "
        + " ".join(f"{n} {e:.2e}" for n, e in zip(names, errs)))
    check(all(bool(jnp.all(jnp.isfinite(a))) for a in got)
          and max(errs) <= BF16_ATOL,
          f"expert layer {shape} through the kernels outside bf16 "
          f"tolerance: {errs}")
    if not interpret:   # a time is the chip's or it is not written
        times = {label: wall_ms(fn, x, router_w, gate_up, down)
                 for label, fn in steps.items()}
        log(f"{tag} expert layer {shape}, ms a forward + backward alone "
            "(wall time over back-to-back calls): "
            + "; ".join(f"{k} {v:.3f}" for k, v in times.items()))


def kernels_phase(sz, interpret, tag):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.ops.decode_attention import (kv_dequantize, kv_quantize,
                                                paged_decode_attention)
    from mxnet_tpu import tune
    from mxnet_tpu.ops.pallas_kernels import (flash_attention_with_grad,
                                              flash_attention_with_lse)

    def qkv(shape, seed):
        rs = np.random.RandomState(seed)
        return [jnp.asarray(rs.randn(*shape) * 0.5, jnp.bfloat16)
                for _ in range(3)]

    # flash forward + backward, causal, bf16
    for shape in sz["flash"]:
        q, k, v = qkv(shape, 0)

        def flash(q, k, v):
            return flash_attention_with_grad(q, k, v, causal=True,
                                             interpret=interpret)

        def loss(fn):
            return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                           ** 2)

        def dense(q, k, v):
            return dense_attention(q, k, v, True)

        t0 = time.perf_counter()
        out = jax.jit(flash)(q, k, v)
        grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
        jax.block_until_ready((out, grads))
        dt = time.perf_counter() - t0
        ref = jax.jit(dense)(q, k, v)
        gref = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
        ferr = max_err(out, ref)
        gerr = [max_err(g, r) / max(1.0, float(jnp.max(jnp.abs(r))))
                for g, r in zip(grads, gref)]
        log(f"{tag} flash fwd+bwd {shape} bf16 causal: fwd err {ferr:.4f}, "
            f"grad err/scale {' '.join(f'{e:.4f}' for e in gerr)} "
            f"(tol {BF16_ATOL}); compile+run {dt:.1f} s")
        check(ferr <= BF16_ATOL and max(gerr) <= BF16_ATOL,
              f"flash {shape} outside bf16 tolerance: {ferr} {gerr}")

    # one ring hop: traced, non-zero k_offset (the scalar-prefetch path)
    q, k, v = qkv(sz["hop"], 1)
    t = sz["hop"][2]
    hop = jax.jit(lambda q, k, v, qo, ko: flash_attention_with_lse(
        q, k, v, causal=True, q_offset=qo, k_offset=ko,
        interpret=interpret))
    for qo, ko in ((2 * t, t), (t, t)):
        out, lse = hop(q, k, v, jnp.int32(qo), jnp.int32(ko))
        err = max_err(out, dense_attention(q, k, v, True, qo, ko))
        log(f"{tag} ring hop {sz['hop']} q_offset={qo} k_offset={ko}: "
            f"err {err:.4f}")
        check(err <= BF16_ATOL and bool(jnp.all(jnp.isfinite(lse))),
              f"ring hop q_offset={qo} k_offset={ko}: err {err}")

    window_check(*sz["window"], interpret, tag)
    delta_rule_check(sz["delta_rule"], interpret, tag)
    conv_silu_check(*sz["conv_silu"], interpret, tag)
    moe_remat_check(sz["moe"], interpret, tag)
    for shape, activation in sz["moe_kernels"]:
        moe_kernels_check(shape, activation, interpret, tag)

    b_, h_, t_, d_ = sz["flash"][0]
    blocks = tune.schedule.flash_fwd_blocks(b_ * h_, t_, d_, "bfloat16",
                                            interpret=interpret)
    st = tune.stats()
    log(f"{tag} schedule table on backend "
        f"{tune.schedule.resolve_backend(interpret)!r}: "
        f"{st['autotune_table_hits']} hits, {st['autotune_table_misses']} "
        f"misses; flash blocks at {sz['flash'][0]}: {blocks} (a miss runs "
        "the legalized default)")

    # paged decode attention over a >= 4k-token cache, bf16 and int8 KV
    g = sz["decode"]
    b, h, d, page, pages = g["b"], g["h"], g["d"], g["page"], g["pages"]
    rs = np.random.RandomState(2)
    qd = jnp.asarray(rs.randn(b, h, d) * 0.3, jnp.bfloat16)
    kp, vp = [jnp.asarray(rs.randn(b * pages + 1, page, h, d) * 0.3,
                          jnp.bfloat16) for _ in range(2)]
    table = jnp.asarray(rs.permutation(b * pages).reshape(b, pages) + 1,
                        jnp.int32)
    lengths = jnp.asarray(rs.randint(page, pages * page + 1, b), jnp.int32)

    def decode_ref(kp, vp):
        # gather every sequence's pages and run the dense composition
        kk = kp[table].reshape(b, pages * page, h, d).transpose(0, 2, 1, 3)
        vv = vp[table].reshape(b, pages * page, h, d).transpose(0, 2, 1, 3)
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhd,bhkd->bhk", qd.astype(jnp.float32),
                           kk.astype(jnp.float32)) / np.sqrt(d)
            s = jnp.where(jnp.arange(pages * page)[None, None, :]
                          < lengths[:, None, None], s, -1e30)
            return jnp.einsum("bhk,bhkd->bhd", jax.nn.softmax(s, -1),
                              vv.astype(jnp.float32))

    out = jax.jit(lambda *a: paged_decode_attention(
        *a, interpret=interpret))(qd, kp, vp, table, lengths)
    err = max_err(out, decode_ref(kp, vp))
    k8, ks = kv_quantize(kp.astype(jnp.float32))
    v8, vs = kv_quantize(vp.astype(jnp.float32))
    out8 = jax.jit(lambda q, k, v, tb, ln, ks, vs: paged_decode_attention(
        q, k, v, tb, ln, k_scales=ks, v_scales=vs,
        interpret=interpret))(qd, k8, v8, table, lengths, ks, vs)
    err8 = max_err(out8, decode_ref(kv_dequantize(k8, ks),
                                    kv_dequantize(v8, vs)))
    log(f"{tag} paged decode b={b} h={h} d={d} cache={pages * page} tokens: "
        f"bf16 err {err:.4f}, int8-KV err {err8:.4f} (vs dense on the "
        "dequantized cache)")
    check(err <= BF16_ATOL and err8 <= BF16_ATOL,
          f"paged decode outside tolerance: bf16 {err}, int8 {err8}")

    # int8 -> int32 conv and FC at one ResNet-18 layer shape, exact
    rs = np.random.RandomState(3)

    def s8(shape):
        return rs.randint(-127, 128, shape).astype(np.int8)

    one = mx.nd.array(np.float32([1.0]))
    rng = (-one, one, -one, one)
    data, weight = s8(sz["conv"]["data"]), s8(sz["conv"]["weight"])
    # (no_bias=True ignores the bias slot; the weight fills it)
    wq = mx.nd.array(weight, dtype="int8")
    out = mx.nd.contrib.quantized_conv(
        mx.nd.array(data, dtype="int8"), wq, wq, *rng, kernel=(3, 3),
        pad=(1, 1), num_filter=weight.shape[0], no_bias=True)[0]
    ref = jax.lax.conv_general_dilated(
        jnp.asarray(data, jnp.int32), jnp.asarray(weight, jnp.int32),
        (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    check(out.dtype == np.int32 and bool(jnp.array_equal(out.data_, ref)),
          "quantized_conv differs from the int32 convolution")
    data, weight = s8(sz["fc"]["data"]), s8(sz["fc"]["weight"])
    wq = mx.nd.array(weight, dtype="int8")
    out = mx.nd.contrib.quantized_fully_connected(
        mx.nd.array(data, dtype="int8"), wq, wq, *rng,
        num_hidden=weight.shape[0], no_bias=True)[0]
    ref = jnp.asarray(data, jnp.int32) @ jnp.asarray(weight, jnp.int32).T
    check(out.dtype == np.int32 and bool(jnp.array_equal(out.data_, ref)),
          "quantized_fully_connected differs from the int32 matmul")
    log(f"{tag} int8 conv {sz['conv']['data']}x{sz['conv']['weight']} and "
        f"FC {sz['fc']['data']}x{sz['fc']['weight']}: int32 results exact "
        f"on {out.context}")


# -------------------------------------------------------------- Four chips

def four_chip_phase(net, one_chip, sz, platform, interpret, tag):
    """dp=4 at global batch 4x, checked against the one-chip trainer's
    own loss function on the same global batch and the same weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    batch = 4 * sz["batch"]
    trainer, (xd, yd), first = train_phase(net, 4, batch, sz, platform, tag)
    check(all(len(p.sharding.device_set) == 4
              for p in trainer.params.values()),
          "a parameter is not placed on all four chips")
    rows = [s.data.shape[0] for s in xd.addressable_shards]
    check(rows == [sz["batch"]] * 4,
          f"batch shards are {rows}, not 4 x {sz['batch']}")
    in_use = [d.memory_stats()["bytes_in_use"] if d.memory_stats() else None
              for d in jax.devices()[:4]]
    log(f"{tag} bytes_in_use per chip: {in_use}")
    check(platform != "tpu" or all(b and b > 100e6 for b in in_use),
          f"a chip holds next to nothing: {in_use}")
    check(trainer._capture_fp != one_chip._capture_fp,
          "the dp=1 and dp=4 step programs share a capture fingerprint")

    # the one-chip reference: the SAME compute_loss the step differentiates
    # (ShardedTrainer._make_compute_loss), forward only, on the same global
    # batch, from the same initial weights the net still holds
    dev0 = jax.devices()[0]
    x, y = make_batch(batch, sz["image"])
    p0 = {k: jax.device_put(v, dev0)
          for k, v in parallel.param_arrays(net).items()}
    a0 = {k: jax.device_put(v, dev0)
          for k, v in parallel.aux_arrays(net).items()}
    ref = float(jax.jit(one_chip._make_compute_loss())(
        p0, a0, jax.device_put(x, dev0), jax.device_put(y, dev0))[0])
    log(f"{tag} first-step loss: dp=4 {first:.4f} vs one chip {ref:.4f} "
        f"on the same {batch}-row batch")
    check(abs(first - ref) <= BF16_ATOL * max(1.0, abs(ref)),
          f"dp=4 loss {first} disagrees with the one-chip loss {ref}")

    # kvstore='tpu': values committed to four different devices sum, and
    # every puller gets the sum on ITS device; then the gluon.Trainer path
    ctxs = [mx.Context(mx.current_context().device_type, i)
            for i in range(4)]
    kv = mx.kvstore.create("tpu")
    kv.init("g", mx.nd.zeros((1024,), ctx=ctxs[0]))
    kv.push("g", [mx.nd.ones((1024,), ctx=c) * (i + 1)
                  for i, c in enumerate(ctxs)])
    outs = [mx.nd.zeros((1024,), ctx=c) for c in ctxs]
    kv.pull("g", out=outs)
    for c, o in zip(ctxs, outs):
        check(o.data_.devices() == {c.jax_device()}
              and bool(np.all(o.asnumpy() == 10.0)),
              f"kvstore('tpu') pull on {c}: devices {o.data_.devices()}, "
              f"value {o.asnumpy()[:2]}")
    log(f"{tag} kvstore('tpu') push/pull over {ctxs}: sum 10.0 on each")
    eager_phase("tpu", tag)

    # one {"sp": 4} ring with the flash kernel per hop
    mesh = parallel.create_mesh({"sp": 4}, jax.devices()[:4])
    rs = np.random.RandomState(4)
    q, k, v = [jnp.asarray(rs.randn(1, 4, sz["ring_t"], 64) * 0.5,
                           jnp.bfloat16) for _ in range(3)]
    out = parallel.ring.ring_attention(q, k, v, mesh=mesh, causal=True,
                                       impl="flash", interpret=interpret)
    err = max_err(out, dense_attention(q, k, v, True))
    log(f"{tag} ring attention sp=4 impl=flash T={sz['ring_t']}: err "
        f"{err:.4f} over {len(out.sharding.device_set)} devices")
    check(err <= BF16_ATOL and len(out.sharding.device_set) == 4,
          f"sp=4 flash ring: err {err}")


# -------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="debugging only: toy sizes on the CPU, kernels in "
                         "interpret mode; prints no result line")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal
    sz = REHEARSAL if rehearsal else FULL
    tag = "[CPU REHEARSAL — not a chip result]" if rehearsal else "[chip]"

    t_start = time.perf_counter()
    dev = device_phase(rehearsal)
    if rehearsal and dev["platform"] == "tpu":
        sys.exit("chip_smoke: --cpu-rehearsal is for a host without a chip")
    counter = CompileCounter()
    platform = dev["platform"]
    failed = []
    shared = {}

    def run(name, fn):
        # a phase boundary: report the failure with its traceback, run the
        # remaining phases (one chip call should say everything it can),
        # and fail the run at the end
        t0 = time.perf_counter()
        try:
            fn()
            log(f"{tag} phase {name}: ok ({time.perf_counter() - t0:.1f} s; "
                f"{counter.line()})")
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"{tag} phase {name}: FAILED")

    def train():
        shared["net"] = build_net(sz["image"], tag)
        shared["one_chip"] = train_phase(
            shared["net"], 1, sz["batch"], sz, platform, tag)[0]
        eager_phase("device", tag)

    run("train", train)
    run("kernels", lambda: kernels_phase(sz, rehearsal, tag))
    if dev["count"] >= 4 and "one_chip" in shared:
        run("four chips", lambda: four_chip_phase(
            shared["net"], shared["one_chip"], sz, platform, rehearsal, tag))
    elif dev["count"] >= 4:
        failed.append("four chips (no one-chip trainer to compare with)")
    else:
        log(f"{tag} phase four chips: not run ({dev['count']} device(s))")

    log(f"{tag} total {time.perf_counter() - t_start:.0f} s; "
        f"{counter.line()}")
    if failed:
        log(f"{tag} FAILED phases: {', '.join(failed)}")
        return 1
    if rehearsal:
        log("CPU REHEARSAL passed — this is not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
