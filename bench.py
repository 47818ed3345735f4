"""Benchmark: ResNet-50 training throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"device"}. Baseline: the reference's published ResNet-50 training
throughput, 109 img/s at bs=32 on 1x K80 (BASELINE.md,
reference example/image-classification/README.md:154).

Every mode needs an accelerator and exits non-zero without one, or when
any of its configurations fails: a number from XLA-CPU is never printed
under a device metric's name. Every printed line names the device
(platform, device_kind, count).

Analysis (stderr): per-config img/s and MFU against the device's
published bf16 peak (observability.perf.DEVICE_PEAKS). ResNet-50 fwd
≈ 4.1 GFLOP/img at 224²; training ≈ 3×.

``--model=transformer`` switches to the dp×fsdp×tp transformer
pretraining bench (docs/parallel.md): a model-zoo decoder-only LM,
SpecLayout-sharded, trained in bf16 through ONE donated captured
executable per step with dependency-chained device timing on, so the
reported MFU is read back from the perf ledger's ``mxnet_tpu_mfu``
gauge (observability/perf.py) rather than re-derived from an analytic
flop count. Gated against TRANSFORMER_MFU_FLOOR; the companion
regression key is ``transformer_step@tuned`` in tools/perf_gate.py.
"""
from __future__ import annotations

import json
import sys
import time

RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.1e9
BASELINE_IMG_S = 109.0  # reference K80 img/s, bs=32

# MFU floor for --model=transformer. The gauge divides XLA-analyzed
# flops by dependency-chained device wall against the device's published
# peak (observability/perf.py); a step that stops overlapping or
# silently falls off the captured path lands under it.
TRANSFORMER_MFU_FLOOR = 1e-4

# Scaling-efficiency floor for --dist: the pod-partitioned captured
# step over the GLOBAL mesh must stay within 10% of running the same
# global batch on a single host's device slice — the gate catches
# pod-partitioning overhead (per-host program dispatch, mesh
# bookkeeping, halo/reshard cost).
DIST_SCALING_FLOOR = 0.9


def _require_chip():
    """(device record, line tag) of the chip this run measures; raises,
    naming the missing chip, when jax found none."""
    from mxnet_tpu.observability import perf

    dev = perf.require_chip()
    return dev, f"[{dev['platform']}:{dev['kind']} x{dev['count']}]"


def _throughput(trainer, x, y, iters, warmup=2, step=None):
    """Training-step throughput on a device-resident synthetic batch — the
    same methodology as the reference's own benchmark harnesses
    (example/image-classification/benchmark_score.py feeds synthetic data
    from the device). Input-pipeline throughput is benchmarked separately
    (io/record_pipeline). ``step`` overrides the step callable (the
    ``--capture`` mode passes the capture()-wrapped step)."""
    import jax

    step = step or trainer.step
    xd = jax.device_put(x, trainer._batch_sharding)
    yd = jax.device_put(y, trainer._batch_sharding)
    for _ in range(warmup):
        step(xd, yd).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = step(xd, yd)
    loss.block_until_ready()
    dt = time.perf_counter() - t0
    return x.shape[0] * iters / dt


def main(capture_mode=False):
    import numpy as np
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.observability import perf

    dev, tag = _require_chip()
    peak_flops = perf.device_peaks(dev["kind"])["bf16_flops_per_s"]

    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    rng = np.random.RandomState(0)

    # (net kwargs, dtype, batch): the TPU-native config (channels-last +
    # space-to-depth stem, PERF.md) leads; the reference-layout NCHW net
    # and fp32 run for comparison. A config that fails fails the run.
    configs = [({"layout": "NHWC", "stem": "s2d"}, "bfloat16", 256),
               ({}, "bfloat16", 256),
               ({}, None, 128)]
    iters = 30

    nets = {}
    best = None
    for net_kw, dtype, batch in configs:
        key = tuple(sorted(net_kw.items()))
        if key not in nets:
            net = vision.resnet50_v1(**net_kw)
            net.initialize(mx.initializer.Xavier())
            net(mx.nd.zeros((2, 3, 224, 224)))  # materialize params
            nets[key] = net
        net = nets[key]
        x = rng.rand(batch, 3, 224, 224).astype(np.float32)
        y = (rng.rand(batch) * 1000).astype(np.float32)
        trainer = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            "sgd", {"learning_rate": 0.1, "momentum": 0.9}, mesh=mesh,
            dtype=dtype)
        step = None
        if capture_mode:
            # whole-program capture: step programs compile through
            # the capture/AOT path
            from mxnet_tpu import capture as _capture

            step = _capture.capture(trainer)
        img_s = _throughput(trainer, x, y, iters, step=step)
        mfu = img_s * RESNET50_TRAIN_FLOPS_PER_IMG / peak_flops
        print(f"# {tag} bs={batch} dtype={dtype or 'float32'} "
              f"{net_kw or 'NCHW'}: {img_s:.1f} img/s, "
              f"MFU={100 * mfu:.1f}%", file=sys.stderr)
        if best is None or img_s > best:
            best = img_s

    out = {
        "metric": "resnet50_train_throughput",
        "value": round(best, 2),
        "unit": "img/s/chip",
        "vs_baseline": round(best / BASELINE_IMG_S, 3),
        "device": dev,
    }
    if capture_mode:
        from mxnet_tpu import capture as _capture

        out["mode"] = "captured"
        out["capture_stats"] = {k: v for k, v in _capture.stats().items()
                                if v}
    print(json.dumps(out))


def main_transformer(capture_mode=True):
    """fsdp×tp transformer pretraining at measured MFU, on the four
    chips of one host (fewer is an error, not a smaller mesh).

    The step count is CI-sized; the point of this mode is the
    *measurement path* — captured donated executable, device timing,
    ledger-derived MFU — not a big number.
    """
    import numpy as np
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import capture, gluon, parallel
    from mxnet_tpu.gluon.model_zoo import transformer as tzoo
    from mxnet_tpu.observability import metrics, perf

    dev, tag = _require_chip()
    spec = {"fsdp": 2, "tp": 2}
    if dev["count"] < 4:
        sys.exit(f"bench.py --model=transformer: {tag} the {spec} mesh "
                 "needs 4 chips")
    mesh = parallel.create_mesh(spec, jax.devices()[:4])
    layout = parallel.SpecLayout.for_mesh(mesh)

    mx.random.seed(0)
    net = tzoo.transformer_lm(prefix="benchtlm_")
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 8)))  # materialize params

    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        "adam", {"learning_rate": 1e-3}, mesh=mesh,
        param_rules=layout.param_rules(),
        batch_axis_name=layout.batch_axes() or "dp",
        dtype="bfloat16")
    step = capture.capture(trainer) if capture_mode else trainer.step

    rng = np.random.RandomState(0)
    batch, seqlen = 8, 32
    # int32 token ids: float ids would be bf16-cast with the activations
    x = (rng.rand(batch, seqlen) * 64).astype(np.int32)
    y = (rng.rand(batch, seqlen) * 64).astype(np.int32)
    xd = jax.device_put(x, trainer.batch_sharding)
    yd = jax.device_put(y, trainer.batch_sharding)

    iters = 30
    prev = perf.set_device_time(True)
    try:
        step(xd, yd).block_until_ready()  # compile -> ledger entry
        t0 = time.perf_counter()
        loss = None
        for _ in range(iters):
            loss = step(xd, yd)
        loss.block_until_ready()
        dt = time.perf_counter() - t0
    finally:
        perf.set_device_time(prev)

    # MFU comes from the gauge, not a local formula: update_gauges()
    # folds the ledger's derived numbers into mxnet_tpu_mfu exactly as
    # the exporters do, and the bench reads the same labelset back
    perf.update_gauges()
    key, mfu = None, None
    for k, e in sorted(perf.ledger().items()):
        if e["label"] == "sharded_step" and e["mfu"] is not None:
            key, mfu = k, metrics.get("mxnet_tpu_mfu").value(executable=k)
            break
    tok_s = batch * seqlen * iters / dt
    print(f"# {tag} mesh={spec} dtype=bfloat16 captured={capture_mode}: "
          f"{tok_s:.0f} tok/s, loss={float(loss):.4f}, "
          f"MFU={'n/a' if mfu is None else f'{100 * mfu:.3f}%'}",
          file=sys.stderr)
    ok = mfu is not None and mfu >= TRANSFORMER_MFU_FLOOR
    out = {
        "metric": "transformer_train_mfu",
        "value": round(mfu, 6) if mfu is not None else 0.0,
        "unit": "mfu_fraction",
        "vs_baseline": round((mfu or 0.0) / TRANSFORMER_MFU_FLOOR, 3),
        "device": dev,
        "extra": {"mesh": spec, "tokens_per_s": round(tok_s, 1),
                  "ledger_key": key, "mfu_floor": TRANSFORMER_MFU_FLOOR,
                  "captured": capture_mode, "passed": ok},
    }
    if capture_mode:
        out["extra"]["capture_steps"] = capture.stats()["capture_steps"]
    print(json.dumps(out))
    return 0 if ok else 1


def main_dist():
    """Pod scaling-efficiency gate (docs/distributed.md).

    The visible chips are grouped into 4 host failure domains
    (``PodTopology.simulated``; a count it cannot split evenly raises).
    Strong scaling on a fixed global batch
    — time the captured transformer step (a) on the GLOBAL pod mesh at
    dp = hosts*chips and (b) on one host's device slice at dp = chips,
    and gate ``t_single / t_pod >= DIST_SCALING_FLOOR``.
    """
    import numpy as np
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import capture, gluon, parallel
    from mxnet_tpu.gluon.model_zoo import transformer as tzoo

    dev, tag = _require_chip()
    hosts = 4
    topo = parallel.PodTopology.simulated(hosts)
    chips = topo.devices_per_host
    # big enough that per-device program dispatch amortizes into the
    # compute; tiny batches would measure dispatch, not scaling
    batch, seqlen = 64, 64
    rng = np.random.RandomState(0)
    x = (rng.rand(batch, seqlen) * 64).astype(np.int32)
    y = (rng.rand(batch, seqlen) * 64).astype(np.int32)
    iters = 4

    def timed_step(mesh, prefix, pod=None):
        mx.random.seed(0)
        net = tzoo.transformer_lm(prefix=prefix)
        net.initialize(mx.initializer.Xavier())
        net(mx.nd.zeros((2, 8)))
        trainer = parallel.ShardedTrainer(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            "adam", {"learning_rate": 1e-3}, mesh=mesh,
            param_rules=parallel.SpecLayout.for_mesh(mesh).param_rules(),
            batch_axis_name="dp", dtype="bfloat16")
        if pod is not None:
            trainer.bind_pod(pod)
        step = capture.capture(trainer)
        xd = jax.device_put(x, trainer.batch_sharding)
        yd = jax.device_put(y, trainer.batch_sharding)
        step(xd, yd).block_until_ready()  # compile
        step(xd, yd).block_until_ready()  # warm
        t0 = time.perf_counter()
        loss = None
        for _ in range(iters):
            loss = step(xd, yd)
        loss.block_until_ready()
        return time.perf_counter() - t0, float(loss)

    pod_mesh, topo = parallel.pod_mesh({"dp": hosts * chips}, topo)
    t_pod, loss_pod = timed_step(pod_mesh, "benchpod_", pod=topo)
    single_devs = [topo.devices[o] for o in topo.host_ordinals(0)]
    single_mesh = parallel.create_mesh({"dp": chips}, single_devs)
    t_single, _ = timed_step(single_mesh, "benchsingle_")

    eff = t_single / t_pod if t_pod > 0 else 0.0
    ok = eff >= DIST_SCALING_FLOOR
    print(f"# {tag} pod={hosts}x{chips} dp={hosts * chips}: "
          f"t_pod={t_pod * 1e3 / iters:.1f}ms/step "
          f"t_single(dp={chips})={t_single * 1e3 / iters:.1f}ms/step "
          f"efficiency={eff:.3f} loss={loss_pod:.4f}", file=sys.stderr)
    print(json.dumps({
        "metric": "dist_scaling_efficiency",
        "value": round(eff, 4),
        "unit": "fraction_of_linear",
        "vs_baseline": round(eff / DIST_SCALING_FLOOR, 3),
        "device": dev,
        "extra": {"hosts": hosts, "devices_per_host": chips,
                  "t_pod_ms": round(t_pod * 1e3 / iters, 2),
                  "t_single_ms": round(t_single * 1e3 / iters, 2),
                  "floor": DIST_SCALING_FLOOR, "passed": ok},
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    unknown = set(sys.argv[1:]) - {"--capture", "--model=transformer",
                                   "--no-capture", "--dist"}
    if unknown:
        sys.exit(f"bench.py: unknown argument(s) {sorted(unknown)}")
    if "--dist" in sys.argv[1:]:
        sys.exit(main_dist())
    if "--model=transformer" in sys.argv[1:]:
        sys.exit(main_transformer(
            capture_mode="--no-capture" not in sys.argv[1:]))
    main(capture_mode="--capture" in sys.argv[1:])
