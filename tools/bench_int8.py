"""Quantized-MODEL throughput on the chip (VERDICT r4 next #3; now the
serving INT8 gate, docs/quantization.md).

Builds ResNet-18 (224² NCHW), folds BatchNorm, quantizes the whole graph
onto the int8 grid (quantize_mode='full' + integer-grid propagation:
conv/relu/residual-add/global-pool all integer), and measures inference
img/s against the bf16 and fp32 fp graphs — a model-level number, not a
matmul-loop microbenchmark. Also reports the int8-vs-fp32 top-1
agreement on the synthetic batch (the accuracy GATE lives in
tools/parity_sweep.py --int8; real-data mAP belongs to
tools/validate_baselines.py on a data-equipped host).

Prints ONE JSON line (same convention as serving_bench.py /
dispatch_bench.py):

    {"metric": "resnet18_int8_infer", "value": <int8 img/s>,
     "unit": "img/s", "vs_baseline": <int8/bf16 model-level speedup>,
     "extra": {...}}

Acceptance gate (non-zero exit on regression): int8 >= 1.25x bf16
model-level. Needs an accelerator and exits non-zero without one (there
is no int8 MXU path to measure on a CPU); the JSON line names the device.
PERF.md history: 1.45x (719 vs 496 img/s) on the previous installation.

Run: python tools/bench_int8.py [--batch 128] [--iters 20]
     [--calib naive|entropy]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GATE_INT8_VS_BF16 = 1.25


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--calib", default="naive",
                    choices=("naive", "entropy"))
    args = ap.parse_args(argv)

    import mxnet_tpu as mx
    import mxnet_tpu.symbol as sym
    from mxnet_tpu.contrib.quantization import (calibrate, fold_batch_norm,
                                                quantize_model)
    from mxnet_tpu.gluon.model_zoo import vision

    from mxnet_tpu.observability import perf

    device = perf.require_chip()
    dev = mx.tpu()
    rng = np.random.RandomState(0)

    net = vision.resnet18_v1(classes=1000)
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 3, 224, 224)))
    s = net(sym.Variable("data"))
    params = {k: p.data() for k, p in net.collect_params().items()}
    fargs = {k: v for k, v in params.items() if k in s.list_arguments()}
    fauxs = {k: v for k, v in params.items()
             if k in s.list_auxiliary_states()}
    fs, fargs, fauxs = fold_batch_norm(s, fargs, fauxs)

    calib_x = rng.rand(32, 3, 224, 224).astype(np.float32)
    calib = mx.io.NDArrayIter(data=calib_x, batch_size=16)
    t0 = time.perf_counter()
    table = calibrate(fs, fargs, fauxs, calib, calib_mode=args.calib)
    calib_s = time.perf_counter() - t0
    qsym, qargs, qaux = quantize_model(fs, fargs, fauxs, calib_table=table,
                                       quantize_mode="full")

    x = rng.rand(args.batch, 3, 224, 224).astype(np.float32)

    def bench(symbol, sargs, saux, dtype=None):
        a = dict(sargs)
        xs = x
        if dtype is not None:
            a = {k: v.astype(dtype) if v.dtype == np.float32 else v
                 for k, v in a.items()}
            xs = x.astype(dtype)
        a = {k: v.as_in_context(dev) for k, v in a.items()}
        ex = symbol.bind(dev, {**a, "data": mx.nd.array(xs, ctx=dev)},
                         aux_states={k: v.as_in_context(dev)
                                     for k, v in saux.items()},
                         grad_req="null")
        out = ex.forward(is_train=False)[0]
        out.wait_to_read()
        # dependency-chained loop: feed a scalar of the output back into
        # the input so each iteration's time ends at a host read
        t0 = time.perf_counter()
        chain = 0.0
        for _ in range(args.iters):
            ex.arg_dict["data"][0, 0, 0, 0] = float(chain)
            o = ex.forward(is_train=False)[0]
            chain = float(o.asnumpy()[0, 0]) * 1e-9
        dt = time.perf_counter() - t0
        return args.batch * args.iters / dt, out.asnumpy()

    res = {}
    res["fp32"], out_fp = bench(fs, fargs, fauxs)
    res["bf16"], _ = bench(fs, fargs, fauxs, dtype="bfloat16")
    res["int8"], out_q = bench(qsym, qargs, qaux)
    agree = float((out_fp.argmax(1) == out_q.argmax(1)).mean())
    ratio = res["int8"] / res["bf16"]
    for k, v in res.items():
        print(f"{k}: {v:.1f} img/s", file=sys.stderr)
    print(f"{device}: int8/bf16: {ratio:.2f}x (gate {GATE_INT8_VS_BF16}x), "
          f"int8/fp32: {res['int8'] / res['fp32']:.2f}x, "
          f"top1 agreement vs fp32: {agree:.3f}, "
          f"calibration ({args.calib}): {calib_s:.1f}s", file=sys.stderr)

    gate_ok = ratio >= GATE_INT8_VS_BF16
    print(json.dumps({
        "metric": "resnet18_int8_infer",
        "value": round(res["int8"], 1),
        "unit": "img/s",
        "vs_baseline": round(ratio, 3),  # int8 vs bf16, model-level
        "device": device,
        "extra": {
            "img_s": {k: round(v, 1) for k, v in res.items()},
            "int8_vs_bf16": round(ratio, 3),
            "int8_vs_fp32": round(res["int8"] / res["fp32"], 3),
            "top1_agreement": round(agree, 4),
            "calib_mode": args.calib,
            "calib_seconds": round(calib_s, 2),
            "batch": args.batch,
            "gate_int8_vs_bf16": GATE_INT8_VS_BF16,
            "gate": "ok" if gate_ok else "FAIL",
        },
    }))
    return 0 if gate_ok else 1


if __name__ == "__main__":
    sys.exit(main())
