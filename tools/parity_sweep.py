#!/usr/bin/env python
"""parity_sweep.py — per-op chip-vs-CPU numerical parity (SURVEY §4's
acceptance mechanism: the reference's cpu-vs-gpu check_consistency runs,
re-aimed at cpu-vs-tpu).

Runs a battery of representative symbols through test_utils
.check_consistency on [cpu fp32, tpu fp32], comparing outputs AND
gradients, in TWO precision modes:

- strict:  jax_default_matmul_precision='highest' — fp32 stays fp32 on
  the MXU; tolerance 1e-3 relative. This is the correctness gate.
- default: the TPU's native mode, where fp32 matmuls run through the
  bf16 MXU datapath; tolerance 3e-2 relative. This documents the
  bf16-on-MXU numerics envelope users get out of the box.

    python tools/parity_sweep.py [--report PARITY_TPU.json]

Requires a TPU-visible jax (skips with a message otherwise). The same
battery runs in CI via tests/test_tpu_parity.py when
MXNET_TPU_TEST_PLATFORM lists the TPU platform plus cpu ('tpu,cpu').

``--int8`` runs the INT8 accuracy gate instead (ROADMAP item 1,
docs/quantization.md; any backend — it is a numerics gate, not a perf
one): ResNet-18, BN-folded and quantized through the full int8-grid
path, must keep top-1 agreement with fp32 >= 0.99 on a
calibration-held-out synthetic batch, for BOTH calibration modes
(naive and entropy). One JSON line, non-zero exit on regression.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def battery():
    """(name, build(sym) -> symbol, shapes dict) — representative coverage
    of every compute family; gradients are checked for all of them."""
    import mxnet_tpu.symbol as sym

    def v(n):
        return sym.Variable(n)

    return [
        ("fully_connected",
         lambda: sym.FullyConnected(v("data"), num_hidden=32, name="fc"),
         {"data": (8, 64)}),
        ("convolution",
         lambda: sym.Convolution(v("data"), kernel=(3, 3), pad=(1, 1),
                                 num_filter=8, name="cv"),
         {"data": (2, 4, 16, 16)}),
        ("deconvolution",
         lambda: sym.Deconvolution(v("data"), kernel=(3, 3), stride=(2, 2),
                                   num_filter=4, no_bias=True, name="dc"),
         {"data": (2, 4, 8, 8)}),
        ("batchnorm",
         lambda: sym.BatchNorm(v("data"), fix_gamma=False, name="bn"),
         {"data": (4, 8, 6, 6)}),
        ("layernorm",
         lambda: sym.LayerNorm(v("data"), name="ln"),
         {"data": (4, 32)}),
        ("pool_max",
         lambda: sym.Pooling(v("data"), kernel=(2, 2), stride=(2, 2),
                             pool_type="max"),
         {"data": (2, 4, 8, 8)}),
        ("pool_avg",
         lambda: sym.Pooling(v("data"), kernel=(3, 3), stride=(2, 2),
                             pad=(1, 1), pool_type="avg"),
         {"data": (2, 4, 8, 8)}),
        ("softmax_ce",
         lambda: sym.log_softmax(sym.FullyConnected(
             v("data"), num_hidden=10, name="fc2")),
         {"data": (8, 32)}),
        ("elemwise_chain",
         lambda: sym.tanh(v("a") * v("b") + sym.exp(v("a")) / 2.0),
         {"a": (4, 16), "b": (4, 16)}),
        ("reductions",
         lambda: sym.sum(v("data"), axis=1) + sym.mean(v("data"), axis=1)
         + sym.norm(v("data"), axis=1),
         {"data": (4, 16)}),
        ("dot",
         lambda: sym.dot(v("a"), v("b")),
         {"a": (16, 32), "b": (32, 8)}),
        ("batch_dot",
         lambda: sym.batch_dot(v("a"), v("b")),
         {"a": (4, 8, 16), "b": (4, 16, 8)}),
        ("linalg",
         lambda: sym.linalg_gemm2(v("a"), v("b")),
         {"a": (8, 8), "b": (8, 8)}),
        ("rnn_lstm",
         lambda: sym.RNN(v("data"), state_size=8, num_layers=1,
                         mode="lstm", state_outputs=False, name="rnn"),
         {"data": (5, 2, 8)}),
        ("attention",
         lambda: sym.scaled_dot_product_attention(v("q"), v("k"), v("v"),
                                                  causal=True),
         {"q": (1, 2, 16, 8), "k": (1, 2, 16, 8), "v": (1, 2, 16, 8)}),
        ("embedding_take",
         lambda: sym.take(v("w"), sym.BlockGrad(
             sym.clip(v("i") * 0 + 2, a_min=0, a_max=7))),
         {"w": (8, 4), "i": (3,)}),
        ("roi_align",
         lambda: sym.contrib.ROIAlign(
             v("data"), sym.BlockGrad(v("rois") * 0 +
                                      sym.BlockGrad(v("rois"))),
             pooled_size=(2, 2), spatial_scale=1.0),
         {"data": (1, 2, 8, 8), "rois": (2, 5)}),
        ("upsampling",
         lambda: sym.UpSampling(v("data"), scale=2, sample_type="nearest"),
         {"data": (1, 2, 4, 4)}),
        ("transposes",
         lambda: sym.transpose(sym.Reshape(v("data"), shape=(4, -1)),
                               axes=(1, 0)),
         {"data": (2, 2, 8)}),
        ("norm_activations",
         lambda: sym.LeakyReLU(sym.L2Normalization(v("data")),
                               act_type="elu"),
         {"data": (4, 16)}),
    ]


# ---------------------------------------------------------------------------
# INT8 accuracy gate (ROADMAP item 1): the deploy-blocking check that a
# calibrated full-int8 ResNet agrees with fp32 on held-out data. Runs on
# any backend — quantization numerics are backend-portable by design
# (symmetric int8 grid, int32 accumulation).
# ---------------------------------------------------------------------------

INT8_AGREEMENT_GATE = 0.99


def int8_gate(classes=10, hw=32, calib_n=64, holdout_n=128, seed=0):
    """Top-1 agreement of the full-int8 ResNet-18 vs fp32, per calib
    mode, on a synthetic batch HELD OUT from calibration. Returns
    (exit_code, result dict) and prints the one-line JSON.

    The synthetic batch is GAUSSIAN (the distribution of normalized
    images) — entropy/KL calibration clips distribution tails by
    design, which is exactly right for gaussian-tailed data but
    pathological on tail-free uniform noise (it would clip real mass;
    the repo's own calibration tests document the same effect). Model
    init is seeded so the gate is a deterministic regression check."""
    import mxnet_tpu as mx
    import mxnet_tpu.symbol as sym
    from mxnet_tpu.contrib.quantization import (calibrate, fold_batch_norm,
                                                quantize_model)
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    net = vision.resnet18_v1(classes=classes, thumbnail=True)
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 3, hw, hw)))
    s = net(sym.Variable("data"))
    params = {k: p.data() for k, p in net.collect_params().items()}
    fargs = {k: v for k, v in params.items() if k in s.list_arguments()}
    fauxs = {k: v for k, v in params.items()
             if k in s.list_auxiliary_states()}
    fs, fargs, fauxs = fold_batch_norm(s, fargs, fauxs)

    calib_x = rng.randn(calib_n, 3, hw, hw).astype(np.float32)
    holdout = rng.randn(holdout_n, 3, hw, hw).astype(np.float32)
    ref = fs.bind(mx.cpu(), {**fargs, "data": mx.nd.array(holdout)},
                  grad_req="null").forward(is_train=False)[0].asnumpy()

    agreement = {}
    ok_all = True
    for mode in ("naive", "entropy"):
        t0 = time.time()
        table = calibrate(fs, fargs, fauxs,
                          mx.io.NDArrayIter(data=calib_x, batch_size=32),
                          calib_mode=mode)
        qsym, qargs, qaux = quantize_model(fs, fargs, fauxs,
                                           calib_table=table,
                                           quantize_mode="full")
        got = qsym.bind(mx.cpu(), {**qargs, "data": mx.nd.array(holdout)},
                        grad_req="null") \
            .forward(is_train=False)[0].asnumpy()
        agree = float((ref.argmax(1) == got.argmax(1)).mean())
        agreement[mode] = round(agree, 4)
        ok = agree >= INT8_AGREEMENT_GATE
        ok_all = ok_all and ok
        print(f"[int8] {mode:8s} top-1 agreement {agree:.4f} "
              f"(gate {INT8_AGREEMENT_GATE}) "
              f"{'ok' if ok else 'FAIL'} ({time.time() - t0:.0f}s)",
              file=sys.stderr, flush=True)

    result = {
        "metric": "int8_top1_agreement_min",
        "value": min(agreement.values()),
        "unit": "fraction",
        "vs_baseline": INT8_AGREEMENT_GATE,  # the gate itself
        "extra": {
            "agreement": agreement,
            "gate": INT8_AGREEMENT_GATE,
            "model": f"resnet18_v1 thumbnail {hw}x{hw}, "
                     f"{classes} classes",
            "calib_examples": calib_n,
            "holdout_examples": holdout_n,
        },
    }
    print(json.dumps(result))
    return (0 if ok_all else 1), result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--report", default="PARITY_TPU.json")
    ap.add_argument("--full", action="store_true",
                    help="registry-wide record/replay sweep (record on "
                         "CPU via the test suite, replay cpu-vs-tpu)")
    ap.add_argument("--catalog", default="/tmp/mxnet_tpu_opcatalog",
                    help="recorded-call dir for --full (reused if present)")
    ap.add_argument("--int8", action="store_true",
                    help="INT8-vs-fp32 top-1 agreement gate (>= 0.99 on "
                         "the calibration-held-out batch, both calib "
                         "modes); runs on any backend")
    args = ap.parse_args()

    if args.int8:
        return int8_gate()[0]

    if args.full:
        if not os.path.isdir(args.catalog) or not os.listdir(args.catalog):
            record_catalog(args.catalog)
        return replay_catalog(args.catalog, args.report)

    import jax

    if not any(d.platform != "cpu" for d in jax.devices()):
        print("no TPU visible; parity sweep needs a chip", file=sys.stderr)
        return 2

    import mxnet_tpu as mx
    from mxnet_tpu.test_utils import check_consistency

    # strict atol 5e-4 absorbs transcendental-approximation differences
    # (TPU VPU exp/tanh vs libm) at tiny magnitudes. default-mode atol:
    # bf16 mantissa rounding accumulates as ~eps_bf16 * sqrt(K) in K-term
    # contractions and is AMPLIFIED by cancellation in backward passes —
    # an ABSOLUTE band (relative bounds are meaningless near zero); 0.12
    # covers K<=64 unit-scale data with gradient chains. This measured
    # envelope is the bf16-on-MXU numerics contract (PERF.md).
    modes = [("strict", "highest", 1e-3, 5e-4),
             ("default", None, 3e-2, 1.2e-1)]
    report = {"device": str(jax.devices()[0]), "modes": {}}
    ok_all = True
    for mode_name, precision, rtol, atol in modes:
        if precision is not None:
            jax.config.update("jax_default_matmul_precision", precision)
        else:
            jax.config.update("jax_default_matmul_precision", None)
        results = []
        for name, build, shapes in battery():
            ctx_list = [
                {"ctx": mx.cpu(), "type_dict":
                 {k: np.float32 for k in shapes}, **shapes},
                {"ctx": mx.tpu(), "type_dict":
                 {k: np.float32 for k in shapes}, **shapes},
            ]
            t0 = time.time()
            np.random.seed(7)  # reproducible inputs per op
            try:
                check_consistency(build(), ctx_list, rtol=rtol, atol=atol)
                status, err = "ok", None
            except Exception as e:  # noqa: BLE001 - report, don't abort
                status, err = "FAIL", f"{type(e).__name__}: {e}"
                ok_all = False
            results.append({"op": name, "status": status,
                            "seconds": round(time.time() - t0, 2),
                            **({"error": err[:500]} if err else {})})
            print(f"[{mode_name}] {name:20s} {status} "
                  f"({results[-1]['seconds']}s)", flush=True)
        report["modes"][mode_name] = {
            "matmul_precision": precision or "tpu default (bf16 MXU)",
            "rtol": rtol, "atol": atol,
            "passed": sum(r["status"] == "ok" for r in results),
            "total": len(results), "results": results}

    with open(args.report, "w") as f:
        json.dump(report, f, indent=2)
    for m, d in report["modes"].items():
        print(f"{m}: {d['passed']}/{d['total']} parity checks passed")
    print(f"report -> {args.report}")
    return 0 if ok_all else 1




# ---------------------------------------------------------------------------
# registry-wide sweep (round 5): record/replay. Phase A runs the per-op
# test files on CPU with MXNET_TPU_RECORD_OPS=<dir>, capturing the first
# concrete call of every op (the exact inputs the suite certified
# against numpy). Phase B replays each call cpu-vs-tpu in both precision
# modes, comparing outputs (and input-gradients for differentiable ops).
# ---------------------------------------------------------------------------

RECORD_TEST_FILES = [
    "tests/test_op_numerics.py", "tests/test_op_tail_r5.py",
    "tests/test_quantized_tail.py", "tests/test_detection.py",
    "tests/test_vision_extra.py", "tests/test_image_ops.py",
    "tests/test_gluon_rnn.py", "tests/test_quantization_pdf.py",
    "tests/test_compression_group_ops.py",
    "tests/test_control_flow_bucketing.py",
    "tests/test_op_eager_battery.py",  # trace-only-path ops, eagerly
]

# stochastic ops: outputs are draws from the seeded key stream — the key
# advances identically but jax PRNG bit-streams are hash-based and
# identical across backends, so values ARE comparable; listed ones with
# device-dependent behavior compare shape/dtype only
SHAPE_ONLY = {"_shuffle"}
# ops that cannot run under jit (host-side calibration; data-dependent
# output shapes) replay eagerly — the deferred-shape boundary the
# reference handles with dynamic-shape NDArrays (SURVEY "excl" rows)
HOST_ONLY = {"_contrib_calibrate_entropy", "boolean_mask",
             "_sample_multinomial"}
# eigendecomposition: eigenvector columns are sign-ambiguous across
# backends; compare |values| (eigenvalues compare exactly)
ABS_COMPARE = {"linalg_syevd"}
# documented default-mode exemptions (strict mode must still pass):
# bilinear sampling computes gather COORDINATES through the bf16 MXU, so
# sub-ulp coordinate shifts move whole samples — the bf16 envelope does
# not bound data-dependent gather positions (triage: PERF.md round 5)
DEFAULT_EXEMPT = {"SpatialTransformer"}


GRAD_SKIP = {"linalg_syevd"}  # eigenvector sign ambiguity taints grads


def _grad_args(op, arrays, params):
    import numpy as np

    if op.no_grad or op.name in GRAD_SKIP:
        return ()
    return tuple(i for i, a in enumerate(arrays)
                 if a is not None
                 and np.issubdtype(np.asarray(a).dtype, np.floating))


def replay_catalog(catalog_dir, report_path):
    import glob
    import pickle

    import jax
    import numpy as np

    import mxnet_tpu  # registers ops  # noqa: F401
    import mxnet_tpu.operator  # Custom  # noqa: F401
    from mxnet_tpu.ops.registry import get_op

    cpu = jax.devices("cpu")[0]
    tpus = [d for d in jax.devices() if d.platform != "cpu"]
    if not tpus:
        print("no TPU visible; --full replay needs a chip", file=sys.stderr)
        return 2
    tpu = tpus[0]

    modes = [("strict", "highest", 1e-3, 5e-4),
             ("default", None, 3e-2, 1.2e-1)]
    entries = sorted(glob.glob(f"{catalog_dir}/*.pkl"))
    print(f"replaying {len(entries)} recorded ops", flush=True)
    report = {"device": str(tpu), "modes": {}}
    ok_all = True
    for mode_name, precision, rtol, atol in modes:
        jax.config.update("jax_default_matmul_precision", precision)
        results = []
        for path in entries:
            with open(path, "rb") as f:
                rec = pickle.load(f)
            name = rec["name"]
            op = get_op(name)
            t0 = time.time()
            if mode_name == "default" and name in DEFAULT_EXEMPT:
                results.append({"op": name, "status": "exempt",
                                "seconds": 0.0})
                continue
            try:
                fn = op.closed(dict(rec["params"]))
                gargs = _grad_args(op, rec["arrays"], rec["params"])

                def combined(*arrs):
                    import jax.numpy as jnp

                    out = fn(*arrs)
                    outs = out if isinstance(out, tuple) else (out,)
                    grads = ()
                    if gargs:
                        def loss(*fa):
                            full = list(arrs)
                            for i, ix in enumerate(gargs):
                                full[ix] = fa[i]
                            o = fn(*full)
                            os_ = o if isinstance(o, tuple) else (o,)
                            return sum(
                                jnp.sum(x.astype(jnp.float32)) for x in os_
                                if jnp.issubdtype(x.dtype, jnp.floating))
                        try:
                            grads = jax.grad(loss, argnums=tuple(
                                range(len(gargs))))(
                                *[arrs[i] for i in gargs])
                        except Exception:
                            grads = ()  # non-differentiable: fwd-only
                    return tuple(outs) + tuple(grads)

                # ONE compiled executable per device — eager replay would
                # compile and dispatch every primitive separately
                jfn = combined if name in HOST_ONLY else jax.jit(combined)

                def run(dev):
                    arrs = [a if a is None else jax.device_put(a, dev)
                            for a in rec["arrays"]]
                    return [np.asarray(o) for o in jfn(*arrs)]

                ref = run(cpu)
                got = run(tpu)
                assert len(ref) == len(got)
                if name in SHAPE_ONLY:
                    for r, g_ in zip(ref, got):
                        assert r.shape == g_.shape and r.dtype == g_.dtype
                else:
                    for r, g_ in zip(ref, got):
                        if name in ABS_COMPARE:
                            r, g_ = np.abs(r), np.abs(g_)
                        if np.issubdtype(r.dtype, np.floating):
                            np.testing.assert_allclose(
                                g_.astype(np.float64),
                                r.astype(np.float64), rtol=rtol, atol=atol)
                        else:
                            assert (r == g_).all(), "integer outputs differ"
                status, err = "ok", None
            except Exception as e:  # noqa: BLE001
                status, err = "FAIL", f"{type(e).__name__}: {e}"
                ok_all = False
            results.append({"op": name, "status": status,
                            "seconds": round(time.time() - t0, 2),
                            **({"error": err[:300]} if err else {})})
            if status != "ok":
                print(f"[{mode_name}] {name}: {status}", flush=True)
        passed = sum(r["status"] == "ok" for r in results)
        report["modes"][mode_name] = {
            "matmul_precision": precision or "tpu default (bf16 MXU)",
            "rtol": rtol, "atol": atol, "passed": passed,
            "total": len(results), "results": results}
        print(f"[{mode_name}] {passed}/{len(results)} ops pass", flush=True)

    with open(report_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"report -> {report_path}")
    return 0 if ok_all else 1


def record_catalog(catalog_dir):
    import subprocess

    env = dict(os.environ)
    env["MXNET_TPU_RECORD_OPS"] = catalog_dir
    env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", *RECORD_TEST_FILES],
        env=env, cwd=repo)
    if r.returncode != 0:
        raise RuntimeError("record phase: test run failed")


if __name__ == "__main__":
    sys.exit(main())
