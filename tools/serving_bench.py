"""Microbenchmark: serving runtime (Predictor buckets + BatchServer).

Prints ONE JSON line (same convention as dispatch_bench.py /
resilience_bench.py) so BENCH rounds can track the inference path:

    {"metric": "serving_samples_per_s_b16", "value": ..., "unit":
     "samples/s", "vs_baseline": <batch16 vs single-request speedup>,
     "extra": {...}}

Sections (details on stderr):
- single:  Predictor batch-1 throughput (the unbatched floor)
- batched: Predictor batch-16 throughput (acceptance: >= 3x single)
- server:  closed-loop BatchServer sweep at several client concurrencies
           (throughput, p50/p99 latency, pad-waste %, shed count)
- overload: tiny queue + many clients, proving load shedding engages
- fleet:   4-replica Fleet sweep — p99 with every replica healthy vs the
           same offered load while one replica is crash-killed
           mid-stream (``replica_crash`` fault). Gates: zero lost
           requests (every future resolves to a result or a structured
           error) and degraded p99 <= 3x the healthy baseline; the
           victim must be auto-restarted and re-admitted.
- int8:    int8-vs-bf16 sweep (docs/quantization.md) — the SAME convnet
           served as a calibrated-int8 Predictor vs a bf16 one at batch
           128, plus a 2-variant Fleet ({model: {bf16, int8}}) proving
           per-model dtype-variant routing end to end. Gate: int8 >=
           1.25x bf16 model-level — the ROADMAP item-1 serving gate,
           measured 1.45x on ResNet-18 by tools/bench_int8.py.

- operate (``--operate``): the operator sweep — under continuous load
           the fleet scales 2 -> 4 (gates: scale-up-phase p99 <= 3x
           steady-state, every newcomer AOT-warm with
           ``warmup_cache_hits >= 1``) and a forced-bad-weights rollout
           is rejected by the canary health gate with zero
           client-visible errors and zero lost requests.

- decode (``--decode``): the generative-decode sweep (docs/decode.md) —
           continuous token-level batching over the paged KV cache
           under churn (staggered admissions, mixed prompt buckets,
           mid-stream cancellations, pool smaller than the offered
           load). Reports tokens/s, TTFT p50/p99 and inter-token p99;
           gates ZERO retraces after warmup, full token budgets on
           every completed stream, and a clean page pool.

Needs an accelerator (exits non-zero without one — every number here is
a device metric) and names it in the JSON line's ``device`` field.
Predictors are built without a ``ctx`` and so live on the chip, the
default context.

Run: python tools/serving_bench.py [--iters N]
     [--skip-fleet] [--skip-int8] [--operate] [--decode]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent import futures

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _mlp_params(seed=0):
    import numpy as np

    rng = np.random.RandomState(seed)
    return {
        "fc1_weight": (rng.randn(64, 20) * 0.1).astype(np.float32),
        "fc1_bias": np.zeros(64, np.float32),
        "fc2_weight": (rng.randn(10, 64) * 0.1).astype(np.float32),
        "fc2_bias": np.zeros(10, np.float32),
    }


def _build_predictor(mx, serving, buckets):
    data = mx.sym.var("data")
    h = mx.sym.FullyConnected(data, num_hidden=64, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="relu1")
    h = mx.sym.FullyConnected(h, num_hidden=10, name="fc2")
    out = mx.sym.softmax(h, name="prob")
    return serving.Predictor(out, _mlp_params(), input_shapes={"data": (20,)},
                             batch_sizes=buckets, warmup=True)


def bench_predict(pred, batch, iters):
    import numpy as np

    x = np.random.RandomState(1).rand(batch, 20).astype(np.float32)
    pred.predict(x)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = pred.predict(x)
    out[0].asnumpy()
    return iters * batch / (time.perf_counter() - t0)


def bench_server(mx, serving, pred, clients, per_client, timeout_ms=1.0,
                 **server_kw):
    import numpy as np

    serving.reset_stats()
    xs = np.random.RandomState(2).rand(clients, 1, 20).astype(np.float32)
    done = []
    lock = threading.Lock()
    srv = serving.BatchServer(pred, batch_timeout_ms=timeout_ms, **server_kw)
    barrier = threading.Barrier(clients + 1)

    def client(tid):
        barrier.wait()
        ok = shed = 0
        for _ in range(per_client):
            try:
                srv.submit(xs[tid]).result(timeout=60)
                ok += 1
            except Exception:
                shed += 1
        with lock:
            done.append((ok, shed))

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(clients)]
    for t in threads:
        t.start()
    barrier.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    srv.close()
    stats = serving.stats()
    served = sum(ok for ok, _ in done)
    shed = sum(s for _, s in done)
    pad = stats["serving_padded_samples"]
    total = max(1, stats["serving_batch_samples"])
    return {
        "rps": served / dt,
        "p50_us": stats["serving_p50_latency_us"],
        "p99_us": stats["serving_p99_latency_us"],
        "pad_waste_pct": 100.0 * pad / total,
        "batches": stats["serving_batches"],
        "requests": stats["serving_requests"],
        # client-observed failures; overload/deadline sheds surface to the
        # client as failed futures, so this is NOT additive with the
        # serving_shed_* counters
        "shed": shed,
        "offered": served + shed,
    }


def _fleet_factory():
    """Module-level so process-mode fleets could pickle it too; the
    bench runs thread mode."""
    import mxnet_tpu as mx
    from mxnet_tpu import serving

    return _build_predictor(mx, serving, buckets=(1, 16))


def bench_fleet(mx, serving, replicas=4, clients=8, per_client=40):
    """The fleet sweep: closed-loop load against a healthy fleet, then
    the same load while one replica is crash-killed mid-stream. Reports
    p99 for both phases plus the loss/error/restart accounting."""
    import numpy as np

    from mxnet_tpu.resilience import faults

    serving.reset_stats()
    fleet = serving.Fleet(_fleet_factory, replicas=replicas,
                          probe_interval_ms=100, breaker_k=3, retries=2,
                          backoff_ms=2, breaker_cooldown_ms=200,
                          server_kw={"batch_timeout_ms": 1.0})
    xs = np.random.RandomState(3).rand(clients, 1, 20).astype(np.float32)

    def run_phase(kill=False):
        lat, counts = [], {"ok": 0, "err": 0, "lost": 0}
        lock = threading.Lock()
        barrier = threading.Barrier(clients + 1)

        def client(tid):
            barrier.wait()
            for _ in range(per_client):
                t0 = time.perf_counter()
                fut = fleet.submit(xs[tid], deadline_ms=2000.0)
                try:
                    fut.result(timeout=10)
                    with lock:
                        counts["ok"] += 1
                        lat.append(time.perf_counter() - t0)
                except futures.TimeoutError:
                    # the future never resolved: a LOST request — the
                    # invariant the fleet must never break (py3.10:
                    # futures.TimeoutError is NOT the builtin)
                    with lock:
                        counts["lost"] += 1
                except Exception:
                    with lock:
                        counts["err"] += 1

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        for t in threads:
            t.start()
        if kill:
            # arm the crash storm before releasing the clients: the
            # victim dies mid-stream, the router retries around it
            ctx = faults.inject("replica_crash", times=6)
            ctx.__enter__()
        barrier.wait()
        try:
            for t in threads:
                t.join()
        finally:
            if kill:
                ctx.__exit__(None, None, None)
        lat.sort()
        p99 = int(lat[min(len(lat) - 1, int(0.99 * (len(lat) - 1) + 0.5))]
                  * 1e6) if lat else 0
        return p99, counts

    # warm every replica's lazy bucket executors off the clock
    for _ in range(2 * replicas):
        fleet.submit(xs[0], deadline_ms=5000.0).result(timeout=30)

    healthy_p99, healthy = run_phase(kill=False)
    degraded_p99, degraded = run_phase(kill=True)
    recovered = fleet.wait_healthy(timeout=30)
    stats = serving.stats()
    fleet.close()
    return {
        "replicas": replicas,
        "clients": clients,
        "fleet_p99_healthy_us": healthy_p99,
        "fleet_p99_killed_us": degraded_p99,
        "healthy": healthy,
        "killed": degraded,
        "lost": healthy["lost"] + degraded["lost"],
        "restarts": stats["fleet_restarts"],
        "retries": stats["fleet_retries"],
        "recovered": recovered,
    }


def bench_operate(mx, serving, clients=8, phase_s=2.0):
    """The operator sweep (docs/serving.md "Fleet operations"): under a
    continuous closed-loop load, scale the fleet 2 -> 4 and require the
    scale-up-phase p99 to stay <= 3x steady-state with every newcomer
    admitted AOT-warm (``warmup_cache_hits >= 1``); then push a
    NaN-poisoned weight artifact through the canaried rollout and
    require the gate to reject it with ZERO client-visible errors and
    zero lost requests end to end."""
    import numpy as np

    from mxnet_tpu.resilience import faults

    serving.reset_stats()
    faults.reset()
    tmp = None
    if not os.environ.get("MXNET_TPU_COMPILE_CACHE"):
        import tempfile

        tmp = tempfile.TemporaryDirectory(prefix="mxnet_tpu_operate_")
        os.environ["MXNET_TPU_COMPILE_CACHE"] = tmp.name
    fleet = serving.Fleet(_fleet_factory, replicas=2,
                          probe_interval_ms=100, breaker_k=3, retries=3,
                          backoff_ms=2, breaker_cooldown_ms=200,
                          server_kw={"batch_timeout_ms": 1.0})
    xs = np.random.RandomState(4).rand(clients, 1, 20).astype(np.float32)
    state = {"phase": "steady", "stop": False}
    lats = {"steady": [], "scale_up": []}
    counts = {"ok": 0, "err": 0, "lost": 0}
    lock = threading.Lock()

    def client(tid):
        while not state["stop"]:
            phase = state["phase"]
            t0 = time.perf_counter()
            fut = fleet.submit(xs[tid], deadline_ms=5000.0)
            try:
                fut.result(timeout=10)
                dt = time.perf_counter() - t0
                with lock:
                    counts["ok"] += 1
                    if phase in lats:
                        lats[phase].append(dt)
            except futures.TimeoutError:
                with lock:
                    counts["lost"] += 1
            except Exception:
                with lock:
                    counts["err"] += 1

    def p99(lat):
        lat = sorted(lat)
        return int(lat[min(len(lat) - 1, int(0.99 * (len(lat) - 1) + 0.5))]
                   * 1e6) if lat else 0

    try:
        # warm every starting replica's bucket executors off the clock
        # (and seed the AOT cache the newcomers will hit)
        for _ in range(4):
            fleet.submit(xs[0], deadline_ms=10000.0).result(timeout=30)
        threads = [threading.Thread(target=client, args=(t,), daemon=True)
                   for t in range(clients)]
        for t in threads:
            t.start()
        try:
            time.sleep(phase_s)
            state["phase"] = "scale_up"
            fleet.scale_to(4)
            time.sleep(phase_s)
            state["phase"] = "rollout"
            newcomers = [r for r in fleet.replicas() if r.rid >= 2]
            warm_hits = [r.predictor.warmup_cache_hits for r in newcomers]
            rm = serving.RolloutManager(
                fleet, eval_batch=xs[0], canary_calls=4)
            cand = {f"arg:{k}": mx.nd.array(v)
                    for k, v in _mlp_params().items()}
            with faults.inject("rollout_bad_weights"):
                rollout = rm.rollout_weights(cand)
            fleet.scale_to(2)
        finally:
            state["stop"] = True
            for t in threads:
                t.join(timeout=30)
        recovered = fleet.wait_healthy(timeout=30)
        stats = serving.stats()
    finally:
        fleet.close()
        if tmp is not None:
            os.environ.pop("MXNET_TPU_COMPILE_CACHE", None)
            tmp.cleanup()
    steady_p99, scale_p99 = p99(lats["steady"]), p99(lats["scale_up"])
    ratio = scale_p99 / max(1, steady_p99)
    ok = (counts["err"] == 0 and counts["lost"] == 0
          and ratio <= 3.0
          and len(warm_hits) == 2 and all(h >= 1 for h in warm_hits)
          and rollout["action"] == "rollback"
          and rollout["gate"] == "health"
          and recovered)
    return {
        "clients": clients,
        "steady_p99_us": steady_p99,
        "scale_up_p99_us": scale_p99,
        "scale_up_vs_steady": round(ratio, 2),
        "newcomer_warm_hits": warm_hits,
        "rollout": {"action": rollout["action"],
                    "gate": rollout.get("gate")},
        "counts": counts,
        "scale_ups": stats["fleet_scale_up"],
        "scale_downs": stats["fleet_scale_down"],
        "recovered": recovered,
        "gate_ok": ok,
    }


def bench_decode(mx, serving, seqs=18, new_tokens=12, clients=6):
    """The decode sweep (docs/decode.md): continuous token-level
    batching under churn — ``clients`` threads submit ``seqs`` streams
    with staggered admissions, mixed prompt lengths (several prefill
    buckets) and mid-stream cancellations, against a pool much smaller
    than the offered load, so sequences join/leave the running batch
    constantly. Reports tokens/s, TTFT p50/p99 and inter-token p99 from
    the serving stats, and gates: ZERO retraces after warmup (the
    executable set is frozen — membership churn is runtime operands
    only), every completed stream got its full token budget, and every
    KV page is back in the pool."""
    import numpy as np

    from mxnet_tpu.gluon.model_zoo.transformer import transformer_lm
    from mxnet_tpu.serving.batcher import DecodeBatcher

    serving.reset_stats()
    mx.random.seed(9)
    net = transformer_lm(vocab=64, units=32, num_heads=2, num_layers=2,
                         max_len=64)
    net.initialize()
    net(mx.nd.array(np.zeros((1, 8), np.int32), dtype="int32"))
    pred = serving.DecodePredictor(net, page_size=4, num_pages=24,
                                   max_seqs=3, prefill_buckets=(8, 16),
                                   warmup=True)
    warm_keys = list(pred.compiled_keys)
    bat = DecodeBatcher(pred, ttft_slo_ms=60000)
    rs = np.random.RandomState(5)
    prompts = [[int(t) for t in rs.randint(0, 64, rs.randint(3, 14))]
               for _ in range(seqs)]
    results = {"full": 0, "cancelled": 0, "short": 0, "err": 0}
    lock = threading.Lock()

    def client(tid):
        for i in range(tid, seqs, clients):
            try:
                s = bat.submit(prompts[i], new_tokens)
                if i % 5 == 4:
                    # churn: rip this stream out mid-generation
                    it = s.tokens(timeout=60)
                    next(it)
                    next(it)
                    s.cancel()
                    with lock:
                        results["cancelled"] += 1
                    continue
                toks = s.result(timeout=120)
                with lock:
                    results["full" if len(toks) == new_tokens
                            else "short"] += 1
            except Exception:
                with lock:
                    results["err"] += 1
            time.sleep(0.002 * (tid % 3))  # stagger re-admissions

    threads = [threading.Thread(target=client, args=(t,))
               for t in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    time.sleep(0.05)  # let cancelled streams' evictions settle
    stats = serving.stats()
    retraced = [k for k in pred.compiled_keys if k not in warm_keys]
    pages_held = pred.pool.in_use
    bat.close()
    ok = (not retraced and results["err"] == 0 and results["short"] == 0
          and results["full"] == seqs - results["cancelled"]
          and pages_held == 0 and stats["decode_p99_ttft_us"] > 0)
    return {
        "streams": seqs,
        "clients": clients,
        "tokens_per_s": round(stats["decode_tokens"] / dt, 1),
        "ttft_p50_us": stats["decode_p50_ttft_us"],
        "ttft_p99_us": stats["decode_p99_ttft_us"],
        "itl_p99_us": stats["decode_p99_itl_us"],
        "completed": results["full"],
        "cancelled": results["cancelled"],
        "errors": results["err"],
        "preemptions": stats["decode_preemptions"],
        "backpressure": stats["decode_backpressure"],
        "pages_inuse_peak": stats["decode_pages_inuse_peak"],
        "retraces_after_warmup": len(retraced),
        "pages_held": pages_held,
        "gate_ok": ok,
    }


# the int8-vs-bf16 release gate lives in ONE place (bench_int8.py owns
# the model-level measurement; this sweep enforces the same bar on the
# Predictor path) so a retune can never fork the threshold
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_int8 import GATE_INT8_VS_BF16  # noqa: E402


def _int8_sym_params(mx, channels=16, hidden=10, hw=16):
    """A quantizable convnet (conv/relu/pool/fc — the int8-grid op set)
    with deterministic params; big enough that the int8 matmul path
    dominates at batch 128."""
    import numpy as np

    s = mx.sym.Convolution(mx.sym.var("data"), kernel=(3, 3), pad=(1, 1),
                           num_filter=channels, name="qc1")
    s = mx.sym.Activation(s, act_type="relu", name="qr1")
    s = mx.sym.Pooling(s, kernel=(2, 2), stride=(2, 2), pool_type="max",
                       name="qp1")
    s = mx.sym.FullyConnected(s, num_hidden=hidden, name="qfc1")
    rng = np.random.RandomState(0)
    feat = channels * (hw // 2) * (hw // 2)
    params = {
        "qc1_weight": (rng.randn(channels, 3, 3, 3) * 0.2)
        .astype(np.float32),
        "qc1_bias": np.zeros(channels, np.float32),
        "qfc1_weight": (rng.randn(hidden, feat) * 0.1).astype(np.float32),
        "qfc1_bias": np.zeros(hidden, np.float32),
    }
    return s, params, (3, hw, hw)


def _int8_variant_factories(mx, serving, batch, hw=16):
    """(bf16 factory, int8 factory) over the SAME model — module-level
    params so restarts rebuild identically (AOT-cache friendly)."""
    import numpy as np

    s, params, tail = _int8_sym_params(mx, hw=hw)
    calib_x = np.random.RandomState(1).rand(64, *tail).astype(np.float32)

    def bf16_factory():
        import jax.numpy as jnp

        p16 = {k: v.astype(jnp.bfloat16) for k, v in params.items()}
        return serving.Predictor(s, p16, input_shapes={"data": tail},
                                 batch_sizes=(batch,), dtype=jnp.bfloat16)

    def int8_factory():
        calib = mx.io.NDArrayIter(data=calib_x, batch_size=32)
        return serving.Predictor(s, dict(params),
                                 input_shapes={"data": tail},
                                 batch_sizes=(batch,), quantize="int8",
                                 calib_data=calib, calib_mode="entropy")

    return bf16_factory, int8_factory, tail


def bench_int8(mx, serving, batch=128, iters=30):
    """int8-vs-bf16 Predictor throughput at batch 128 plus the
    dtype-variant fleet routing proof. Returns the result dict."""
    import numpy as np

    bf16_factory, int8_factory, tail = _int8_variant_factories(
        mx, serving, batch)
    x = np.random.RandomState(2).rand(batch, *tail).astype(np.float32)

    def run(pred):
        pred.predict(x)  # warm / compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = pred.predict(x)
        np.asarray(out[0].asnumpy())  # force the chain to the host
        return iters * batch / (time.perf_counter() - t0)

    p16 = bf16_factory()
    p8 = int8_factory()
    bf16_sps = run(p16)
    int8_sps = run(p8)
    ratio = int8_sps / bf16_sps

    # dtype-variant fleet: one model, two variants, routed explicitly
    fleet = serving.Fleet({"convnet": {"bf16": bf16_factory,
                                       "int8": int8_factory}},
                          replicas=1, probe_interval_ms=200,
                          server_kw={"batch_timeout_ms": 1.0})
    try:
        r16 = fleet.submit(x[:1], deadline_ms=30000, model="convnet",
                           variant="bf16").result(timeout=60)
        r8 = fleet.submit(x[:1], deadline_ms=30000, model="convnet",
                          variant="int8").result(timeout=60)
        variants = fleet.variants("convnet")
        scale = float(np.abs(np.asarray(r16[0],
                                        np.float32)).max()) or 1.0
        variant_close = bool(np.abs(
            np.asarray(r16[0], np.float32)
            - np.asarray(r8[0], np.float32)).max() < 0.25 * scale)
    finally:
        fleet.close()
    gate_ok = ratio >= GATE_INT8_VS_BF16
    return {
        "batch": batch,
        "bf16_samples_per_s": round(bf16_sps, 1),
        "int8_samples_per_s": round(int8_sps, 1),
        "int8_vs_bf16": round(ratio, 3),
        "gate_int8_vs_bf16": GATE_INT8_VS_BF16,
        "gate": "ok" if gate_ok else "FAIL",
        "fleet_variants": variants,
        "variant_outputs_close": variant_close,
        "int8_warmup_cache_hits": p8.warmup_cache_hits,
        "gate_ok": gate_ok,
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=1000)
    ap.add_argument("--skip-fleet", action="store_true")
    ap.add_argument("--skip-int8", action="store_true")
    ap.add_argument("--operate", action="store_true",
                    help="run the operator sweep (autoscale under load + "
                         "canaried rollout) and gate the exit code on it")
    ap.add_argument("--decode", action="store_true",
                    help="run the decode sweep (paged KV continuous "
                         "batching under churn: tokens/s, TTFT, "
                         "inter-token p99, zero-retrace gate) and gate "
                         "the exit code on it")
    args = ap.parse_args(argv)

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.observability import perf

    dev = perf.require_chip()
    print(f"device: {dev}", file=sys.stderr)

    pred = _build_predictor(mx, serving, buckets=(1, 16))
    print(f"warmup: {pred.warmup_ms:.0f} ms for buckets "
          f"{list(pred.buckets)}", file=sys.stderr)

    single = bench_predict(pred, 1, args.iters)
    batched = bench_predict(pred, 16, args.iters)
    speedup = batched / single
    print(f"predict: single {single:.0f} samples/s | batch16 "
          f"{batched:.0f} samples/s ({speedup:.2f}x)", file=sys.stderr)

    sweeps = {}
    for clients in (1, 8, 32):
        r = bench_server(mx, serving, pred, clients,
                         per_client=max(20, args.iters // (4 * clients)))
        sweeps[clients] = r
        print(f"server c={clients:<3}: {r['rps']:.0f} req/s, "
              f"p50 {r['p50_us']} us, p99 {r['p99_us']} us, "
              f"pad waste {r['pad_waste_pct']:.1f}%, "
              f"{r['batches']} batches / {r['requests']} reqs",
              file=sys.stderr)

    over = bench_server(mx, serving, pred, 16, per_client=20,
                        timeout_ms=20.0, max_queue_depth=4,
                        shed_policy="reject_new")
    print(f"overload (depth 4): shed {over['shed']} of "
          f"{over['offered']} offered", file=sys.stderr)

    int8 = None
    int8_ok = True
    if not args.skip_int8:
        int8 = bench_int8(mx, serving)
        int8_ok = int8.pop("gate_ok") and int8["variant_outputs_close"]
        print(f"int8 (batch {int8['batch']}): bf16 "
              f"{int8['bf16_samples_per_s']:.0f} vs int8 "
              f"{int8['int8_samples_per_s']:.0f} samples/s "
              f"({int8['int8_vs_bf16']:.2f}x, gate "
              f"{int8['gate_int8_vs_bf16']}x -> {int8['gate']}), "
              f"variants {int8['fleet_variants']}", file=sys.stderr)

    fleet = None
    fleet_ok = True
    if not args.skip_fleet:
        fleet = bench_fleet(mx, serving)
        ratio = (fleet["fleet_p99_killed_us"]
                 / max(1, fleet["fleet_p99_healthy_us"]))
        fleet_ok = (fleet["lost"] == 0 and fleet["recovered"]
                    and fleet["restarts"] >= 1 and ratio <= 3.0)
        print(f"fleet ({fleet['replicas']} replicas, {fleet['clients']} "
              f"clients): p99 healthy {fleet['fleet_p99_healthy_us']} us, "
              f"one-killed {fleet['fleet_p99_killed_us']} us "
              f"({ratio:.2f}x, gate 3x), lost {fleet['lost']}, "
              f"restarts {fleet['restarts']}, retries {fleet['retries']}, "
              f"recovered {fleet['recovered']}", file=sys.stderr)

    operate = None
    operate_ok = True
    if args.operate:
        operate = bench_operate(mx, serving)
        operate_ok = operate["gate_ok"]
        print(f"operate ({operate['clients']} clients): scale-up p99 "
              f"{operate['scale_up_p99_us']} us vs steady "
              f"{operate['steady_p99_us']} us "
              f"({operate['scale_up_vs_steady']}x, gate 3x), newcomer "
              f"warm hits {operate['newcomer_warm_hits']}, bad-weights "
              f"rollout -> {operate['rollout']['action']} "
              f"(gate={operate['rollout']['gate']}), "
              f"err {operate['counts']['err']}, lost "
              f"{operate['counts']['lost']} -> "
              f"{'ok' if operate_ok else 'FAIL'}", file=sys.stderr)

    decode = None
    decode_ok = True
    if args.decode:
        decode = bench_decode(mx, serving)
        decode_ok = decode["gate_ok"]
        print(f"decode ({decode['streams']} streams, {decode['clients']} "
              f"clients, {decode['cancelled']} cancelled): "
              f"{decode['tokens_per_s']:.0f} tokens/s, TTFT p50 "
              f"{decode['ttft_p50_us']} us / p99 {decode['ttft_p99_us']} "
              f"us, inter-token p99 {decode['itl_p99_us']} us, "
              f"preemptions {decode['preemptions']}, retraces after "
              f"warmup {decode['retraces_after_warmup']}, pages held "
              f"{decode['pages_held']} -> "
              f"{'ok' if decode_ok else 'FAIL'}", file=sys.stderr)

    print(json.dumps({
        "metric": "serving_samples_per_s_b16",
        "value": round(batched, 1),
        "unit": "samples/s",
        "vs_baseline": round(speedup, 2),  # batch16 vs single-request
        "device": dev,
        "extra": {
            "single_samples_per_s": round(single, 1),
            "batch16_vs_single": round(speedup, 2),
            "warmup_ms": round(pred.warmup_ms, 1),
            "server_rps_c8": round(sweeps[8]["rps"], 1),
            "server_rps_c32": round(sweeps[32]["rps"], 1),
            "p50_us_c8": sweeps[8]["p50_us"],
            "p99_us_c8": sweeps[8]["p99_us"],
            "pad_waste_pct_c8": round(sweeps[8]["pad_waste_pct"], 1),
            "overload_shed": over["shed"],
            "fleet": fleet,
            "fleet_gate_ok": fleet_ok,
            "int8": int8,
            "int8_gate_ok": int8_ok,
            "operate": operate,
            "operate_gate_ok": operate_ok,
            "decode": decode,
            "decode_gate_ok": decode_ok,
        },
    }))
    return 0 if (fleet_ok and int8_ok and operate_ok and decode_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
