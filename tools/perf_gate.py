"""Continuous perf-regression gate over the observability perf ledger.

The repo's perf story used to live in ad-hoc ``BENCH_*.json`` files with
no machine-checked trajectory: nothing stopped the roofline win from
silently eroding one "harmless" change at a time. This gate closes that
loop (docs/observability.md "Performance attribution", PERF.md round 6):

1. **collect** — run a small deterministic workload (one captured gluon
   training step + one warmed serving Predictor bucket) and gather, per
   perf-ledger key (``<label>@<fingerprint16>``, the AOT-fingerprint
   identity), the ledger's ``compile_ms`` / ``peak_hbm_bytes`` plus a
   best-of-N measured ``step_ms`` wall time. Streaming ingestion rides
   along under the fixed ``stream_ingest@host_pipeline`` key (per-batch
   host pipeline wall time over a synthetic dataset — no compiled
   executable, so ``step_ms`` only), so an ingestion regression fails
   the gate like a compute regression (docs/data.md).
2. **compare** — against the committed per-backend baseline store
   ``tools/perf_baseline.json`` (schema-versioned). A key missing from
   the baseline means the program's *identity* changed (shape / dtype /
   code / calibration — the same invalidation rules as the AOT cache),
   so it **re-baselines instead of false-failing**: reported as
   ``rebaselined``, and the run only fails when EVERY baseline key for
   this backend went stale (a fingerprint-schema change must never
   silently orphan the whole store — run ``--update``). A key present
   in both fails the gate when any gated metric regressed beyond its
   tolerance, and each regression records a ``perf`` flight-recorder
   event (``event=regression``).
3. **drill** — the ``perf_regression`` fault kind
   (``resilience.faults.maybe_perf_regression``, drilled as
   tools/chaos_run.py's 20th kind) inflates the measured numbers
   between collect and compare, proving the gate actually fails — exit
   non-zero, flight trail present — when an executable gets slower or
   fatter.

Prints ONE JSON line (the repo-wide tool contract)::

    {"metric": "perf_gate_regressions", "value": <n>, "unit":
     "regressions", "extra": {"backend": ..., "checked": ...,
     "rebaselined": [...], "per_regression": [...]}}

Exit code is non-zero on any regression, an unreadable/invalid
baseline, or a fully-orphaned baseline backend section. ``--update``
(re)writes this backend's section from the current measurements.

Run: JAX_PLATFORMS=cpu python tools/perf_gate.py --interpret [--update]
     [--baseline P]

The two flash keys time the compiled Pallas kernel, which needs the
chip. ``--interpret`` (the CPU CI gate) times the Pallas interpreter
instead, and says so in the JSON line; without it a backend that cannot
compile the kernel fails the run rather than switching on its own.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASELINE_SCHEMA_VERSION = 1
# bumped whenever the ledger-key derivation (capture.fingerprint schema,
# perf.ledger_key format) changes shape: validate_baseline rejects a
# store written under another key schema instead of letting every
# lookup quietly miss forever
KEY_SCHEMA_VERSION = 1

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "perf_baseline.json")

# THE metric registry of the gate: what the baseline stores per key and
# what compare() checks, with per-metric regression tolerances (%).
# Wall-time tolerances are deliberately loose — the gate catches
# erosion, interleaved best-of-N absorbs scheduler noise — while the
# memory bound is tight: peak HBM is deterministic per program.
# graftlint RD005 keeps every name documented under docs/.
GATED_METRICS = ("step_ms", "compile_ms", "peak_hbm_bytes")
TOLERANCE_PCT = {"step_ms": 50.0, "compile_ms": 150.0,
                 "peak_hbm_bytes": 10.0}


def _loss_fn(out, y):
    # module-level on purpose: the loss bytecode is part of the capture
    # fingerprint, so a stable definition keeps the ledger key (and the
    # committed baseline) stable across runs
    return ((out - y) ** 2).sum()


def collect(steps=30, trials=3, rounds=2, interpret=False):
    """Run the gate workload ``rounds`` times and return the per-metric
    **minimum** ``{key: {metric: value}}`` per perf-ledger key — wall
    compile time is one long uninterruptible section, so min-of-rounds
    (not a single sample) is what absorbs a scheduler burst landing on
    exactly one compile. Identity is deterministic across rounds and
    processes: fixed seeds, fixed-prefix block names (a gensym'd prefix
    would re-key every run), AOT disk cache disabled so ``compile_ms``
    measures a real compile."""
    measured = None
    for _ in range(max(1, rounds)):
        cur = _collect_once(steps, trials, interpret)
        if measured is None:
            measured = cur
            continue
        for key, rec in cur.items():
            prev = measured.setdefault(key, rec)
            for m, v in rec.items():
                if isinstance(v, (int, float)) and prev.get(m) is not None:
                    prev[m] = min(prev[m], v)
                elif prev.get(m) is None:
                    prev[m] = v
    return measured


def _collect_once(steps, trials, interpret=False):
    saved_cache = os.environ.pop("MXNET_TPU_COMPILE_CACHE", None)
    try:
        import numpy as np

        import mxnet_tpu as mx
        from mxnet_tpu import capture, serving
        from mxnet_tpu.observability import perf

        perf.clear()
        mx.random.seed(11)
        net = mx.gluon.nn.Dense(8, in_units=16, prefix="perfgate_net_")
        net.initialize()
        trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.1, "momentum": 0.9})
        step = capture.capture(trainer, net=net, loss_fn=_loss_fn,
                               label="trainer_step")
        x = mx.nd.array(np.arange(256, dtype=np.float32).reshape(16, 16)
                        / 256.0)
        y = mx.nd.ones((16, 8))
        step(x, y, batch_size=16)  # compile -> ledger entry
        step_ms = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _k in range(steps):
                step(x, y, batch_size=16)
            mx.nd.waitall()
            step_ms = min(step_ms, (time.perf_counter() - t0) / steps * 1e3)

        mx.random.seed(11)
        srv_net = mx.gluon.nn.Dense(8, in_units=16,
                                    prefix="perfgate_srv_")
        srv_net.initialize()
        pred = serving.Predictor.from_block(
            srv_net, input_shapes={"data": (16,)}, batch_sizes=(8,))
        xb = np.ones((8, 16), np.float32)
        pred.predict(xb)
        serve_ms = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _k in range(steps):
                outs = pred.predict(xb)
            outs[0].wait_to_read()
            serve_ms = min(serve_ms, (time.perf_counter() - t0) / steps * 1e3)

        # numerics telemetry rides a FIXED key (like stream_ingest): the
        # tapped program's structural fingerprint folds the row plan, so
        # a ledger-derived key would re-baseline on any tap-plan tweak
        # instead of gating the telemetry cost's erosion. step_ms is the
        # amortized per-step wall at the production sampling interval 10
        # (ISSUE 14's <=2%-overhead surface; a committed TPU baseline is
        # the evidence for the production claim).
        mx.random.seed(11)
        tap_net = mx.gluon.nn.Dense(8, in_units=16,
                                    prefix="perfgate_tapnet_")
        tap_net.initialize()
        tap_trainer = mx.gluon.Trainer(tap_net.collect_params(), "sgd",
                                       {"learning_rate": 0.1,
                                        "momentum": 0.9})
        from mxnet_tpu.observability import numerics as _numerics

        tap_step = capture.capture(
            tap_trainer, net=tap_net, loss_fn=_loss_fn,
            numerics=_numerics.NumericsTap(interval=10, policy="record"),
            label="numerics_trainer_step")
        tap_step(x, y, batch_size=16)
        tap_ms = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _k in range(steps):
                tap_step(x, y, batch_size=16)
            mx.nd.waitall()
            tap_ms = min(tap_ms, (time.perf_counter() - t0) / steps * 1e3)

        measured = {}
        for key, e in perf.ledger().items():
            if e["label"].startswith("numerics_trainer_step"):
                continue  # carried by the fixed numerics_tap key below
            if e["label"] == "sharded_step":
                # the transformer workload below; carried by the fixed
                # transformer_step@tuned key — its ledger fingerprint
                # folds the kernel schedule token, so a tuned-table edit
                # would orphan a ledger-derived key instead of gating
                # the step's wall-time trajectory across table changes
                continue
            rec = {"compile_ms": e["compile_ms"],
                   "peak_hbm_bytes": e["peak_hbm_bytes"]}
            if e["label"] == "trainer_step":
                rec["step_ms"] = step_ms
            elif e["label"].startswith("serving_bucket"):
                rec["step_ms"] = serve_ms
            measured[key] = rec
        measured["numerics_tap@capture"] = {"step_ms": tap_ms}
        measured["stream_ingest@host_pipeline"] = {
            "step_ms": _measure_stream_ingest(steps, trials)}
        # the tuned Pallas flash kernels ride fixed keys too
        # (docs/autotune.md): the schedule table steers their blocks at
        # trace time, so these keys deliberately do NOT re-key with the
        # table — the gate watches the kernels' wall-time trajectory
        # ACROSS schedule changes (a tuned table that slows the kernel
        # fails here like any compute regression)
        measured["flash_attn_fwd@tuned"] = {
            "step_ms": _measure_flash(trials, bwd=False,
                                      interpret=interpret)}
        measured["flash_attn_bwd@tuned"] = {
            "step_ms": _measure_flash(trials, bwd=True,
                                      interpret=interpret)}
        # the dp×fsdp×tp pretraining workload (bench.py
        # --model=transformer) gates its per-step wall under a fixed key
        # for the same reason as the flash kernels: attention resolves
        # through the schedule table at trace time (impl='auto'), so the
        # key must survive table edits
        measured["transformer_step@tuned"] = {
            "step_ms": _measure_transformer_step(trials)}
        # the decode serving path gates both phases under fixed keys
        # (docs/decode.md): prefill cost sets TTFT, the fixed-shape step
        # sets inter-token latency, and both resolve their paged
        # attention through the schedule table at trace time — same
        # survive-table-edits rationale as the flash kernels above
        prefill_ms, decode_ms = _measure_decode(trials)
        measured["prefill@tuned"] = {"step_ms": prefill_ms}
        measured["decode_step@tuned"] = {"step_ms": decode_ms}
        return measured
    finally:
        if saved_cache is not None:
            os.environ["MXNET_TPU_COMPILE_CACHE"] = saved_cache


def _measure_stream_ingest(steps, trials):
    """Best-of-N per-batch host-pipeline wall time (index range read +
    raw decode + batch assembly, io/stream.py) over a fixed synthetic
    dataset. The key is the fixed string ``stream_ingest@host_pipeline``
    — there is no compiled executable behind it, so the entry gates
    ``step_ms`` only."""
    import shutil
    import tempfile

    import numpy as np

    from mxnet_tpu import recordio
    from mxnet_tpu.io import stream as dstream

    sdir = tempfile.mkdtemp(prefix="perfgate_stream_")
    try:
        prefix = os.path.join(sdir, "synth")
        rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec",
                                         "w")
        rs = np.random.RandomState(11)
        for i in range(64):
            rec.write_idx(i, recordio.pack(
                recordio.IRHeader(0, float(i % 8), i, 0),
                rs.rand(16).astype(np.float32).tobytes()))
        rec.close()
        stream_ms = 1e9
        for _ in range(trials):
            it = dstream.StreamBatchIter(
                prefix + ".rec", batch_size=16,
                decode=dstream.raw_decoder((16,)), shuffle=True, seed=3,
                decode_threads=1)
            t0 = time.perf_counter()
            for _k in range(steps):
                next(it)
            stream_ms = min(stream_ms,
                            (time.perf_counter() - t0) / steps * 1e3)
        return stream_ms
    finally:
        shutil.rmtree(sdir, ignore_errors=True)


def _measure_flash(trials, bwd, steps=5, interpret=False):
    """Best-of-N wall ms for the schedule-resolved flash-attention
    forward (or forward+backward) at a fixed shape: the compiled kernel,
    or the Pallas interpreter when the caller asks (``--interpret``).
    Blocks resolve through the schedule table exactly as production
    callers' do."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu.ops import pallas_kernels as pk

    rs = np.random.RandomState(7)
    q, k, v = [jnp.asarray(rs.randn(1, 2, 256, 32).astype(np.float32) * 0.3)
               for _ in range(3)]
    if bwd:
        def loss(q, k, v):
            out = pk.flash_attention_with_grad(q, k, v, causal=True,
                                               interpret=interpret)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        fn = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    else:
        fn = jax.jit(lambda q, k, v: pk.flash_attention(
            q, k, v, causal=True, interpret=interpret))
    jax.block_until_ready(fn(q, k, v))  # warmup absorbs trace+compile
    best = 1e9
    for _ in range(trials):
        t0 = time.perf_counter()
        out = None
        for _k in range(steps):
            out = fn(q, k, v)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return best


def _measure_transformer_step(trials, steps=3):
    """Best-of-N wall ms for one sharded model-zoo transformer training
    step (docs/parallel.md): bf16 AMP, attention resolved through the
    schedule registry (impl='auto' — dense off-chip, tuned flash on a
    TPU host), the whole step one donated captured executable. The gate
    runs on whatever devices exist, so this uses a dp=1 mesh — the
    wall-time *trajectory* is what's gated, not the parallel layout
    (bench.py --model=transformer owns the dp×fsdp×tp MFU number)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import transformer as tzoo

    mx.random.seed(11)
    net = tzoo.transformer_lm(vocab=64, units=32, num_heads=2,
                              num_layers=2, max_len=64, impl="auto",
                              prefix="perfgate_tlm_")
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((2, 8)))
    mesh = parallel.create_mesh({"dp": 1}, jax.devices()[:1])
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        "sgd", {"learning_rate": 0.1}, mesh=mesh, dtype="bfloat16")
    rs = np.random.RandomState(7)
    x = (rs.rand(4, 16) * 64).astype(np.int32)
    y = (rs.rand(4, 16) * 64).astype(np.int32)
    xd = jax.device_put(x, trainer.batch_sharding)
    yd = jax.device_put(y, trainer.batch_sharding)
    trainer.step(xd, yd).block_until_ready()  # warmup absorbs compile
    best = 1e9
    for _ in range(trials):
        t0 = time.perf_counter()
        loss = None
        for _k in range(steps):
            loss = trainer.step(xd, yd)
        loss.block_until_ready()
        best = min(best, (time.perf_counter() - t0) / steps * 1e3)
    return best


def _measure_decode(trials, steps=8):
    """Best-of-N wall ms for the two decode-serving executables at fixed
    shapes: one bucketed prefill (the TTFT cost) and ONE fixed-shape
    decode step over the full slot array (the inter-token cost). Both
    replay warmed executables against real pool pages — exactly the
    per-call work `serving.DecodeBatcher`'s engine loop pays — so
    erosion here is erosion of TTFT / inter-token latency."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import transformer as tzoo

    mx.random.seed(11)
    net = tzoo.transformer_lm(vocab=64, units=32, num_heads=2,
                              num_layers=2, max_len=64,
                              prefix="perfgate_dlm_")
    net.initialize(mx.initializer.Xavier())
    net(mx.nd.zeros((1, 8), dtype="int32"))
    pred = serving.DecodePredictor(net, page_size=4, num_pages=16,
                                   max_seqs=2, prefill_buckets=(8,),
                                   warmup=True)
    pages = pred.pool.alloc(4)
    try:
        row = np.zeros((pred.max_pages,), np.int32)
        row[:len(pages)] = pages
        prompt = np.arange(8, dtype=np.int32) % 64
        prefill_ms = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _k in range(steps):
                pred.prefill(prompt, row)
            prefill_ms = min(prefill_ms,
                             (time.perf_counter() - t0) / steps * 1e3)
        table = np.zeros((pred.max_seqs, pred.max_pages), np.int32)
        table[0] = row
        toks = np.zeros((pred.max_seqs,), np.int32)
        positions = np.full((pred.max_seqs,), 8, np.int32)
        active = np.zeros((pred.max_seqs,), np.int32)
        active[0] = 1
        decode_ms = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _k in range(steps):
                pred.step(toks, positions, active, table)
            decode_ms = min(decode_ms,
                            (time.perf_counter() - t0) / steps * 1e3)
    finally:
        pred.pool.free(pages)
    return prefill_ms, decode_ms


def compare(current, baseline_entries, tolerance_pct=None,
            record_flight=True):
    """Compare measured ``{key: {metric: value}}`` against one backend's
    baseline entries. Returns ``(regressions, rebaselined)`` where each
    regression is ``{key, metric, baseline, current, pct, tolerance_pct}``
    (one ``perf`` flight event each) and ``rebaselined`` lists keys with
    no baseline identity (changed fingerprint — new program, not a
    regression). The ``perf_regression`` chaos hook sits between the
    caller's measurements and this comparison. ``record_flight=False``
    suppresses the flight events — the gate's *first* measure passes it
    so a scheduler burst that the one-shot re-measure then clears never
    plants phantom ``perf:regression`` events in the always-on
    recorder (and so in later crash reports)."""
    from mxnet_tpu.observability import flight
    from mxnet_tpu.resilience import faults

    current = faults.maybe_perf_regression(current)
    tol = dict(TOLERANCE_PCT)
    tol.update(tolerance_pct or {})
    regressions, rebaselined = [], []
    for key, metrics in sorted(current.items()):
        base = baseline_entries.get(key)
        if base is None:
            rebaselined.append(key)
            continue
        for m in GATED_METRICS:
            b, c = base.get(m), metrics.get(m)
            if b is None or c is None or b <= 0:
                continue
            pct = (c - b) / b * 100.0
            if pct > tol.get(m, 0.0):
                reg = {"key": key, "metric": m, "baseline": b,
                       "current": c, "pct": round(pct, 1),
                       "tolerance_pct": tol.get(m, 0.0)}
                regressions.append(reg)
                if record_flight:
                    flight.record("perf", event="regression", key=key,
                                  metric=m, baseline=b, current=c,
                                  pct=reg["pct"])
    return regressions, rebaselined


def validate_baseline(data):
    """Structural validation of a perf-baseline store; returns a list of
    problem strings (empty = valid). Checked: schema version, key-schema
    version (a fingerprint-schema change must announce itself, never
    silently orphan every key), per-backend entry shape, and that every
    stored metric is one the gate actually reads (a stale metric name
    would be dead weight nobody compares)."""
    problems = []
    if not isinstance(data, dict):
        return ["baseline is not a JSON object"]
    if data.get("schema_version") != BASELINE_SCHEMA_VERSION:
        problems.append(
            f"schema_version {data.get('schema_version')!r} != supported "
            f"{BASELINE_SCHEMA_VERSION}")
    if data.get("key_schema") != KEY_SCHEMA_VERSION:
        problems.append(
            f"key_schema {data.get('key_schema')!r} != current "
            f"{KEY_SCHEMA_VERSION} (fingerprint-key derivation changed: "
            "every stored key is stale — regenerate with "
            "perf_gate.py --update)")
    backends = data.get("backends")
    if not isinstance(backends, dict) or not backends:
        problems.append("no per-backend sections under 'backends'")
        return problems
    for backend, section in sorted(backends.items()):
        entries = (section or {}).get("entries")
        if not isinstance(entries, dict) or not entries:
            problems.append(f"backend {backend!r} has no entries")
            continue
        for key, rec in sorted(entries.items()):
            if "@" not in key:
                problems.append(
                    f"{backend}:{key!r} is not a <label>@<fingerprint> "
                    "ledger key (stale key format)")
                continue
            if not isinstance(rec, dict) or not rec:
                problems.append(f"{backend}:{key} entry is empty")
                continue
            unknown = sorted(set(rec) - set(GATED_METRICS))
            if unknown:
                problems.append(
                    f"{backend}:{key} stores unknown metric(s) {unknown} "
                    f"(gated metrics: {list(GATED_METRICS)})")
            for m, v in sorted(rec.items()):
                if m in GATED_METRICS and (
                        not isinstance(v, (int, float))
                        or isinstance(v, bool) or v < 0):
                    problems.append(
                        f"{backend}:{key}.{m} is not a non-negative "
                        f"number: {v!r}")
    return problems


def load_baseline(path):
    """-> (data, problems). Missing file is a problem (the gate without
    a baseline gates nothing); unreadable/invalid likewise."""
    if not os.path.isfile(path):
        return None, [f"baseline {path} does not exist "
                      "(run perf_gate.py --update to create it)"]
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return None, [f"cannot read baseline {path}: {e}"]
    return data, validate_baseline(data)


def update_baseline(path, backend, measured):
    """Write/merge this backend's section from ``measured``; other
    backends' sections are preserved (one store serves the fleet)."""
    data = None
    if os.path.isfile(path):
        try:
            with open(path, encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = None
    if not isinstance(data, dict) \
            or data.get("schema_version") != BASELINE_SCHEMA_VERSION \
            or data.get("key_schema") != KEY_SCHEMA_VERSION:
        data = {"schema_version": BASELINE_SCHEMA_VERSION,
                "key_schema": KEY_SCHEMA_VERSION, "backends": {}}
    entries = {k: {m: (round(v, 4) if isinstance(v, float) else v)
                   for m, v in rec.items() if v is not None}
               for k, rec in sorted(measured.items())}
    data.setdefault("backends", {})[backend] = {
        "created": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "entries": entries,
    }
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
    return data


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--update", action="store_true",
                    help="write this backend's baseline section from "
                         "the current measurements instead of gating")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--interpret", action="store_true",
                    help="time the flash keys in the Pallas interpreter "
                         "(the CPU CI gate) instead of the compiled kernel")
    args = ap.parse_args(argv)

    import jax

    backend = jax.default_backend()
    measured = collect(args.steps, args.trials, interpret=args.interpret)
    if args.update:
        update_baseline(args.baseline, backend, measured)
        print(f"baseline[{backend}] <- {len(measured)} entr(ies) "
              f"-> {args.baseline}", file=sys.stderr)
        print(json.dumps({"metric": "perf_gate_regressions", "value": 0,
                          "unit": "regressions",
                          "extra": {"backend": backend, "updated": True,
                                    "keys": sorted(measured)}}))
        return 0

    data, problems = load_baseline(args.baseline)
    if problems:
        for p in problems:
            print(f"perf_gate: {p}", file=sys.stderr)
        print(json.dumps({"metric": "perf_gate_regressions", "value": 0,
                          "unit": "regressions",
                          "extra": {"backend": backend,
                                    "baseline_problems": problems}}))
        return 1

    section = data["backends"].get(backend)
    if section is None:
        # a backend with no committed numbers yet has nothing to erode;
        # TPU hosts bootstrap with --update, CPU CI keeps gating
        print(f"perf_gate: no baseline for backend {backend!r} "
              "(nothing gated; run --update to start)", file=sys.stderr)
        print(json.dumps({"metric": "perf_gate_regressions", "value": 0,
                          "unit": "regressions",
                          "extra": {"backend": backend,
                                    "ungated_backend": True}}))
        return 0

    # first measure records NO flight events: a regression the one-shot
    # re-measure clears was scheduler noise, and phantom perf:regression
    # events must never pollute crash-report forensics
    regressions, rebaselined = compare(measured, section["entries"],
                                       record_flight=False)
    if regressions:
        # one re-measure before declaring a regression: min-of-rounds
        # absorbs steady background load, but not a burst covering a
        # whole collect() — the obs_bench / chaos-harness methodology.
        # (The perf_regression drill calls compare() directly, so the
        # retry can never eat an injected fault's one fire window.)
        print(f"perf_gate: {len(regressions)} regression(s) on first "
              "measure; re-measuring once", file=sys.stderr)
        measured = collect(args.steps, args.trials,
                           interpret=args.interpret)
        regressions, rebaselined = compare(measured, section["entries"])
    checked = [k for k in measured if k in section["entries"]]
    orphaned = bool(section["entries"]) and not checked
    for r in regressions:
        print(f"perf_gate: REGRESSION {r['key']} {r['metric']} "
              f"{r['baseline']:.4g} -> {r['current']:.4g} "
              f"(+{r['pct']}%, tolerance {r['tolerance_pct']}%)",
              file=sys.stderr)
    for k in rebaselined:
        print(f"perf_gate: {k} has no baseline identity (fingerprint "
              "changed) — re-baseline with --update", file=sys.stderr)
    if orphaned:
        print("perf_gate: EVERY baseline key for this backend is stale — "
              "the program identities all changed; the store is orphaned "
              "and gates nothing. Run perf_gate.py --update.",
              file=sys.stderr)

    print(json.dumps({
        "metric": "perf_gate_regressions",
        "value": len(regressions),
        "unit": "regressions",
        "extra": {
            "backend": backend,
            "flash_interpret": args.interpret,
            "checked": sorted(checked),
            "rebaselined": sorted(rebaselined),
            "orphaned": orphaned,
            "per_regression": regressions,
            "tolerance_pct": TOLERANCE_PCT,
        },
    }))
    return 0 if not regressions and not orphaned else 1


if __name__ == "__main__":
    sys.exit(main())
