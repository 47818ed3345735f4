"""Capture benchmark: captured step vs eager-bulk, and AOT cold-start.

Two measurements, two gates (docs/capture.md):

1. **Steady state** — one whole-program captured trainer step vs the
   eager fwd/bwd + bulked-update hot loop on the same net/optimizer.
   Gate: captured per-step wall time <= the eager-bulk time (the
   captured program replaces dozens of dispatches with one).
2. **Cold start** — a fresh process builds + first-steps the same
   captured program cold (no program artifact, jax's persistent compile
   cache off) vs warm (artifact present, executable in the one compile
   cache the package configures — never a directory of this tool's).
   Gate: warm >= 5x faster (the artifact skips tracing/lowering, the
   persistent cache skips compilation).

A chip belongs to one process, so the cold-start children run FIRST,
while this parent has not touched jax; the parent measures steady state
afterwards.

Prints ONE JSON line (house convention, tools/dispatch_bench.py):

    {"metric": "capture_step_speedup", "value": <bulk/captured>,
     "unit": "x", "extra": {...}}

Exit code is non-zero when either gate fails.

Run: python tools/capture_bench.py [--steps N]   (the JSON line names the
device; under JAX_PLATFORMS=cpu it is the capture-layer CI gate)
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

LAYERS = 32     # deep enough that XLA compile dominates the cold start
WIDTH = 256
BATCH = 16


def _build(mx, seed=11):
    import numpy as np

    mx.random.seed(seed)
    net = mx.gluon.nn.HybridSequential(prefix="capbench_")
    with net.name_scope():
        for _ in range(LAYERS):
            net.add(mx.gluon.nn.Dense(WIDTH, activation="relu"))
        net.add(mx.gluon.nn.Dense(8))
    net.initialize()
    x = mx.nd.array(np.random.RandomState(0)
                    .rand(BATCH, WIDTH).astype(np.float32))
    y = mx.nd.ones((BATCH, 8))
    net(x)  # materialize params
    trainer = mx.gluon.Trainer(net.collect_params(), "adam",
                               {"learning_rate": 1e-3})
    return net, trainer, x, y


def _loss_fn(out, y):
    return ((out - y) ** 2).sum()


# ------------------------------------------------------------- steady state

def steady_state(steps, trials):
    import mxnet_tpu as mx
    from mxnet_tpu import capture

    net, trainer, x, y = _build(mx)

    def eager_bulk_step():
        with mx.autograd.record():
            loss = _loss_fn(net(x), y)
        loss.backward()
        trainer.step(BATCH)
        return loss

    os.environ["MXNET_TPU_BULK_OPT_UPDATES"] = "16"
    try:
        for _ in range(3):
            eager_bulk_step()         # warmup/compile
        mx.nd.waitall()
        bulk = 1e9
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(steps):
                loss = eager_bulk_step()
            loss.wait_to_read()
            bulk = min(bulk, (time.perf_counter() - t0) / steps)
    finally:
        del os.environ["MXNET_TPU_BULK_OPT_UPDATES"]

    net_c, trainer_c, xc, yc = _build(mx)
    step = capture.capture(trainer_c, net=net_c, loss_fn=_loss_fn)
    for _ in range(3):
        step(xc, yc, batch_size=BATCH)  # warmup/compile
    mx.nd.waitall()
    captured = 1e9
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(xc, yc, batch_size=BATCH)
        loss.wait_to_read()
        captured = min(captured, (time.perf_counter() - t0) / steps)
    return bulk, captured


# --------------------------------------------------------------- cold start

def _child_coldstart(cache_dir):
    """Child mode: build + first-step one captured program. Reports two
    times: ``first_step_s`` (the whole compile-inclusive first call —
    includes the eager discovery pass and host bookkeeping the cache
    does not address) and ``compile_s``, the time inside
    ``capture.aot_compile`` — trace + lower + XLA compile when cold,
    artifact deserialize + executable relink when warm. The >=5x gate
    applies to ``compile_s``: that is the work the AOT cache replaces."""
    os.environ["MXNET_TPU_COMPILE_CACHE"] = cache_dir
    import mxnet_tpu as mx
    from mxnet_tpu import capture

    compile_s = [0.0]
    inner = capture.aot_compile

    def timed_aot_compile(*a, **k):
        t0 = time.perf_counter()
        try:
            return inner(*a, **k)
        finally:
            compile_s[0] += time.perf_counter() - t0

    # module-level rebind: CapturedTrainerStep resolves the global name
    capture.aot_compile = timed_aot_compile
    net, trainer, x, y = _build(mx)
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    t0 = time.perf_counter()
    loss = step(x, y, batch_size=BATCH)
    loss.wait_to_read()
    dt = time.perf_counter() - t0
    print(json.dumps({"first_step_s": dt, "compile_s": compile_s[0],
                      "stats": capture.stats()}))


def cold_start():
    """Run the child three times against one program-artifact dir: cold
    (jax's persistent compile cache switched off), a second pass that
    stores the executable in the persistent cache, then warm."""
    d = tempfile.mkdtemp(prefix="capbench_programs_")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    # keep the step's executable whatever it took to compile (pinned to
    # XLA-CPU the package leaves jax's 1 s floor in place)
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    out = {}
    try:
        for phase, xla_cache in (("cold", "false"), ("store", "true"),
                                 ("warm", "true")):
            env["JAX_ENABLE_COMPILATION_CACHE"] = xla_cache
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__),
                 "--_coldstart", d],
                capture_output=True, text=True, env=env, timeout=600)
            if proc.returncode != 0:
                raise RuntimeError(
                    f"{phase} child failed:\n{proc.stderr[-2000:]}")
            out[phase] = json.loads(proc.stdout.strip().splitlines()[-1])
    finally:
        shutil.rmtree(d, ignore_errors=True)
    return out["cold"], out["warm"]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--trials", type=int, default=4)
    ap.add_argument("--skip-coldstart", action="store_true")
    ap.add_argument("--_coldstart", metavar="DIR", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args._coldstart:
        _child_coldstart(args._coldstart)
        return 0

    warm_speedup = first_step_speedup = None
    cold_ok = True
    cold = warm = None
    if not args.skip_coldstart:
        cold, warm = cold_start()  # children first: parent still off jax
        assert warm["stats"].get("aot_cache_hits", 0) >= 1, \
            f"warm child missed the AOT cache: {warm['stats']}"
        warm_speedup = cold["compile_s"] / warm["compile_s"]
        first_step_speedup = cold["first_step_s"] / warm["first_step_s"]
        cold_ok = warm_speedup >= 5.0
        print(f"# cold-start compile {cold['compile_s']:.2f}s, warm "
              f"{warm['compile_s']:.2f}s ({warm_speedup:.1f}x, gate 5x); "
              f"whole first step {cold['first_step_s']:.2f}s -> "
              f"{warm['first_step_s']:.2f}s ({first_step_speedup:.1f}x)",
              file=sys.stderr)

    bulk, captured = steady_state(args.steps, args.trials)
    step_ok = captured <= bulk
    print(f"# eager-bulk {bulk * 1e3:.3f} ms/step, captured "
          f"{captured * 1e3:.3f} ms/step ({bulk / captured:.2f}x)",
          file=sys.stderr)

    from mxnet_tpu.observability import perf

    print(json.dumps({
        "metric": "capture_step_speedup",
        "value": round(bulk / captured, 3),
        "unit": "x",
        "device": perf.device_record(),
        "extra": {
            "eager_bulk_ms_per_step": round(bulk * 1e3, 3),
            "captured_ms_per_step": round(captured * 1e3, 3),
            "step_gate": "captured <= eager_bulk",
            "step_gate_ok": step_ok,
            "coldstart_compile_cold_s": (
                None if cold is None else round(cold["compile_s"], 3)),
            "coldstart_compile_warm_s": (
                None if warm is None else round(warm["compile_s"], 3)),
            "coldstart_warm_speedup_x": (None if warm_speedup is None
                                         else round(warm_speedup, 2)),
            "coldstart_first_step_cold_s": (
                None if cold is None else round(cold["first_step_s"], 3)),
            "coldstart_first_step_warm_s": (
                None if warm is None else round(warm["first_step_s"], 3)),
            "coldstart_first_step_speedup_x": (
                None if first_step_speedup is None
                else round(first_step_speedup, 2)),
            "coldstart_gate_x": 5.0,
            "coldstart_gate_ok": cold_ok,
        },
    }))
    return 0 if (step_ok and cold_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
