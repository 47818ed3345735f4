"""Chaos harness: drill every fault kind and prove the runtime recovers.

Runs a short training/serving loop under each ``MXNET_TPU_FAULTS`` kind
(via the same ``resilience.faults`` hooks the env var arms) and reports
recovered/failed per kind, plus the watchdog's overhead on the
un-faulted eager step path (acceptance gate: <= 5%).

Prints ONE JSON line (same convention as tools/dispatch_bench.py /
resilience_bench.py):

    {"metric": "chaos_recovered_kinds", "value": <n>, "unit": "kinds",
     "extra": {"total": ..., "per_kind": {...}, "watchdog_overhead_pct":
               ..., "overhead_gate_pct": 5.0}}

Exit code is non-zero when any kind failed to recover or the overhead
gate is blown. The per-kind drills are importable
(``run_kind(kind)``) — the ``chaos``-marked tier-1 tests in
tests/test_watchdog.py run the FAST_KINDS in-process.

Run: JAX_PLATFORMS=cpu python tools/chaos_run.py [--kinds a,b] [--steps N]
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The peer_death_recover drill needs a multi-device dp mesh; force the
# virtual CPU device count (like tests/conftest.py) before jax loads.
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()

# Every drill must finish fast even when recovery is broken: tight
# watchdog deadlines, short hang caps.
_DEADLINE = "0.5"
_ENV = {
    "MXNET_TPU_WATCHDOG_STEP_TIMEOUT": _DEADLINE,
    "MXNET_TPU_WATCHDOG_COLLECTIVE_TIMEOUT": _DEADLINE,
    "MXNET_TPU_WATCHDOG_BATCH_TIMEOUT": _DEADLINE,
    "MXNET_TPU_FAULT_HANG_CAP": "10",
}

FAST_KINDS = ("nan_grad", "nan_serving", "ckpt_enospc",
              "ckpt_partial_write", "ckpt_shard_corrupt",
              "ckpt_crash_before_manifest", "ckpt_async_crash",
              "hang_step", "hang_collective", "hang_batch", "peer_death",
              "peer_death_recover", "peer_death_multiaxis", "oom_step",
              "dist_connect_timeout", "host_death",
              "host_hang_collective", "coordinator_loss",
              "ckpt_partial_pod",
              "capture_step", "replica_crash", "replica_hang",
              "replica_nan_storm", "int8_calib_mismatch",
              "perf_regression", "slo_burn", "step_time_anomaly",
              "record_corrupt", "nonfinite_grad", "rollout_bad_weights",
              "canary_slo_regression", "autoscale_flap",
              "decode_replica_death", "kv_pool_exhaustion",
              "sdc_bitflip_param", "sdc_bitflip_grad",
              "sdc_device_sticky", "sdc_serving", "preempt")

# Flight-recorder contract (docs/observability.md): every drill must
# leave a matching event trail — a drill whose injection leaves no
# forensic record is a regression. Specs are (event kind, field,
# value); the default is the drill's own `fault` event. Exceptions:
# drills arming a different underlying kind, and ckpt_async_crash,
# whose fault fires inside the forked writer CHILD — the parent-side
# trail is the barrier's `ckpt: async_failed` event.
EXPECTED_FLIGHT_EVENTS = {
    "peer_death_recover": (("fault", "fault", "peer_death"),),
    "peer_death_multiaxis": (("fault", "fault", "peer_death"),),
    "capture_step": (("fault", "fault", "nan_grad"),
                     ("fault", "fault", "hang_step")),
    "ckpt_async_crash": (("ckpt", "op", "async_failed"),),
    # the SDC drills must leave the DETECTION trail too, not just the
    # injection: a fault that fired but was never caught is the exact
    # regression this defense exists to prevent
    "sdc_bitflip_param": (("fault", "fault", "sdc_bitflip_param"),
                          ("integrity", "op", "rollback")),
    "sdc_bitflip_grad": (("fault", "fault", "sdc_bitflip_grad"),
                         ("integrity", "op", "rollback")),
    "sdc_device_sticky": (("fault", "fault", "sdc_device_sticky"),
                          ("integrity", "op", "quarantine")),
    "sdc_serving": (("fault", "fault", "sdc_serving"),
                    ("integrity", "op", "serving_mismatch")),
    "preempt": (("fault", "fault", "preempt"),
                ("integrity", "op", "preempt_exit")),
}


def _flight_missing(kind, mark):
    """Event specs the drill should have left in the flight recorder
    (events after bookmark ``mark``) but did not; None when the
    recorder is disabled (nothing to assert against)."""
    from mxnet_tpu.observability import flight

    if flight.ring_size() == 0:
        return None
    events = flight.events(since_seq=mark)
    expected = EXPECTED_FLIGHT_EVENTS.get(
        kind, (("fault", "fault", kind),))
    missing = []
    for ekind, field, value in expected:
        if not any(e["kind"] == ekind and e.get(field) == value
                   for e in events):
            missing.append(f"{ekind}:{field}={value}")
    return missing


def _mx():
    import mxnet_tpu as mx

    return mx


def _trainer(mx, seed=11):
    import numpy as np

    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1, "momentum": 0.9})

    def step(k=0):
        x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3) + k)
        y = mx.nd.ones((2, 4))
        with mx.autograd.record():
            loss = ((net(x) - y) ** 2).sum()
        loss.backward()
        trainer.step(2)

    return net, trainer, step


def _params_finite(mx, net):
    import numpy as np

    return all(np.isfinite(p.data().asnumpy()).all()
               for p in net.collect_params().values())


# ------------------------------------------------------------------- drills

def _drill_nan_grad(mx, workdir):
    from mxnet_tpu.resilience import HealthSentinel, faults

    net, trainer, step = _trainer(mx)
    HealthSentinel(policy="skip_batch").attach(trainer)
    with faults.inject("nan_grad", at_step=1) as f:
        for k in range(3):
            step(k)
    ok = f.fired == 1 and _params_finite(mx, net)
    return ok, f"fired={f.fired} params_finite={_params_finite(mx, net)}"


def _drill_ckpt(mx, workdir, kind):
    import warnings

    from mxnet_tpu.resilience import CheckpointManager, faults

    net, trainer, step = _trainer(mx)
    step(0)
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
    mgr.save(1, net=net, trainer=trainer)
    step(1)
    try:
        with faults.inject(kind):
            mgr.save(2, net=net, trainer=trainer)
    except (OSError, faults.SimulatedCrash):
        pass  # an announced failure is fine; recovery is what matters
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        manifest = mgr.restore_latest(net=net, trainer=trainer)
    # a silently-corrupting kind must NOT restore the poisoned step 2
    want = (1,) if kind in ("ckpt_partial_write", "ckpt_shard_corrupt") \
        else (1, 2)
    ok = manifest is not None and manifest["step"] in want
    return ok, f"restored step={None if manifest is None else manifest['step']}"


def _drill_ckpt_async_crash(mx, workdir):
    """The background async writer dies before publishing: the barrier
    on the next save reports the loss (warning + counter), the debris is
    GC-able, and restore falls back to the previous checkpoint."""
    import warnings

    from mxnet_tpu.resilience import CheckpointManager, faults

    net, trainer, step = _trainer(mx)
    step(0)
    d = os.path.join(workdir, "ckpt")
    mgr = CheckpointManager(d, keep_n=3)
    mgr.save(1, net=net, trainer=trainer)
    step(1)
    with faults.inject("ckpt_async_crash"):
        mgr.save(2, net=net, trainer=trainer, async_=True)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            published = mgr.wait_for_async()
    debris_before = [n for n in os.listdir(d) if ".tmp." in n]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        manifest = mgr.restore_latest(net=net, trainer=trainer)
    # fork-mode debris carries the dead child's pid, so the restore's GC
    # removes it; thread-mode debris (live pid) is cleaned at next save
    debris_after = [n for n in os.listdir(d)
                    if ".tmp." in n and f".{os.getpid()}" not in n]
    ok = (not published and manifest is not None and manifest["step"] == 1
          and len(debris_before) == 1 and not debris_after)
    return ok, (f"published={published} restored="
                f"{None if manifest is None else manifest['step']} "
                f"debris {len(debris_before)}->{len(debris_after)}")


def _drill_peer_death_recover(mx, workdir):
    """A dp peer dies mid-run and the run SURVIVES: the trainer shrinks
    the mesh to the survivors, reloads the latest reshardable checkpoint
    onto it, and keeps training (counted + crash-reported)."""
    import warnings

    import numpy as np

    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import (CheckpointManager, elastic, faults,
                                      watchdog)

    # recovery recompiles the step on the shrunk mesh inside the guarded
    # scope — the deadline must cover compile time, not just execution
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "120"
    if len(jax.devices()) < 2:
        return False, "needs >= 2 devices (xla_force_host_platform_device_count)"
    dp = min(4, len(jax.devices()))
    mx.random.seed(13)
    net = mx.gluon.nn.Dense(4, in_units=4, prefix="chaos_net_")
    net.initialize()
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
    trainer = ShardedTrainer(net, lambda p, l: ((p - l) ** 2),
                             optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=create_mesh({"dp": dp},
                                              jax.devices()[:dp]),
                             checkpoint_manager=mgr)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    trainer.step(x, y)
    mgr.save(1, trainer=trainer)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with faults.inject("peer_death"):
            loss = trainer.step(x, y)     # dies -> shrinks -> re-runs
    new_dp = int(trainer.mesh.shape.get("dp", 0))
    trainer.step(x, y)                    # training continues on survivors
    s = {**watchdog.stats(), **elastic.stats()}
    ok = (new_dp == dp // 2 and np.isfinite(float(loss))
          and s["watchdog_peer_recoveries"] >= 1
          and s["elastic_mesh_shrinks"] >= 1
          and trainer.last_recovery is not None
          and trainer.last_recovery["step"] == 1)
    return ok, (f"dp {dp}->{new_dp} recoveries="
                f"{s['watchdog_peer_recoveries']}")


def _drill_peer_death_multiaxis(mx, workdir):
    """A dp peer dies during a CAPTURED dp×fsdp×tp transformer step and
    the run survives with the model-parallel topology intact: the shrink
    excises one whole dp slice (every fsdp×tp position of the dead
    slot), the checkpoint reloads onto the {dp:1, fsdp:2, tp:2}
    survivor mesh, and the continued run is bitwise-equal to a
    hand-seeded oracle trainer built directly on the shrunk topology
    (docs/parallel.md)."""
    import warnings

    import numpy as np

    import jax
    from mxnet_tpu import capture
    from mxnet_tpu.gluon.model_zoo import transformer as tzoo
    from mxnet_tpu.parallel import SpecLayout
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import (CheckpointManager, elastic, faults,
                                      watchdog)

    # recovery recompiles the transformer step on the shrunk mesh inside
    # the guarded scope — the deadline must cover compile time
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "180"
    if len(jax.devices()) < 8:
        return False, "needs >= 8 devices (xla_force_host_platform_device_count)"

    def build(axes, devs, mgr=None):
        mx.random.seed(29)
        net = tzoo.transformer_lm(vocab=16, units=8, num_heads=2,
                                  num_layers=1, max_len=16,
                                  prefix="chaos_tlm_")
        net.initialize()
        net(mx.nd.zeros((2, 4)))
        mesh = create_mesh(axes, devs)
        layout = SpecLayout.for_mesh(mesh)
        return ShardedTrainer(
            net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            mesh=mesh, param_rules=layout.param_rules(),
            batch_axis_name=layout.batch_axes(), checkpoint_manager=mgr)

    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
    trainer = build({"dp": 2, "fsdp": 2, "tp": 2}, jax.devices()[:8],
                    mgr)
    step = capture.capture(trainer)
    rs = np.random.RandomState(29)
    x = (rs.rand(8, 8) * 16).astype(np.int32)
    y = (rs.rand(8, 8) * 16).astype(np.int32)
    step(x, y)
    mgr.save(1, trainer=trainer)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with faults.inject("peer_death"):
            loss1 = step(x, y)            # dies -> shrinks -> re-runs
    new_axes = {str(a): int(s) for a, s in
                zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)}
    loss2 = step(x, y)                    # training continues

    # hand-seeded oracle: same net, built DIRECTLY on the shrunk
    # topology, restored from the same checkpoint — the recovered run
    # must match it bitwise, step for step
    oracle = build({"dp": 1, "fsdp": 2, "tp": 2}, jax.devices()[:4])
    mgr.restore_latest(trainer=oracle)
    o1, o2 = oracle.step(x, y), oracle.step(x, y)
    bitwise = (
        np.float32(loss1).tobytes() == np.float32(o1).tobytes()
        and np.float32(loss2).tobytes() == np.float32(o2).tobytes()
        and all(np.array_equal(np.asarray(trainer.params[k]),
                               np.asarray(oracle.params[k]))
                for k in trainer.params))
    s = {**watchdog.stats(), **elastic.stats()}
    ok = (new_axes == {"dp": 1, "fsdp": 2, "tp": 2} and bitwise
          and s["watchdog_peer_recoveries"] >= 1
          and s["elastic_mesh_shrinks"] >= 1
          and trainer.last_recovery is not None
          and trainer.last_recovery["step"] == 1)
    return ok, (f"axes {new_axes} bitwise={bitwise} recoveries="
                f"{s['watchdog_peer_recoveries']}")


def _pod_dense_trainer(mx, workdir, prefix, seed):
    """4-virtual-host x 2-chip simulated pod, dp=8 Dense trainer with a
    pod-bound checkpoint manager — the shared rig of the host-domain
    drills."""
    import numpy as np

    import jax
    from mxnet_tpu.parallel.mesh import PodTopology, pod_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import CheckpointManager

    topo = PodTopology.simulated(4, jax.devices()[:8])
    mesh, topo = pod_mesh({"dp": 8}, topo)
    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4, in_units=4, prefix=prefix)
    net.initialize()
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3,
                            pod=topo)
    trainer = ShardedTrainer(net, lambda p, l: ((p - l) ** 2),
                             optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=mesh,
                             checkpoint_manager=mgr).bind_pod(topo)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    return trainer, mgr, x, y


def _drill_host_death(mx, workdir):
    """A whole HOST (all 4 of its chips) dies during a CAPTURED
    dp×fsdp×tp transformer step on a 2-virtual-host pod (the CI pod
    shape: 2 hosts x 4 chips) and the run survives: host 1's rank slice
    IS dp slot 1, so the pod-wide shrink excises it whole, the
    distributed-commit checkpoint reloads cross-topology onto the
    survivor's mesh, and the continued run is bitwise-equal to a
    hand-seeded oracle trainer built directly on the shrunk pod
    (docs/distributed.md)."""
    import warnings

    import numpy as np

    import jax
    from mxnet_tpu import capture
    from mxnet_tpu.gluon.model_zoo import transformer as tzoo
    from mxnet_tpu.parallel import SpecLayout
    from mxnet_tpu.parallel.mesh import PodTopology, create_mesh, pod_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import (CheckpointManager, elastic, faults,
                                      watchdog)

    # recovery recompiles the transformer step on the shrunk mesh inside
    # a fresh step guard — the deadline must cover compile time
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "180"
    if len(jax.devices()) < 8:
        return False, "needs >= 8 devices (xla_force_host_platform_device_count)"

    def build_net():
        mx.random.seed(31)
        net = tzoo.transformer_lm(vocab=16, units=8, num_heads=2,
                                  num_layers=1, max_len=16,
                                  prefix="chaos_pod_tlm_")
        net.initialize()
        net(mx.nd.zeros((2, 4)))
        return net

    def build_trainer(net, mesh, mgr=None):
        layout = SpecLayout.for_mesh(mesh)
        return ShardedTrainer(
            net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
            optimizer="sgd", optimizer_params={"learning_rate": 0.1},
            mesh=mesh, param_rules=layout.param_rules(),
            batch_axis_name=layout.batch_axes(), checkpoint_manager=mgr)

    topo = PodTopology.simulated(2, jax.devices()[:8])
    mesh, topo = pod_mesh({"dp": 2, "fsdp": 2, "tp": 2}, topo)
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3,
                            pod=topo)
    trainer = build_trainer(build_net(), mesh, mgr).bind_pod(topo)
    step = capture.capture(trainer)
    rs = np.random.RandomState(31)
    x = (rs.rand(8, 8) * 16).astype(np.int32)
    y = (rs.rand(8, 8) * 16).astype(np.int32)
    step(x, y)
    mgr.save(1, trainer=trainer)        # pod distributed commit
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with faults.inject("host_death"):   # victim: host 1 (dp slot 0)
            loss1 = step(x, y)          # dies -> pod shrink -> re-runs
    new_axes = {str(a): int(s) for a, s in
                zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)}
    loss2 = step(x, y)                  # training continues on survivors

    # hand-seeded oracle: same net, built DIRECTLY on the surviving
    # hosts' devices, restored from the same distributed-commit
    # checkpoint — the recovered pod must match it bitwise
    oracle = build_trainer(build_net(),
                           create_mesh({"dp": 1, "fsdp": 2, "tp": 2},
                                       jax.devices()[:4]))
    mgr.restore_latest(trainer=oracle)
    o1, o2 = oracle.step(x, y), oracle.step(x, y)
    bitwise = (
        np.float32(loss1).tobytes() == np.float32(o1).tobytes()
        and np.float32(loss2).tobytes() == np.float32(o2).tobytes()
        and all(np.array_equal(np.asarray(trainer.params[k]),
                               np.asarray(oracle.params[k]))
                for k in trainer.params))
    s = {**watchdog.stats(), **elastic.stats()}
    pod = trainer.pod
    ok = (new_axes == {"dp": 1, "fsdp": 2, "tp": 2} and bitwise
          and pod is not None and pod.num_hosts == 1
          and s["watchdog_host_lost"] >= 1
          and s["watchdog_peer_recoveries"] >= 1
          and s["elastic_mesh_shrinks"] >= 1
          and trainer.last_recovery is not None
          and trainer.last_recovery["step"] == 1)
    return ok, (f"axes {new_axes} hosts=2->"
                f"{pod.num_hosts if pod else '?'} bitwise={bitwise} "
                f"host_lost={s['watchdog_host_lost']}")


def _drill_host_hang_collective(mx, workdir):
    """A pod host WEDGES (not crashes) at the collective entry: no
    process exits, so only the watchdog's stall deadline can see it. The
    stall converts to a dead-host verdict via the pod liveness layer's
    suspect-blame (the armed fault names its victim; a real pod scans
    stale heartbeats), and recovery proceeds exactly as for a crash."""
    import threading
    import warnings

    import numpy as np

    import jax
    from mxnet_tpu.resilience import elastic, faults, watchdog

    if len(jax.devices()) < 8:
        return False, "needs >= 8 devices (xla_force_host_platform_device_count)"
    # detection needs a SHORT step deadline, but the post-shrink retry
    # recompiles inside a fresh guard reading the same env knob — lift
    # the deadline the moment the stall converts to a dead-host verdict
    # (the mark precedes the async raise, and recovery takes far longer
    # than this watcher's poll interval)
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "0.75"
    stop = threading.Event()

    def lift():
        while not stop.is_set():
            if watchdog.dead_hosts():
                os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "180"
                return
            time.sleep(0.002)

    lifter = threading.Thread(target=lift, daemon=True)
    lifter.start()
    try:
        trainer, mgr, x, y = _pod_dense_trainer(mx, workdir,
                                                "chaos_hang_host_", 37)
        trainer.step(x, y)
        mgr.save(1, trainer=trainer)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with faults.inject("host_hang_collective"):  # victim: host 1
                loss = trainer.step(x, y)  # wedges -> stall -> shrink
    finally:
        stop.set()
    new_dp = int(trainer.mesh.shape.get("dp", 0))
    trainer.step(x, y)                     # training continues
    s = {**watchdog.stats(), **elastic.stats()}
    pod = trainer.pod
    ok = (new_dp == 4 and pod is not None and pod.num_hosts == 2
          and np.isfinite(float(loss))
          and s["watchdog_host_lost"] >= 1
          and s["watchdog_peer_recoveries"] >= 1
          and s["elastic_mesh_shrinks"] >= 1
          and trainer.last_recovery is not None
          and trainer.last_recovery["step"] == 1)
    return ok, (f"dp 8->{new_dp} hosts=4->"
                f"{pod.num_hosts if pod else '?'} "
                f"host_lost={s['watchdog_host_lost']}")


def _drill_coordinator_loss(mx, workdir):
    """The COORDINATOR host (rank 0) dies: the liveness layer marks it,
    survivors shrink it out of the pod, and the lowest surviving host is
    promoted — the renumbered topology's new host 0 is the old host 1,
    and the pod keeps training under the new coordinator."""
    import warnings

    import numpy as np

    import jax
    from mxnet_tpu.resilience import elastic, faults, watchdog

    # recovery recompiles on the shrunk mesh inside a fresh step guard
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "120"
    if len(jax.devices()) < 8:
        return False, "needs >= 8 devices (xla_force_host_platform_device_count)"
    trainer, mgr, x, y = _pod_dense_trainer(mx, workdir, "chaos_coord_",
                                            41)
    trainer.step(x, y)
    mgr.save(1, trainer=trainer)
    coord_before = watchdog.coordinator()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with faults.inject("coordinator_loss"):
            loss = trainer.step(x, y)      # host 0 dies -> promotion
    coord_after = watchdog.coordinator()
    new_dp = int(trainer.mesh.shape.get("dp", 0))
    trainer.step(x, y)                     # training continues
    s = {**watchdog.stats(), **elastic.stats()}
    pod = trainer.pod
    import jax as _jax

    # host 0 (ordinals 0,1) excised; trim keeps ordinals 2..5, so the
    # promoted pod's first device is the old global ordinal 2
    promoted = (pod is not None and pod.devices is not None
                and pod.devices[0].id == _jax.devices()[2].id)
    ok = (coord_before == 0 and coord_after == 0 and promoted
          and new_dp == 4 and pod.num_hosts == 2
          and np.isfinite(float(loss))
          and s["watchdog_host_lost"] >= 1
          and s["watchdog_peer_recoveries"] >= 1
          and trainer.last_recovery is not None)
    return ok, (f"dp 8->{new_dp} promoted={promoted} "
                f"hosts=4->{pod.num_hosts if pod else '?'}")


def _drill_ckpt_partial_pod(mx, workdir):
    """A host crashes MID-DISTRIBUTED-COMMIT (after its shards, before
    its completion marker): the manifest is never published, so the
    failed attempt is pure debris — the previous checkpoint restores
    bitwise, and the staleness GC reaps the shared tmpdir once its
    orphan grace expires. Never a torn manifest, never a lost
    checkpoint."""
    import numpy as np

    import jax
    from mxnet_tpu.resilience import checkpoint, faults

    if len(jax.devices()) < 8:
        return False, "needs >= 8 devices (xla_force_host_platform_device_count)"
    trainer, mgr, x, y = _pod_dense_trainer(mx, workdir, "chaos_cpp_", 43)
    directory = os.path.join(workdir, "ckpt")
    trainer.step(x, y)
    mgr.save(1, trainer=trainer)           # clean distributed commit
    before = {k: np.asarray(v).copy() for k, v in trainer.params.items()}
    trainer.step(x, y)                     # advance past the checkpoint
    crashed = False
    try:
        with faults.inject("ckpt_partial_pod"):
            mgr.save(2, trainer=trainer)   # dies after host 0's shards
    except faults.SimulatedCrash:
        crashed = True
    if not crashed:
        return False, "ckpt_partial_pod fault never fired"
    entries = sorted(os.listdir(directory))
    torn = [e for e in entries if e == "ckpt-00000002"]
    debris = [e for e in entries if e.endswith(".tmp.pod")]
    man = mgr.restore_latest(trainer=trainer)
    restored = (man is not None and man["step"] == 1
                and all(np.array_equal(np.asarray(trainer.params[k]),
                                       before[k]) for k in before))
    # the shared tmpdir is debris, reaped only past its orphan grace
    prior = os.environ.get("MXNET_TPU_CKPT_ORPHAN_GRACE_S")
    try:
        os.environ["MXNET_TPU_CKPT_ORPHAN_GRACE_S"] = "0"
        mgr._gc_debris()
    finally:
        if prior is None:
            os.environ.pop("MXNET_TPU_CKPT_ORPHAN_GRACE_S", None)
        else:
            os.environ["MXNET_TPU_CKPT_ORPHAN_GRACE_S"] = prior
    reaped = not any(e.endswith(".tmp.pod") for e in os.listdir(directory))
    kept = os.path.isfile(os.path.join(directory, "ckpt-00000001",
                                       "manifest.json"))
    s = checkpoint.stats()
    ok = (not torn and len(debris) == 1 and restored and reaped and kept
          and s["ckpt_pod_commit_failures"] >= 1)
    return ok, (f"torn={torn} debris={len(debris)} restored={restored} "
                f"reaped={reaped}")


def _drill_hang_step(mx, workdir):
    import numpy as np

    from mxnet_tpu.resilience import (CheckpointManager, HealthSentinel,
                                      faults)

    net, trainer, step = _trainer(mx)
    step(0)
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
    HealthSentinel(policy="rollback").attach(trainer, net=net,
                                             checkpoint_manager=mgr)
    mgr.save(1, net=net, trainer=trainer)
    saved = {k: v.asnumpy().copy()
             for k, v in net._collect_params_with_prefix().items()}
    t0 = time.monotonic()
    with faults.inject("hang_step"):
        step(1)   # stalls -> StallError -> rollback -> returns
    elapsed = time.monotonic() - t0
    now = {k: v.asnumpy() for k, v in net._collect_params_with_prefix().items()}
    bitwise = all(np.array_equal(saved[k], now[k]) for k in saved)
    step(2)       # training continues
    ok = bitwise and elapsed < 2 * float(_DEADLINE) + 1.0
    return ok, f"elapsed={elapsed:.2f}s bitwise={bitwise}"


def _drill_hang_collective(mx, workdir):
    from mxnet_tpu.resilience import StallError, faults

    kv = mx.kvstore.create("tpu")
    kv.init(0, mx.nd.ones((4,)))
    t0 = time.monotonic()
    try:
        with faults.inject("hang_collective"):
            kv.push(0, mx.nd.ones((4,)))
        return False, "no StallError raised"
    except StallError:
        elapsed = time.monotonic() - t0
    kv.push(0, mx.nd.ones((4,)))  # the store keeps serving
    ok = elapsed < 2 * float(_DEADLINE) + 1.0
    return ok, f"elapsed={elapsed:.2f}s"


def _drill_peer_death(mx, workdir):
    from mxnet_tpu.resilience import PeerLostError, faults, watchdog

    kv = mx.kvstore.create("tpu")
    kv.init(0, mx.nd.ones((4,)))
    try:
        try:
            with faults.inject("peer_death"):
                kv.push(0, mx.nd.ones((4,)))
            return False, "no PeerLostError raised"
        except PeerLostError as e:
            named = "1" in str(e) and e.ranks == (1,)
        watchdog.reset_peers()
        kv.push(0, mx.nd.ones((4,)))  # rank re-admitted, service resumes
        return named, f"named_rank={named}"
    finally:
        watchdog.reset_peers()


def _drill_hang_batch(mx, workdir):
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.resilience import StallError, faults

    mx.random.seed(5)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    pred = serving.Predictor.from_block(net, input_shapes={"data": (3,)},
                                        batch_sizes=(4,))
    x = np.ones((1, 3), np.float32)
    with serving.BatchServer(pred, max_batch_size=4,
                             batch_timeout_ms=1.0) as srv:
        with faults.inject("hang_batch"):
            fut = srv.submit(x)
            try:
                fut.result(timeout=10)
                return False, "stalled batch resolved"
            except StallError:
                pass
        ok_after = srv.submit(x).result(timeout=10)  # queue not wedged
    return len(ok_after) > 0, "queue survived the stalled batch"


def _drill_nan_serving(mx, workdir):
    """A poisoned inference batch (kind ``nan_serving``) flows through
    the real compiled executable; the BatchServer's output health check
    fails ONLY that batch's futures and the queue keeps serving."""
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.resilience.sentinel import NumericHealthError

    mx.random.seed(5)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    pred = serving.Predictor.from_block(net, input_shapes={"data": (3,)},
                                        batch_sizes=(4,))
    x = np.ones((1, 3), np.float32)
    with serving.BatchServer(pred, max_batch_size=4,
                             batch_timeout_ms=1.0) as srv:
        with faults.inject("nan_serving") as f:
            fut = srv.submit(x)
            try:
                fut.result(timeout=10)
                return False, "poisoned batch resolved as healthy"
            except NumericHealthError:
                pass
        ok_after = srv.submit(x).result(timeout=10)  # queue not wedged
    ok = (f.fired == 1 and len(ok_after) > 0
          and np.isfinite(ok_after[0]).all())
    return ok, "poisoned batch isolated; queue kept serving"


def _drill_oom_step(mx, workdir):
    import numpy as np

    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import elastic, faults

    # the retry compiles fresh grad/apply executables inside the guarded
    # step — the deadline must cover compile time, not just execution
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "120"
    mx.random.seed(7)
    net = mx.gluon.nn.Dense(4, in_units=4)
    net.initialize()
    trainer = ShardedTrainer(net, lambda p, l: ((p - l) ** 2),
                             optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1},
                             mesh=create_mesh({"dp": 1}, jax.devices()[:1]))
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    with faults.inject("oom_step", times=1) as f:
        trainer.step(x, y)
    trainer.step(x, y)  # sticky accumulation keeps working
    s = elastic.stats()
    ok = (f.fired == 1 and trainer._elastic_n == 2
          and s["elastic_shrinks"] >= 1 and s["elastic_accum_steps"] >= 2)
    return ok, f"n={trainer._elastic_n} stats={s}"


def _drill_capture_step(mx, workdir):
    """Fault injection under a CAPTURED whole-program step
    (mxnet_tpu.capture, docs/capture.md): a nan_grad-poisoned batch
    flows through the compiled program's fused finite check and the
    in-program select leaves weights bitwise-untouched (skip_batch);
    then hang_step stalls the captured call and the rollback sentinel
    restores the checkpoint, exactly like the eager drills."""
    import numpy as np

    from mxnet_tpu import capture
    from mxnet_tpu.resilience import (CheckpointManager, HealthSentinel,
                                      faults)

    def loss_fn(out, y):
        return ((out - y) ** 2).sum()

    net, trainer, _ = _trainer(mx)
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=2)
    sent = HealthSentinel(policy="skip_batch")
    step = capture.capture(trainer, net=net, loss_fn=loss_fn,
                           sentinel=sent)
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    y = mx.nd.ones((2, 4))
    step(x, y, batch_size=2)  # compile + one clean step
    before = {k: v.asnumpy().copy()
              for k, v in net._collect_params_with_prefix().items()}
    with faults.inject("nan_grad") as f:
        step(x, y, batch_size=2)
    now = {k: v.asnumpy()
           for k, v in net._collect_params_with_prefix().items()}
    gated = f.fired == 1 and all(
        np.array_equal(before[k], now[k]) for k in before)

    # stall the captured call: rollback policy -> checkpoint restore
    sent.policy = "rollback"
    sent.attach(trainer, net=net, checkpoint_manager=mgr)
    mgr.save(1, net=net, trainer=trainer)
    t0 = time.monotonic()
    with faults.inject("hang_step"):
        out = step(x, y, batch_size=2)  # stalls -> rollback -> skipped
    elapsed = time.monotonic() - t0
    now = {k: v.asnumpy()
           for k, v in net._collect_params_with_prefix().items()}
    rolled = out is None and all(
        np.array_equal(before[k], now[k]) for k in before)
    step(x, y, batch_size=2)  # training continues
    ok = gated and rolled and elapsed < 2 * float(_DEADLINE) + 1.0
    return ok, f"gated={gated} rolled_back={rolled} elapsed={elapsed:.2f}s"


def _drill_replica_fault(mx, workdir, kind):
    """The ISSUE-8 chaos gate, in miniature: a 2-replica fleet under a
    stream of deadlined requests while one replica is killed / hung /
    NaN-poisoned mid-stream. Zero admitted requests may be lost (every
    future resolves, and with retries every one of them to a CORRECT
    result), the victim must be auto-restarted — warm from the AOT
    compile cache — and re-admitted through a half-open breaker probe."""
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults

    saved_cache = os.environ.get("MXNET_TPU_COMPILE_CACHE")
    os.environ["MXNET_TPU_COMPILE_CACHE"] = os.path.join(workdir, "aot")
    try:
        def factory():
            # the stable prefix keeps param names (and so the AOT cache
            # fingerprint) identical across rebuilds — a gensym'd name
            # (dense0_ vs dense7_) would miss the cache on every restart
            mx.random.seed(5)
            net = mx.gluon.nn.Dense(4, in_units=3, prefix="fleet_net_")
            net.initialize()
            return serving.Predictor.from_block(
                net, input_shapes={"data": (3,)}, batch_sizes=(2,))

        serving.reset_stats()
        x = np.ones((1, 3), np.float32)
        with serving.Fleet(factory, replicas=2, probe_interval_ms=50,
                           breaker_k=2, retries=2, backoff_ms=1,
                           breaker_cooldown_ms=100,
                           server_kw={"batch_timeout_ms": 1.0}) as fleet:
            baseline = fleet.submit(x, deadline_ms=10000).result(timeout=10)
            with faults.inject(kind, times=4) as f:
                futs = [fleet.submit(x, deadline_ms=10000)
                        for _ in range(8)]
                oks = errs = 0
                for fu in futs:
                    try:
                        r = fu.result(timeout=30)
                        oks += int(np.array_equal(r[0], baseline[0]))
                    except Exception:
                        errs += 1
            recovered = fleet.wait_healthy(timeout=20)
            victim = fleet.replicas()[0]
            warm_hits = getattr(victim.predictor, "warmup_cache_hits", 0)
            after = fleet.submit(x, deadline_ms=10000).result(timeout=10)
        s = serving.stats()
        ok = (oks == 8 and errs == 0 and f.fired >= 1 and recovered
              and s["fleet_restarts"] >= 1 and s["fleet_drains"] >= 1
              and s["fleet_half_open_probes"] >= 1 and warm_hits >= 1
              and np.array_equal(after[0], baseline[0]))
        return ok, (f"ok={oks}/8 errs={errs} fired={f.fired} "
                    f"restarts={s['fleet_restarts']} "
                    f"half_open={s['fleet_half_open_probes']} "
                    f"warm_hits={warm_hits} recovered={recovered}")
    finally:
        if saved_cache is None:
            os.environ.pop("MXNET_TPU_COMPILE_CACHE", None)
        else:
            os.environ["MXNET_TPU_COMPILE_CACHE"] = saved_cache


def _drill_int8_calib_mismatch(mx, workdir):
    """A stale calibration table reaches an int8 quantize (the shipped
    table no longer matches the model): the apply path must reject it
    with a STRUCTURED CalibrationMismatchError — mis-scaled int8 serves
    silently wrong answers, an error is recoverable. Disarmed, the same
    table applies cleanly and the quantized model serves finite
    outputs."""
    import numpy as np

    from mxnet_tpu import symbol as sym
    from mxnet_tpu.contrib.quantization import (CalibrationMismatchError,
                                                calibrate, quantize_model)
    from mxnet_tpu.resilience import faults

    rng = np.random.RandomState(3)
    data = sym.Variable("data")
    c = sym.Convolution(data, kernel=(3, 3), pad=(1, 1), num_filter=4,
                        name="chaos_c1")
    r = sym.Activation(c, act_type="relu", name="chaos_r1")
    net = sym.FullyConnected(r, num_hidden=4, name="chaos_fc1")
    args = {"chaos_c1_weight": mx.nd.array(
                (rng.randn(4, 2, 3, 3) * 0.2).astype(np.float32)),
            "chaos_c1_bias": mx.nd.zeros((4,)),
            "chaos_fc1_weight": mx.nd.array(
                (rng.randn(4, 4 * 6 * 6) * 0.1).astype(np.float32)),
            "chaos_fc1_bias": mx.nd.zeros((4,))}
    x = rng.rand(8, 2, 6, 6).astype(np.float32)
    table = calibrate(net, args, {}, mx.io.NDArrayIter(data=x, batch_size=4),
                      calib_mode="naive")
    with faults.inject("int8_calib_mismatch") as f:
        try:
            quantize_model(net, args, {}, calib_table=table,
                           quantize_mode="full")
            return False, "stale table was accepted silently"
        except CalibrationMismatchError as e:
            structured = e.model_digest is not None
    # disarmed: the true table applies and the int8 model serves
    qsym, qargs, qaux = quantize_model(net, args, {}, calib_table=table,
                                       quantize_mode="full")
    ex = qsym.bind(mx.cpu(), {**qargs, "data": mx.nd.array(x)},
                   grad_req="null")
    out = ex.forward(is_train=False)[0].asnumpy()
    ok = f.fired == 1 and structured and np.isfinite(out).all()
    return ok, (f"fired={f.fired} structured={structured} "
                f"recovered_finite={bool(np.isfinite(out).all())}")


def _drill_perf_regression(mx, workdir):
    """The continuous perf gate must actually FAIL when an executable
    regresses: armed, the fault inflates the measured numbers entering
    ``tools/perf_gate.py``'s baseline comparison — every gated metric
    blows its tolerance, each with a ``perf`` flight event — and
    disarmed, the identical measurements pass clean (recovery = the
    gate is discriminating, not just noisy)."""
    import importlib.util

    from mxnet_tpu.observability import flight
    from mxnet_tpu.resilience import faults

    spec = importlib.util.spec_from_file_location(
        "perf_gate", os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                  "perf_gate.py"))
    perf_gate = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(perf_gate)

    baseline = {
        "trainer_step@feedfacefeedface": {
            "step_ms": 1.0, "compile_ms": 50.0, "peak_hbm_bytes": 4096},
        "serving_bucket8@deadbeefdeadbeef": {
            "step_ms": 0.2, "compile_ms": 20.0, "peak_hbm_bytes": 1024},
    }
    current = {k: dict(v) for k, v in baseline.items()}
    mark = flight.last_seq()
    with faults.inject("perf_regression") as f:
        regressions, rebaselined = perf_gate.compare(current, baseline)
    perf_events = [e for e in flight.events(kind="perf",
                                            since_seq=mark)
                   if e.get("event") == "regression"]
    detected = (f.fired == 1 and len(regressions) >= 1
                and not rebaselined
                and len(perf_events) == len(regressions))
    # disarmed: the same measurements against the same baseline are clean
    clean, _ = perf_gate.compare(current, baseline)
    ok = detected and not clean
    return ok, (f"fired={f.fired} regressions={len(regressions)} "
                f"flight_perf_events={len(perf_events)} "
                f"clean_after={not clean}")


def _assert_one_incident(alerts, rule_id, want_ledger_key=False):
    """Shared incident checks for the alerting drills: exactly one
    incident is open, for the expected rule, and its report is
    CORRELATED — a flight slice containing the injected fault event,
    at least one exemplar span tree, and (when asked) an implicated
    perf-ledger key. Returns (ok, detail, incident)."""
    incs = alerts.incidents()
    opened = [i for i in incs if i["status"] == "open"]
    if len(incs) != 1 or len(opened) != 1:
        return (False,
                f"expected exactly one open incident, got {len(incs)} "
                f"({len(opened)} open)", None)
    inc = opened[0]
    if inc["rule"] != rule_id:
        return False, f"incident rule {inc['rule']} != {rule_id}", inc
    has_fault = any(e.get("kind") == "fault" for e in inc["flight"])
    has_exemplar = len(inc["exemplars"]) >= 1 and all(
        tree for tree in inc["exemplars"])
    has_key = (not want_ledger_key
               or bool(inc["evidence"].get("ledger_keys")))
    if not (has_fault and has_exemplar and has_key):
        return (False,
                f"incident not correlated: fault_event={has_fault} "
                f"exemplars={has_exemplar} ledger_key={has_key}", inc)
    return True, "", inc


def _drill_slo_burn(mx, workdir):
    """An SLO burn on a LIVE 2-replica fleet: the injected fault
    inflates the deadline-miss counters feeding metrics.slo_counters(),
    the multi-window burn-rate rule goes FIRING and opens exactly ONE
    correlated incident (flight slice with the fault event, >=1
    exemplar serve.request tree, fleet replica states), and once the
    injection stops the rule cools down and the incident RESOLVES."""
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.observability import alerts, flight, trace
    from mxnet_tpu.resilience import faults

    def factory():
        mx.random.seed(5)
        net = mx.gluon.nn.Dense(4, in_units=3, prefix="burn_net_")
        net.initialize()
        return serving.Predictor.from_block(
            net, input_shapes={"data": (3,)}, batch_sizes=(2,))

    alerts.reset()
    serving.reset_stats()
    prev_trace = trace.set_enabled(True)
    prev_alerts = alerts.set_enabled(False)  # drive a synthetic clock:
    trace.clear()                            # no real-time auto-ticks
    try:
        x = np.ones((1, 3), np.float32)
        with serving.Fleet(factory, replicas=2,
                           server_kw={"batch_timeout_ms": 1.0}) as fleet:
            for _ in range(4):
                fleet.submit(x, deadline_ms=10000).result(timeout=10)
            t = 1000.0
            alerts.evaluate(now=t, force=True)  # clean window bookmark
            if alerts.incidents():
                return False, "incident open before the injection"
            with faults.inject("slo_burn", times=None) as f:
                for _ in range(2):
                    t += 30.0
                    alerts.evaluate(now=t, force=True)
            ok, why, inc = _assert_one_incident(alerts,
                                                "slo_deadline_burn")
            if not ok:
                return False, why
            burn = inc["evidence"]["windows"]["fast"]["burn"]
            has_fleet = len(inc["fleet"]) == 2
            exemplar_root = inc["exemplars"][0][0]["name"]
            # injection stopped: the rule must cool down and resolve
            t += alerts.get_rule("slo_deadline_burn").cooldown_s + 1.0
            alerts.evaluate(now=t, force=True)
        resolved = (not alerts.open_incidents()
                    and alerts.incidents()[0]["status"] == "resolved")
        states = [e["state"] for e in flight.events(kind="alert")]
        ok = (f.fired >= 1 and resolved and has_fleet
              and exemplar_root == "serve.request"
              and states[-2:] == ["FIRING", "RESOLVED"])
        return ok, (f"fired={f.fired} burn={burn} fleet_states={has_fleet} "
                    f"exemplar={exemplar_root} resolved={resolved}")
    finally:
        trace.set_enabled(prev_trace)
        alerts.set_enabled(prev_alerts)
        alerts.reset()


def _drill_step_time_anomaly(mx, workdir):
    """A step-time anomaly on a CAPTURED training step: the fault
    inflates one measured step duration as the median/MAD drift
    detector ingests it, exactly one correlated incident opens — its
    report naming the implicated perf-ledger key (the captured step's
    executable) next to the flight slice and an exemplar step
    timeline — and clean steps after the injection resolve it."""
    import numpy as np

    from mxnet_tpu import capture
    from mxnet_tpu.observability import alerts, trace
    from mxnet_tpu.resilience import faults

    def loss_fn(out, y):
        return ((out - y) ** 2).sum()

    def evaluate(now):
        # the drill sets the step durations the detector reads from the
        # trace ring, as it sets ``now``: the wall time of a CPU step on
        # a loaded host doubles on its own, before or after the fault
        for s in trace.spans():
            if s["name"] in alerts.StepTimeDriftRule.STEP_ROOTS:
                s["dur_ns"] = 10_000_000
        alerts.evaluate(now=now, force=True)

    alerts.reset()
    prev_trace = trace.set_enabled(True)
    prev_alerts = alerts.set_enabled(False)
    trace.clear()
    try:
        net, trainer, _ = _trainer(mx)
        step = capture.capture(trainer, net=net, loss_fn=loss_fn)
        x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
        y = mx.nd.ones((2, 4))
        for _ in range(10):
            step(x, y, batch_size=2)
        t = 1000.0
        evaluate(t)  # banks the clean baseline
        if alerts.incidents():
            return False, "incident open before the injection"
        with faults.inject("step_time_anomaly", times=1) as f:
            step(x, y, batch_size=2)   # the next ingest inflates this one
            t += 5.0
            evaluate(t)
        ok, why, inc = _assert_one_incident(alerts, "step_time_drift",
                                            want_ledger_key=True)
        if not ok:
            return False, why
        keys = inc["evidence"]["ledger_keys"]
        ledgered = any(k.startswith("trainer_step@") for k in keys) \
            and all(k in inc["perf"] for k in keys)
        exemplar_root = inc["exemplars"][0][0]["name"]
        # clean steps only: the detector must stop breaching + resolve
        for _ in range(3):
            step(x, y, batch_size=2)
        t += alerts.get_rule("step_time_drift").cooldown_s + 1.0
        evaluate(t)
        resolved = (not alerts.open_incidents()
                    and alerts.incidents()[0]["status"] == "resolved")
        ok = (f.fired == 1 and ledgered and resolved
              and exemplar_root == "train.captured_step")
        return ok, (f"fired={f.fired} ledger_keys={keys} "
                    f"exemplar={exemplar_root} resolved={resolved}")
    finally:
        trace.set_enabled(prev_trace)
        alerts.set_enabled(prev_alerts)
        alerts.reset()


def _drill_nonfinite_grad(mx, workdir):
    """A NaN lands in ONE layer's numerics mid-run under a CAPTURED
    step with the in-graph telemetry tap armed: the fused finite flag
    trips, the ``numerics_nonfinite`` alert FIRES with exactly one
    correlated incident whose evidence carries the automatic numerics
    snapshot, ``tools/numerics_bisect.py`` replays that snapshot
    eagerly and names the poisoned layer, training keeps running under
    ``MXNET_TPU_NONFINITE_POLICY=skip`` (the in-program select gated
    every bad update), and a fresh run under ``policy=halt`` raises a
    structured NumericsDivergenceError at onset."""
    import importlib.util

    import numpy as np

    from mxnet_tpu import capture
    from mxnet_tpu.observability import alerts, numerics, trace
    from mxnet_tpu.resilience import faults

    alerts.reset()
    numerics.reset()
    prev_trace = trace.set_enabled(True)
    prev_alerts = alerts.set_enabled(False)
    trace.clear()
    saved_env = {k: os.environ.get(k) for k in
                 ("MXNET_TPU_NUMERICS_SNAPSHOT_DIR",
                  "MXNET_TPU_FAULT_NONFINITE_LAYER")}
    os.environ["MXNET_TPU_NUMERICS_SNAPSHOT_DIR"] = \
        os.path.join(workdir, "numerics")
    os.environ["MXNET_TPU_FAULT_NONFINITE_LAYER"] = "dense1"

    def loss_fn(out, y):
        return ((out - y) ** 2).sum()

    def make_net(prefix):
        mx.random.seed(7)
        net = mx.gluon.nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(mx.gluon.nn.Dense(16, activation="relu"))
            net.add(mx.gluon.nn.Dense(8, activation="relu"))
            net.add(mx.gluon.nn.Dense(4))
        net.initialize()
        net(mx.nd.zeros((2, 8)))
        return net

    def build(policy, prefix):
        net = make_net(prefix)
        trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.05})
        tap = numerics.NumericsTap(interval=1, policy=policy)
        return net, capture.capture(trainer, net=net, loss_fn=loss_fn,
                                    numerics=tap)

    def batch(k):
        rs = np.random.RandomState(k)
        return (mx.nd.array(rs.rand(8, 8).astype(np.float32)),
                mx.nd.ones((8, 4)))

    try:
        # --- policy=skip: detect, snapshot, alert, keep training
        net, step = build("skip", "chaosnum_")
        for k in range(4):
            step(*batch(k), batch_size=8)
        t = 1000.0
        alerts.evaluate(now=t, force=True)
        if alerts.incidents():
            return False, "incident open before the injection"
        with faults.inject("nonfinite_grad", times=1) as f:
            step(*batch(4), batch_size=8)  # poisons dense1's weight
        survived = 0
        for k in range(5, 8):  # skip policy: the loop keeps running
            step(*batch(k), batch_size=8)
            survived += 1
        t += 5.0
        alerts.evaluate(now=t, force=True)
        ok_inc, why, inc = _assert_one_incident(alerts,
                                                "numerics_nonfinite")
        if not ok_inc:
            return False, why
        snap = inc["evidence"].get("snapshot")
        if not snap or not os.path.isdir(snap):
            return False, f"incident carries no numerics snapshot: {snap}"
        # --- the snapshot bisects back to the poisoned layer
        spec = importlib.util.spec_from_file_location(
            "numerics_bisect", os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "numerics_bisect.py"))
        bisect_tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bisect_tool)
        # run_bisect replays EAGERLY and replaces every param from the
        # snapshot: a bare structurally-identical net suffices — no
        # capture (and no pair of XLA compiles) for the replay
        replay_net = make_net("chaosnumr_")
        report = bisect_tool.run_bisect(snap, replay_net, loss_fn)
        named = report.get("first_bad_layer") or ""
        localized = "dense1" in named
        # --- policy=halt: a fresh run raises at onset
        numerics.reset()
        alerts.reset()
        net2, step2 = build("halt", "chaoshalt_")
        halted = False
        with faults.inject("nonfinite_grad", times=1) as f2:
            try:
                for k in range(3):
                    step2(*batch(k), batch_size=8)
            except numerics.NumericsDivergenceError:
                halted = True
        ok = (f.fired == 1 and f2.fired == 1 and survived == 3
              and localized and halted)
        return ok, (f"fired={f.fired}+{f2.fired} survived={survived} "
                    f"first_bad_layer={named!r} localized={localized} "
                    f"halted={halted} snapshot={os.path.basename(snap)}")
    finally:
        trace.set_enabled(prev_trace)
        alerts.set_enabled(prev_alerts)
        alerts.reset()
        numerics.reset()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _drill_record_corrupt(mx, workdir):
    """A streamed RecordIO payload is corrupted in flight (bitrot the
    range read can't see — same length, only the index CRC catches it):
    policy=raise surfaces a STRUCTURED RecordCorruptError naming the
    shard/key/offset, and policy=skip counts ``io_records_corrupt``,
    substitutes the row, and keeps delivering every other record —
    never garbage bytes decoded into a batch."""
    import numpy as np

    from mxnet_tpu import recordio
    from mxnet_tpu.io import stream as dstream
    from mxnet_tpu.resilience import faults

    prefix = os.path.join(workdir, "stream")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(8):
        payload = np.full(4, i, np.float32).tobytes()
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(i), i, 0), payload))
    rec.close()
    decode = dstream.raw_decoder((4,))

    # policy=raise: the corrupt record is a structured error, not data
    it = dstream.StreamBatchIter(prefix + ".rec", batch_size=2,
                                 decode=decode, epochs=1,
                                 corrupt_policy="raise")
    with faults.inject("record_corrupt") as f:
        try:
            next(it)
            return False, "corrupt record decoded into a batch"
        except recordio.RecordCorruptError as e:
            structured = (e.path is not None and e.key is not None
                          and e.offset is not None)
        finally:
            # drain the decode pool INSIDE the inject scope: pool.map
            # re-raises on the first errored row while a sibling worker
            # may still be mid-read, and that straggler must not live
            # long enough to swallow the next phase's single-shot fault
            it.close()

    # policy=skip: counted substitute row, stream completes the epoch
    before = dstream.stats()["io_records_corrupt"]
    it = dstream.StreamBatchIter(prefix + ".rec", batch_size=2,
                                 decode=decode, epochs=1,
                                 corrupt_policy="skip")
    with faults.inject("record_corrupt") as f2:
        batches = list(it)
    skipped = dstream.stats()["io_records_corrupt"] - before
    labels = sorted(float(v) for b in batches for v in np.atleast_1d(b.label))
    # 8 records, one corrupt: its row is substituted by a valid batch
    # row, so geometry holds (4 batches x 2 rows) with one duplicate
    ok = (structured and f.fired == 1 and f2.fired == 1 and skipped == 1
          and len(batches) == 4 and len(set(labels)) == 7)
    return ok, (f"structured={structured} skipped={skipped} "
                f"batches={len(batches)} distinct_labels={len(set(labels))}")


def _drill_dist_connect_timeout(mx, workdir):
    from mxnet_tpu.kvstore import dist as kd
    from mxnet_tpu.resilience import faults

    t0 = time.monotonic()
    try:
        with faults.inject("dist_connect_timeout", times=None):
            kd.init_distributed("127.0.0.1:9", num_processes=2, process_id=0,
                                timeout=1.0, max_retries=2, backoff=0.05)
        return False, "no TimeoutError raised"
    except TimeoutError:
        elapsed = time.monotonic() - t0
    return elapsed < 5.0, f"elapsed={elapsed:.2f}s"


def _operator_fleet(mx, serving):
    """Shared 2-replica fleet + candidate-params builder for the
    operator drills (stable prefix so rollout candidates name the same
    arguments the serving symbol binds)."""
    import numpy as np

    def factory():
        mx.random.seed(5)
        net = mx.gluon.nn.Dense(4, in_units=3, prefix="op_net_")
        net.initialize()
        return serving.Predictor.from_block(
            net, input_shapes={"data": (3,)}, batch_sizes=(2,),
            warmup=False)

    def candidate():
        mx.random.seed(5)
        net = mx.gluon.nn.Dense(4, in_units=3, prefix="op_net_")
        net.initialize()
        return {f"arg:{name}": p.data()
                for name, p in net.collect_params().items()}

    fleet = serving.Fleet(factory, replicas=2, probe_interval_ms=50,
                          breaker_k=2, retries=2, backoff_ms=1,
                          breaker_cooldown_ms=100,
                          server_kw={"batch_timeout_ms": 1.0})
    return fleet, candidate, np.ones((1, 3), np.float32)


def _drill_rollout_gate(mx, workdir, kind):
    """A canaried weight rollout meets a bad artifact: the injected
    fault poisons the candidate params with NaN (``rollout_bad_weights``
    — caught by the canary health gate) or inflates the measured canary
    latencies (``canary_slo_regression`` — caught by the SLO regression
    window). Either way the rollout must return ``rollback``, the prior
    artifact must keep serving bit-identical answers, and a client
    hammer riding through the whole window must see ZERO errors."""
    import itertools
    import threading
    import types

    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving import operator

    serving.reset_stats()
    fleet, candidate, x = _operator_fleet(mx, serving)
    gate = "health" if kind == "rollout_bad_weights" else "latency"
    # the canary windows read a clock the drill sets (every call takes
    # 1 ms, the fault's factor on top): on a loaded host, beside the
    # hammer, a clean baseline window can read several times the
    # candidate's, and the inflated p50 then passes the latency gate
    ticks = itertools.count()
    operator.time = types.SimpleNamespace(
        perf_counter=lambda: next(ticks) * 1e-3)
    try:
        if not fleet.wait_healthy(timeout=20):
            return False, "fleet never became healthy"
        baseline = fleet.submit(x, deadline_ms=10000).result(timeout=10)
        rm = serving.RolloutManager(fleet, eval_batch=x, canary_calls=4)
        results = {"ok": 0, "err": 0}
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    r = fleet.submit(x, deadline_ms=10000).result(
                        timeout=10)
                    results["ok"] += int(
                        np.array_equal(r[0], baseline[0]))
                except Exception:
                    results["err"] += 1

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        try:
            with faults.inject(kind, times=None) as f:
                res = rm.rollout_weights(candidate())
        finally:
            stop.set()
            t.join(timeout=10)
        after = fleet.submit(x, deadline_ms=10000).result(timeout=10)
        s = serving.stats()
        ok = (res["action"] == "rollback" and res.get("gate") == gate
              and f.fired >= 1 and results["err"] == 0
              and results["ok"] >= 1
              and s["rollout_rollbacks"] >= 1
              and s["rollout_promotions"] == 0
              and np.array_equal(after[0], baseline[0]))
        return ok, (f"action={res['action']} gate={res.get('gate')} "
                    f"fired={f.fired} client_ok={results['ok']} "
                    f"client_err={results['err']} "
                    f"rollbacks={s['rollout_rollbacks']}")
    finally:
        operator.time = time
        fleet.close()


def _drill_autoscale_flap(mx, workdir):
    """A maximally adversarial square-wave load signal hits the
    autoscaler every evaluation: hysteresis (distinct up/down
    thresholds) + per-direction cooldowns must bound the damage to AT
    MOST ONE scale event across the flap window — every other
    evaluation is a recorded HOLD — and the fleet keeps serving
    throughout."""
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults

    serving.reset_stats()
    fleet, _candidate, x = _operator_fleet(mx, serving)
    try:
        if not fleet.wait_healthy(timeout=20):
            return False, "fleet never became healthy"
        baseline = fleet.submit(x, deadline_ms=10000).result(timeout=10)
        asc = serving.Autoscaler(fleet, min_replicas=1, max_replicas=8,
                                 up_queue=4.0, down_queue=1.0,
                                 cooldown_s=3600.0)
        with faults.inject("autoscale_flap", times=None) as f:
            actions = [d["action"] for _ in range(8)
                       for d in asc.evaluate()]
        scale_events = sum(1 for a in actions if a != "hold")
        after = fleet.submit(x, deadline_ms=10000).result(timeout=10)
        s = serving.stats()
        ok = (f.fired == 8 and scale_events <= 1
              and actions.count("scale_down") == 0
              and s["fleet_scale_hold"] >= 6
              and fleet.replica_count() <= 3
              and np.array_equal(after[0], baseline[0]))
        return ok, (f"fired={f.fired} actions={actions} "
                    f"scale_events={scale_events} "
                    f"holds={s['fleet_scale_hold']} "
                    f"replicas={fleet.replica_count()}")
    finally:
        fleet.close()


def _decode_net(mx):
    """Tiny deterministic transformer LM + eager greedy reference for
    the decode drills.  The reference rolls the FULL context through the
    uncaptured block each token — the paged path must match it
    token-for-token (greedy argmax is deterministic)."""
    import numpy as np

    from mxnet_tpu.gluon.model_zoo.transformer import transformer_lm

    mx.random.seed(11)
    net = transformer_lm(vocab=40, units=24, num_heads=2, num_layers=1,
                         max_len=48)
    net.initialize()
    net(mx.nd.array(np.zeros((1, 8), np.int32), dtype="int32"))

    def ref_decode(prompt, n):
        seq = list(prompt)
        out = []
        for _ in range(n):
            logits = net(mx.nd.array(np.asarray([seq], np.int32),
                                     dtype="int32"))
            nxt = int(np.asarray(logits.asnumpy())[0, -1].argmax())
            out.append(nxt)
            seq.append(nxt)
        return out

    return net, ref_decode


def _drill_decode_replica_death(mx, workdir):
    """A decode replica dies mid-stream (fault raises inside its engine
    loop while a sequence is half-generated).  The StreamRouter must
    reroute the orphaned stream to the surviving replica — re-prefilling
    from the already-emitted tokens — and the client must receive the
    SAME token sequence as an uninterrupted greedy decode.  Afterwards
    ``revive()`` restores capacity and every KV page is back in the
    free pool."""
    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults

    serving.reset_stats()
    net, ref_decode = _decode_net(mx)

    def factory():
        return serving.DecodePredictor(net, page_size=4, num_pages=24,
                                       max_seqs=3, prefill_buckets=(8,),
                                       warmup=True)

    router = serving.StreamRouter(factory, replicas=2, ttft_slo_ms=60000)
    try:
        prompt = [5, 11, 23, 2]
        # fires on the victim engine loop's 3rd iteration — after TTFT,
        # mid-stream, with pages held
        with faults.inject("decode_replica_death", at_step=2,
                           times=1) as f:
            got = router.submit_stream(prompt, 12).result(timeout=120)
        expect = ref_decode(prompt, 12)
        live_after_death = router.live_replicas
        revived = router.revive()
        s = serving.stats()
        pages_held = sum(b.predictor.pool.in_use for b in router.replicas)
        ok = (got == expect and f.fired == 1
              and s["decode_reroutes"] >= 1
              and live_after_death == 1
              and revived == 1 and router.live_replicas == 2
              and pages_held == 0)
        return ok, (f"fired={f.fired} parity={got == expect} "
                    f"reroutes={s['decode_reroutes']} "
                    f"live_after_death={live_after_death} "
                    f"revived={revived} pages_held={pages_held}")
    finally:
        router.close()


def _drill_kv_pool_exhaustion(mx, workdir):
    """The paged KV pool reports zero free pages at admission time (the
    fault starves ``PagePool.alloc``).  Admission must BACKPRESSURE —
    the stream stays queued, nothing crashes, no partial allocation
    leaks — and once the fault clears the sequence is admitted and
    finishes token-for-token correct."""
    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults
    from mxnet_tpu.serving.batcher import DecodeBatcher

    serving.reset_stats()
    net, ref_decode = _decode_net(mx)
    pred = serving.DecodePredictor(net, page_size=4, num_pages=8,
                                   max_seqs=2, prefill_buckets=(8,),
                                   warmup=True)
    bat = DecodeBatcher(pred, ttft_slo_ms=60000)
    try:
        prompt = [7, 3, 29, 14]
        with faults.inject("kv_pool_exhaustion", at_step=0,
                           times=3) as f:
            got = bat.submit(prompt, 6).result(timeout=120)
        expect = ref_decode(prompt, 6)
        s = serving.stats()
        ok = (got == expect and f.fired >= 1
              and s["decode_backpressure"] >= 1
              and pred.pool.in_use == 0)
        return ok, (f"fired={f.fired} parity={got == expect} "
                    f"backpressure={s['decode_backpressure']} "
                    f"pages_held={pred.pool.in_use}")
    finally:
        bat.close()


# ------------------------------------------------ SDC / integrity drills

def _sdc_build_trainer(mx, seed, prefix, mesh_devs, dp, mgr=None):
    """A small sharded trainer with a FIXED prefix and seed, so a second
    build (the bitwise oracle) gets identical param names and init."""
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4, in_units=4, prefix=prefix)
    net.initialize()
    return ShardedTrainer(net, lambda p, l: ((p - l) ** 2),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1},
                          mesh=create_mesh({"dp": dp},
                                           (mesh_devs
                                            or jax.devices())[:dp]),
                          checkpoint_manager=mgr)


def _host_params(trainer):
    import numpy as np

    return {k: np.asarray(v) for k, v in trainer.params.items()}


def _params_equal(got, want):
    import numpy as np

    return (sorted(got) == sorted(want)
            and all(np.array_equal(got[k], want[k]) for k in got))


def _drill_sdc_transient(mx, workdir, kind):
    """Transient SDC (kinds ``sdc_bitflip_param`` / ``sdc_bitflip_grad``):
    one finite low-mantissa-bit flip in the post-step weights (fused
    path) or the accumulated gradient (microbatches=2 path) that no NaN
    sentinel can see. The shadow replay audit mismatches, every device
    passes the known-answer self-test (so NO quarantine), the step rolls
    back to the retained snapshot and re-runs — the final params are
    bitwise-equal to an un-faulted oracle run."""
    import numpy as np

    from mxnet_tpu.resilience import faults, integrity

    # the audit compiles replay executables inside the guarded step
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "120"
    saved = os.environ.get("MXNET_TPU_INTEGRITY_AUDIT_EVERY")
    os.environ["MXNET_TPU_INTEGRITY_AUDIT_EVERY"] = "1"
    accum = kind == "sdc_bitflip_grad"
    n = 2 if accum else None
    x = np.arange(64, dtype=np.float32).reshape(16, 4) / 64
    y = np.ones((16, 4), np.float32)
    try:
        before = integrity.stats()
        oracle = _sdc_build_trainer(mx, 17, "sdc_net_", None, 4)
        for _ in range(2):
            oracle.step(x, y, microbatches=n)
        want = _host_params(oracle)
        trainer = _sdc_build_trainer(mx, 17, "sdc_net_", None, 4)
        with faults.inject(kind, times=1) as f:
            trainer.step(x, y, microbatches=n)   # corrupt -> rollback
        trainer.step(x, y, microbatches=n)       # clean audited step
        bitwise = _params_equal(_host_params(trainer), want)
        d = {k: integrity.stats()[k] - before[k] for k in before}
        ok = (f.fired == 1 and bitwise
              and d["integrity_audit_mismatches"] >= 1
              and d["integrity_rollbacks"] >= 1
              and d["integrity_quarantined"] == 0
              and not integrity.quarantined_devices())
        return ok, (f"fired={f.fired} bitwise={bitwise} "
                    f"mismatches={d['integrity_audit_mismatches']} "
                    f"rollbacks={d['integrity_rollbacks']} "
                    f"quarantined={integrity.quarantined_devices()}")
    finally:
        if saved is None:
            os.environ.pop("MXNET_TPU_INTEGRITY_AUDIT_EVERY", None)
        else:
            os.environ["MXNET_TPU_INTEGRITY_AUDIT_EVERY"] = saved


def _drill_sdc_device_sticky(mx, workdir):
    """The end-to-end SDC gate: a sticky lying device corrupts every
    step while it participates in the mesh. The audit mismatches, the
    known-answer battery names exactly that chip, it is
    sticky-quarantined and excised through the existing mesh-shrink +
    reshardable-restore recovery (dp 4 -> 2); corruption stops the
    moment the quarantine takes effect, training resumes bitwise
    against an oracle trained on the shrunk mesh from the same
    checkpoint, and the ``sdc_detected`` alert opens an incident from
    the mismatch counters."""
    import numpy as np

    import jax
    from mxnet_tpu.observability import alerts
    from mxnet_tpu.resilience import CheckpointManager, faults, integrity

    if len(jax.devices()) < 4:
        return False, "needs >= 4 devices (xla_force_host_platform_device_count)"
    # recovery recompiles the step on the shrunk mesh inside the guarded
    # scope — the deadline must cover compile time, not just execution
    os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "120"
    saved = {k: os.environ.get(k) for k in
             ("MXNET_TPU_INTEGRITY_AUDIT_EVERY", "MXNET_TPU_FAULT_DEVICE")}
    os.environ["MXNET_TPU_INTEGRITY_AUDIT_EVERY"] = "1"
    os.environ["MXNET_TPU_FAULT_DEVICE"] = "0"
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    alerts.reset()
    prev_alerts = alerts.set_enabled(False)  # synthetic clock below
    before = integrity.stats()
    try:
        mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
        trainer = _sdc_build_trainer(mx, 19, "sdc_sticky_net_",
                                     jax.devices(), 4, mgr=mgr)
        trainer.step(x, y)                   # clean audited step 1
        mgr.save(1, trainer=trainer)
        t = 1000.0
        alerts.evaluate(now=t, force=True)   # clean counter baseline
        with faults.inject("sdc_device_sticky", times=None) as f:
            loss = trainer.step(x, y)  # corrupt -> quarantine -> shrink
        t += 30.0
        alerts.evaluate(now=t, force=True)
        fired = [i for i in alerts.open_incidents()
                 if i["rule"] == "sdc_detected"]
        new_dp = int(trainer.mesh.shape.get("dp", 0))
        live_ids = {int(d.id) for d in trainer.mesh.devices.flat}
        trainer.step(x, y)                   # resumes on the survivors
        got = _host_params(trainer)
        # shrunk-mesh oracle: the same checkpoint restored onto a clean
        # dp=2 mesh that excludes the victim, replaying steps 2..3
        mgr2 = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
        oracle = _sdc_build_trainer(mx, 19, "sdc_sticky_net_",
                                    jax.devices()[2:], 2, mgr=mgr2)
        mgr2.restore_latest(trainer=oracle)
        oracle.step(x, y)
        oracle.step(x, y)
        bitwise = _params_equal(got, _host_params(oracle))
        d = {k: integrity.stats()[k] - before[k] for k in before}
        ok = (f.fired >= 1 and np.isfinite(float(loss))
              and new_dp == 2 and 0 not in live_ids
              and integrity.quarantined_devices() == [0]
              and d["integrity_selftest_failures"] >= 1
              and d["integrity_quarantined"] == 1
              and len(fired) == 1 and bitwise)
        return ok, (f"dp 4->{new_dp} quarantined="
                    f"{integrity.quarantined_devices()} bitwise={bitwise} "
                    f"alert_open={len(fired) == 1} fired={f.fired}")
    finally:
        alerts.set_enabled(prev_alerts)
        alerts.reset()
        integrity.reset_state()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _drill_sdc_serving(mx, workdir):
    """A replica silently serves wrong-but-finite answers (one low bit
    flipped in its output — no NaN probe fires). The golden-query audit
    names exactly the lying replica, walks it through the fleet's
    DRAINING -> DEAD -> RESTARTING machinery, and the restarted replica
    passes a fresh audit bitwise."""
    import numpy as np

    from mxnet_tpu import serving
    from mxnet_tpu.resilience import faults, integrity

    saved = os.environ.get("MXNET_TPU_FAULT_REPLICA")
    os.environ["MXNET_TPU_FAULT_REPLICA"] = "0"

    def factory():
        mx.random.seed(23)
        net = mx.gluon.nn.Dense(4, in_units=3, prefix="sdc_fleet_net_")
        net.initialize()
        return serving.Predictor.from_block(
            net, input_shapes={"data": (3,)}, batch_sizes=(2,))

    serving.reset_stats()
    before = integrity.stats()
    try:
        x = np.ones((1, 3), np.float32)
        with serving.Fleet(factory, replicas=2, probe_interval_ms=50,
                           breaker_k=2, retries=2, backoff_ms=1,
                           breaker_cooldown_ms=100,
                           server_kw={"batch_timeout_ms": 1.0}) as fleet:
            fleet.wait_healthy(timeout=20)
            # golden answers from the known-good replica (rid 1)
            good = [r for r in fleet.replicas() if r.rid == 1][0]
            golden = good.submit(x).result(timeout=10)
            clean = integrity.audit_serving(fleet, x, golden)
            with faults.inject("sdc_serving", times=None) as f:
                failed = integrity.audit_serving(fleet, x, golden)
            recovered = fleet.wait_healthy(timeout=20)
            after = integrity.audit_serving(fleet, x, golden)
        s = serving.stats()
        d = {k: integrity.stats()[k] - before[k] for k in before}
        ok = (clean == [] and failed == [0] and f.fired >= 1
              and recovered and after == []
              and d["integrity_serving_failures"] >= 1
              and s["fleet_restarts"] >= 1)
        return ok, (f"failed={failed} recovered={recovered} "
                    f"after={after} restarts={s['fleet_restarts']} "
                    f"fired={f.fired}")
    finally:
        if saved is None:
            os.environ.pop("MXNET_TPU_FAULT_REPLICA", None)
        else:
            os.environ["MXNET_TPU_FAULT_REPLICA"] = saved


def _drill_preempt(mx, workdir):
    """A preemption notice (the drillable twin of the SIGTERM trap): the
    trainer finishes the in-flight step, publishes an emergency async
    checkpoint, and exits cleanly via ``integrity.Preempted``; a fresh
    trainer restores exactly the drained state and resumes."""
    import numpy as np

    import jax
    from mxnet_tpu.resilience import CheckpointManager, faults, integrity

    before = integrity.stats()
    mgr = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
    trainer = _sdc_build_trainer(mx, 29, "preempt_net_",
                                 jax.devices(), 2, mgr=mgr)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    trainer.step(x, y)
    caught = None
    with faults.inject("preempt", times=1) as f:
        try:
            trainer.step(x, y)
        except integrity.Preempted as e:
            caught = e
    want = _host_params(trainer)  # the drained (post-step-2) state
    mgr2 = CheckpointManager(os.path.join(workdir, "ckpt"), keep_n=3)
    resumed = _sdc_build_trainer(mx, 29, "preempt_net_",
                                 jax.devices(), 2, mgr=mgr2)
    manifest = mgr2.restore_latest(trainer=resumed)
    bitwise = (manifest is not None
               and _params_equal(_host_params(resumed), want))
    resumed.step(x, y)            # training resumes past the drain
    d = {k: integrity.stats()[k] - before[k] for k in before}
    ok = (f.fired == 1 and caught is not None
          and getattr(caught, "step", None) == 2
          and getattr(caught, "code", 1) == 0
          and manifest is not None and manifest["step"] == 2
          and bitwise and d["integrity_preempt_exits"] >= 1
          and not integrity.preempt_requested())
    return ok, (f"fired={f.fired} step={getattr(caught, 'step', None)} "
                f"restored={None if manifest is None else manifest['step']} "
                f"bitwise={bitwise}")


def _dispatch_drill(mx, kind, tmp):
    if kind == "nan_grad":
        return _drill_nan_grad(mx, tmp)
    if kind in ("ckpt_enospc", "ckpt_partial_write",
                "ckpt_shard_corrupt", "ckpt_crash_before_manifest"):
        return _drill_ckpt(mx, tmp, kind)
    if kind == "ckpt_async_crash":
        return _drill_ckpt_async_crash(mx, tmp)
    if kind == "peer_death_recover":
        return _drill_peer_death_recover(mx, tmp)
    if kind == "peer_death_multiaxis":
        return _drill_peer_death_multiaxis(mx, tmp)
    if kind == "host_death":
        return _drill_host_death(mx, tmp)
    if kind == "host_hang_collective":
        return _drill_host_hang_collective(mx, tmp)
    if kind == "coordinator_loss":
        return _drill_coordinator_loss(mx, tmp)
    if kind == "ckpt_partial_pod":
        return _drill_ckpt_partial_pod(mx, tmp)
    if kind == "hang_step":
        return _drill_hang_step(mx, tmp)
    if kind == "hang_collective":
        return _drill_hang_collective(mx, tmp)
    if kind == "hang_batch":
        return _drill_hang_batch(mx, tmp)
    if kind == "nan_serving":
        return _drill_nan_serving(mx, tmp)
    if kind == "peer_death":
        return _drill_peer_death(mx, tmp)
    if kind == "oom_step":
        return _drill_oom_step(mx, tmp)
    if kind == "dist_connect_timeout":
        return _drill_dist_connect_timeout(mx, tmp)
    if kind == "capture_step":
        return _drill_capture_step(mx, tmp)
    if kind in ("replica_crash", "replica_hang", "replica_nan_storm"):
        return _drill_replica_fault(mx, tmp, kind)
    if kind == "int8_calib_mismatch":
        return _drill_int8_calib_mismatch(mx, tmp)
    if kind == "perf_regression":
        return _drill_perf_regression(mx, tmp)
    if kind == "slo_burn":
        return _drill_slo_burn(mx, tmp)
    if kind == "step_time_anomaly":
        return _drill_step_time_anomaly(mx, tmp)
    if kind == "record_corrupt":
        return _drill_record_corrupt(mx, tmp)
    if kind == "nonfinite_grad":
        return _drill_nonfinite_grad(mx, tmp)
    if kind in ("rollout_bad_weights", "canary_slo_regression"):
        return _drill_rollout_gate(mx, tmp, kind)
    if kind == "autoscale_flap":
        return _drill_autoscale_flap(mx, tmp)
    if kind == "decode_replica_death":
        return _drill_decode_replica_death(mx, tmp)
    if kind == "kv_pool_exhaustion":
        return _drill_kv_pool_exhaustion(mx, tmp)
    if kind in ("sdc_bitflip_param", "sdc_bitflip_grad"):
        return _drill_sdc_transient(mx, tmp, kind)
    if kind == "sdc_device_sticky":
        return _drill_sdc_device_sticky(mx, tmp)
    if kind == "sdc_serving":
        return _drill_sdc_serving(mx, tmp)
    if kind == "preempt":
        return _drill_preempt(mx, tmp)
    raise ValueError(f"unknown chaos kind {kind!r}")


def run_kind(kind, workdir=None):
    """Run one chaos drill; returns (recovered: bool, detail: str).
    Faults/peers/env are reset around the drill. On top of the drill's
    own recovery check, the fault must have left a matching
    flight-recorder event (docs/observability.md) — no silent
    injections."""
    from mxnet_tpu.observability import flight as _obs_flight
    from mxnet_tpu.resilience import faults, integrity, watchdog

    mx = _mx()
    saved_env = {k: os.environ.get(k) for k in _ENV}
    os.environ.update(_ENV)
    faults.reset()
    watchdog.reset_peers()
    watchdog.reset_pod()
    integrity.reset_state()
    tmp = workdir or tempfile.mkdtemp(prefix="chaos_")
    mark = _obs_flight.last_seq()
    try:
        ok, detail = _dispatch_drill(mx, kind, tmp)
        missing = _flight_missing(kind, mark)
        if missing:
            ok = False
            detail += (f"; NO flight-recorder fault event for {missing} "
                       "(every injected fault must leave a trail)")
        elif missing is not None:
            detail += "; flight=ok"
        return ok, detail
    finally:
        faults.reset()
        watchdog.reset_peers()
        watchdog.reset_pod()
        integrity.reset_state()
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        if workdir is None:
            shutil.rmtree(tmp, ignore_errors=True)


# ----------------------------------------------------------- overhead gate

def watchdog_overhead_pct(steps=200, trials=5):
    """Per-step overhead of an ARMED step watchdog on the un-faulted
    eager CPU path. Armed and bare trials are INTERLEAVED (best-of-N
    each) so background-load drift between two long separate loops
    cannot masquerade as watchdog cost. Acceptance: <= 5%."""
    mx = _mx()

    def run(step):
        t0 = time.perf_counter()
        for k in range(steps):
            step(k)
        mx.nd.waitall()
        return (time.perf_counter() - t0) / steps

    _, _, step = _trainer(mx)
    for k in range(10):
        step(k)  # warmup / compile
    bare = armed = 1e9
    prior = os.environ.get("MXNET_TPU_WATCHDOG_STEP_TIMEOUT")
    try:
        for _ in range(trials):
            os.environ.pop("MXNET_TPU_WATCHDOG_STEP_TIMEOUT", None)
            bare = min(bare, run(step))
            os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = "300"
            armed = min(armed, run(step))
    finally:
        if prior is None:  # restore, don't disarm a configured watchdog
            os.environ.pop("MXNET_TPU_WATCHDOG_STEP_TIMEOUT", None)
        else:
            os.environ["MXNET_TPU_WATCHDOG_STEP_TIMEOUT"] = prior
    return max(0.0, (armed - bare) / bare * 100.0), bare, armed


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--kinds", default=",".join(FAST_KINDS),
                    help="comma list of fault kinds to drill")
    ap.add_argument("--steps", type=int, default=200,
                    help="steps for the overhead measurement")
    ap.add_argument("--skip-overhead", action="store_true")
    args = ap.parse_args(argv)

    kinds = [k for k in args.kinds.split(",") if k]
    per_kind = {}
    for kind in kinds:
        t0 = time.monotonic()
        try:
            ok, detail = run_kind(kind)
        except Exception as e:  # a crashed drill is a failed drill
            ok, detail = False, f"{type(e).__name__}: {e}"
        elapsed = time.monotonic() - t0
        per_kind[kind] = {"recovered": bool(ok), "detail": detail,
                          "elapsed_s": round(elapsed, 2)}
        print(f"{kind}: {'recovered' if ok else 'FAILED'} ({detail}, "
              f"{elapsed:.2f}s)", file=sys.stderr)

    overhead = None
    gate_ok = True
    if not args.skip_overhead:
        overhead, bare, armed = watchdog_overhead_pct(args.steps)
        if overhead > 5.0:
            # one re-measure: interleaved best-of-N absorbs steady
            # background load, but not a burst on exactly one side
            overhead, bare, armed = watchdog_overhead_pct(args.steps)
        gate_ok = overhead <= 5.0
        print(f"watchdog overhead: {overhead:.2f}% "
              f"(bare {bare * 1e3:.3f} ms/step, armed {armed * 1e3:.3f} "
              f"ms/step, gate 5%)", file=sys.stderr)

    recovered = sum(1 for v in per_kind.values() if v["recovered"])
    print(json.dumps({
        "metric": "chaos_recovered_kinds",
        "value": recovered,
        "unit": "kinds",
        "extra": {
            "total": len(per_kind),
            "per_kind": per_kind,
            "watchdog_overhead_pct": (None if overhead is None
                                      else round(overhead, 2)),
            "overhead_gate_pct": 5.0,
        },
    }))
    return 0 if (recovered == len(per_kind) and gate_ok) else 1


if __name__ == "__main__":
    sys.exit(main())
