"""Whole-program step capture + persistent AOT compile cache
(mxnet_tpu/capture.py, docs/capture.md).

Acceptance (ISSUE 7): captured Trainer and ShardedTrainer steps are
bitwise-equal to the existing eager/bulk path (dp=1 and dp=8),
kill-resume stays bitwise under capture, the chaos drills pass with
capture enabled, and the AOT cache round-trips with stale/corrupt
artifacts falling back to a fresh compile.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import capture, profiler
from mxnet_tpu.resilience import CheckpointManager, HealthSentinel, faults

pytestmark = pytest.mark.capture

NIN, NOUT, BS = 8, 4, 8


def _loss_fn(out, y):
    return ((out - y) ** 2).sum()


def _build_gluon(seed=0, opt="adam", opt_params=None, prefix="cap_"):
    mx.random.seed(seed)
    net = mx.gluon.nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(16, activation="relu"))
        net.add(mx.gluon.nn.Dense(NOUT))
    net.initialize()
    net(mx.nd.zeros((2, NIN)))  # materialize params
    trainer = mx.gluon.Trainer(
        net.collect_params(), opt,
        dict(opt_params or {"learning_rate": 1e-3}))
    return net, trainer


def _batch(k):
    rs = np.random.RandomState(100 + k)
    return (mx.nd.array(rs.rand(BS, NIN).astype(np.float32)),
            mx.nd.ones((BS, NOUT)))


def _params_np(net):
    return {k: v.asnumpy().copy()
            for k, v in net._collect_params_with_prefix().items()}


def _assert_bitwise(a, b):
    assert set(a) == set(b)
    for k in a:
        assert np.array_equal(a[k], b[k]), k


def _eager_run(steps, opt="adam", opt_params=None, sentinel=None):
    net, trainer = _build_gluon(opt=opt, opt_params=opt_params)
    if sentinel is not None:
        sentinel.attach(trainer)
    losses = []
    for k in range(steps):
        x, y = _batch(k)
        with mx.autograd.record():
            loss = _loss_fn(net(x), y)
        loss.backward()
        trainer.step(BS)
        losses.append(loss.asnumpy())
    return net, trainer, losses


@pytest.fixture(autouse=True)
def _fresh_capture_state():
    capture.reset_stats()
    capture.clear_retrace_log()
    faults.reset()
    yield
    capture.reset_stats()
    capture.clear_retrace_log()
    faults.reset()


# ----------------------------------------------------------------- bitwise

@pytest.mark.parametrize("opt,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    # Adam: lr/bias-correction scalars drift every step — the dynamic
    # scalar operands + per-step replay must track them exactly
    ("adam", {"learning_rate": 1e-3}),
])
def test_captured_step_bitwise_vs_eager_bulk(opt, opt_params, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_BULK_OPT_UPDATES", "16")
    ref_net, ref_trainer, ref_losses = _eager_run(5, opt, opt_params)
    monkeypatch.delenv("MXNET_TPU_BULK_OPT_UPDATES")

    net, trainer = _build_gluon(opt=opt, opt_params=opt_params)
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    losses = []
    for k in range(5):
        x, y = _batch(k)
        losses.append(step(x, y, batch_size=BS).asnumpy())

    # the bitwise contract is the training trajectory: weights and
    # optimizer state
    _assert_bitwise(_params_np(ref_net), _params_np(net))
    assert trainer.get_states_bytes() == ref_trainer.get_states_bytes()
    # the REPORTED loss is held to float32 ULPs, not bits: the captured
    # program fuses the last Dense's bias add into the loss reduction
    # (one XLA-CPU loop: reduce(((dot + b) - y)^2)), where eager rounds
    # `out` to memory first — 31.992929 vs 31.992928, one ULP, with the
    # float64 sum between them. No gradient flows through that
    # reduction, which is why the state above stays exact
    # (docs/capture.md).
    for lr_, lc in zip(ref_losses, losses):
        np.testing.assert_allclose(lc, lr_, rtol=4 * np.finfo(np.float32).eps)
    s = capture.stats()
    assert s["capture_steps"] == 5
    assert s["capture_misses"] == 1 and s["capture_hits"] == 4
    assert s["capture_retraces"] == 0


def test_captured_sharded_step_bitwise_dp8():
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")

    def build(seed=13):
        mx.random.seed(seed)
        net = mx.gluon.nn.Dense(NOUT, in_units=NIN, prefix="capdp_")
        net.initialize()
        return ShardedTrainer(net, lambda p, l: ((p - l) ** 2),
                              optimizer="sgd",
                              optimizer_params={"learning_rate": 0.1,
                                                "momentum": 0.9},
                              mesh=create_mesh({"dp": 8}, jax.devices()))

    def batches():
        for k in range(4):
            rs = np.random.RandomState(200 + k)
            yield (rs.rand(8, NIN).astype(np.float32),
                   np.ones((8, NOUT), np.float32))

    ref = build()
    ref_losses = [np.asarray(ref.step(x, y)) for x, y in batches()]

    tr = build()
    step = capture.capture(tr)
    losses = [np.asarray(step(x, y)) for x, y in batches()]

    for k in ref.params:
        assert np.array_equal(np.asarray(ref.params[k]),
                              np.asarray(tr.params[k])), k
    for lr_, lc in zip(ref_losses, losses):
        assert np.array_equal(lr_, lc)
    assert capture.stats()["capture_steps"] == 4


def test_capture_kill_switch_runs_eager(monkeypatch):
    ref_net, ref_trainer, _ = _eager_run(3)
    monkeypatch.setenv("MXNET_TPU_CAPTURE", "0")
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    for k in range(3):
        x, y = _batch(k)
        step(x, y, batch_size=BS)
    _assert_bitwise(_params_np(ref_net), _params_np(net))
    assert trainer.get_states_bytes() == ref_trainer.get_states_bytes()
    s = capture.stats()
    assert s["capture_fallback_eager"] == 3 and s["capture_misses"] == 0


# ------------------------------------------------------- retrace forensics

def test_retrace_forensics_on_signature_change():
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    x, y = _batch(0)
    step(x, y, batch_size=BS)
    assert capture.stats()["capture_retraces"] == 0
    # half batch: new signature -> recompile WITH a structured reason
    step(mx.nd.array(x.asnumpy()[:4]), mx.nd.array(y.asnumpy()[:4]),
         batch_size=4)
    s = capture.stats()
    assert s["capture_retraces"] == 1 and s["capture_misses"] == 2
    log = capture.retrace_log()
    assert len(log) == 1
    assert log[0]["label"] == "trainer_step"
    assert "changed" in log[0]["reason"]
    # the reason lands in the dispatch ring -> watchdog crash reports
    ring = [e["op"] for e in profiler.dispatch_ring()]
    assert any(e.startswith("capture_retrace:trainer_step:") for e in ring)


def test_retrace_on_checkpoint_restore_rebinds_state(tmp_path):
    # reference: eager run with a mid-run save/restore
    ref_net, ref_trainer = _build_gluon()
    mgr_ref = CheckpointManager(tmp_path / "ref", keep_n=2)
    net, trainer = _build_gluon()
    mgr = CheckpointManager(tmp_path / "cap", keep_n=2)
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)

    def eager_step(k):
        x, y = _batch(k)
        with mx.autograd.record():
            loss = _loss_fn(ref_net(x), y)
        loss.backward()
        ref_trainer.step(BS)

    eager_step(0)
    mgr_ref.save(1, net=ref_net, trainer=ref_trainer)
    eager_step(1)
    mgr_ref.restore_latest(net=ref_net, trainer=ref_trainer)
    eager_step(2)

    x, y = _batch(0)
    step(x, y, batch_size=BS)
    mgr.save(1, net=net, trainer=trainer)
    x, y = _batch(1)
    step(x, y, batch_size=BS)
    # restore rebinds the updater state dict: the captured entry must
    # re-discover its state cells, not silently read the orphaned ones
    mgr.restore_latest(net=net, trainer=trainer)
    x, y = _batch(2)
    step(x, y, batch_size=BS)
    _assert_bitwise(_params_np(ref_net), _params_np(net))
    assert ref_trainer.get_states_bytes() == trainer.get_states_bytes()
    assert any("rebound" in e["reason"] for e in capture.retrace_log())


def test_sharded_recapture_notes_hyperparam_rebind():
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    mx.random.seed(7)
    net = mx.gluon.nn.Dense(NOUT, in_units=NIN, prefix="caplr_")
    net.initialize()
    tr = ShardedTrainer(net, lambda p, l: ((p - l) ** 2), optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1},
                        mesh=create_mesh({"dp": 1}, jax.devices()[:1]))
    step = capture.capture(tr)
    x = np.arange(8 * NIN, dtype=np.float32).reshape(8, NIN) / 64
    y = np.ones((8, NOUT), np.float32)
    step(x, y)
    tr.set_learning_rate(0.01)  # hyperparams are baked into the program
    step(x, y)
    assert any("rebind" in e["reason"] for e in capture.retrace_log())


def test_capture_check_every_sampling_matches_eager():
    """HealthSentinel(check_every=N): captured must keep eager's
    sampling — an unhealthy batch on an OFF-cadence step updates the
    weights (eager before_update never looks at it), and sentinel
    counters only move on check steps."""
    from mxnet_tpu.resilience import sentinel as _sentinel

    def poisoned(k):
        x, y = _batch(k)
        if k == 1:  # off-cadence under check_every=2 (checks at 1,3,..)
            x = mx.nd.array(x.asnumpy() * np.float32("nan"))
        return x, y

    # eager reference
    _sentinel.reset_stats()
    net_r, trainer_r = _build_gluon()
    HealthSentinel(policy="skip_batch", check_every=2).attach(trainer_r)
    for k in range(4):
        x, y = poisoned(k)
        with mx.autograd.record():
            loss = _loss_fn(net_r(x), y)
        loss.backward()
        trainer_r.step(BS)
    eager_stats = {k: v for k, v in _sentinel.stats().items() if v}
    ref = _params_np(net_r)
    assert not all(np.isfinite(v).all() for v in ref.values())  # NaN went in

    _sentinel.reset_stats()
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn,
                           sentinel=HealthSentinel(policy="skip_batch",
                                                   check_every=2))
    for k in range(4):
        x, y = poisoned(k)
        step(x, y, batch_size=BS)
    # NaN params compare equal via bitpattern
    got = _params_np(net)
    for k in ref:
        assert np.array_equal(ref[k], got[k], equal_nan=True), k
    assert {k: v for k, v in _sentinel.stats().items() if v} == eager_stats


def test_capture_grad_norm_trip_counter():
    from mxnet_tpu.resilience import sentinel as _sentinel

    _sentinel.reset_stats()
    net, trainer = _build_gluon()
    step = capture.capture(
        trainer, net=net, loss_fn=_loss_fn,
        sentinel=HealthSentinel(policy="skip_batch",
                                grad_norm_threshold=1e-9))
    x, y = _batch(0)
    before = _params_np(net)
    step(x, y, batch_size=BS)  # finite grads, but norm >> 1e-9
    s = _sentinel.stats()
    assert s["sentinel_grad_norm_trips"] == 1 and s["sentinel_nonfinite"] == 0
    _assert_bitwise(before, _params_np(net))  # update gated


def test_kill_switch_scaler_path_keeps_watchdog(monkeypatch):
    """MXNET_TPU_CAPTURE=0 with a loss scaler: the eager fallback must
    still arm the step watchdog and honor the hang_step drill."""
    from mxnet_tpu.amp.loss_scaler import LossScaler
    from mxnet_tpu.resilience import StallError

    monkeypatch.setenv("MXNET_TPU_CAPTURE", "0")
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_STEP_TIMEOUT", "0.5")
    monkeypatch.setenv("MXNET_TPU_FAULT_HANG_CAP", "10")
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn,
                           loss_scaler=LossScaler())
    x, y = _batch(0)
    step(x, y, batch_size=BS)
    with faults.inject("hang_step"):
        with pytest.raises(StallError):
            step(x, y, batch_size=BS)
    step(x, y, batch_size=BS)  # training continues


# ------------------------------------------------------------- kill-resume

def test_kill_resume_bitwise_under_capture(tmp_path):
    total = 6
    # uninterrupted captured run
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    for k in range(total):
        x, y = _batch(k)
        step(x, y, batch_size=BS)
    ref_params = _params_np(net)
    ref_states = trainer.get_states_bytes()

    # crashed run: checkpoint each step, die during the 4th save
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    mgr = CheckpointManager(tmp_path, keep_n=2)
    with faults.inject("ckpt_crash_before_manifest", at_step=3):
        with pytest.raises(faults.SimulatedCrash):
            for k in range(total):
                x, y = _batch(k)
                step(x, y, batch_size=BS)
                mgr.save(k + 1, net=net, trainer=trainer)

    # resume in a "fresh process": new net/trainer/captured step
    net, trainer = _build_gluon(seed=12345)
    manifest = CheckpointManager(tmp_path).restore_latest(
        net=net, trainer=trainer)
    assert manifest["step"] == 3
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    for k in range(manifest["step"], total):
        x, y = _batch(k)
        step(x, y, batch_size=BS)
    _assert_bitwise(ref_params, _params_np(net))
    assert trainer.get_states_bytes() == ref_states


# ------------------------------------------------- chaos drills w/ capture

def test_capture_nan_grad_skip_batch_gates_weights():
    from mxnet_tpu.resilience import sentinel as _sentinel

    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn,
                           sentinel=HealthSentinel(policy="skip_batch"))
    x, y = _batch(0)
    step(x, y, batch_size=BS)  # compile + one clean step
    before = _params_np(net)
    states_before = trainer.get_states_bytes()
    with faults.inject("nan_grad") as f:
        step(x, y, batch_size=BS)
    assert f.fired == 1
    # the in-program select gated every weight AND optimizer-state write
    _assert_bitwise(before, _params_np(net))
    assert trainer.get_states_bytes() == states_before
    assert _sentinel.stats()["sentinel_nonfinite"] >= 1
    after = step(x, y, batch_size=BS)  # clean step trains again
    assert np.isfinite(after.asnumpy()).all()
    assert not all(np.array_equal(before[k], v)
                   for k, v in _params_np(net).items())


def test_capture_hang_step_rollback(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_STEP_TIMEOUT", "0.5")
    monkeypatch.setenv("MXNET_TPU_FAULT_HANG_CAP", "10")
    net, trainer = _build_gluon()
    mgr = CheckpointManager(tmp_path, keep_n=2)
    sent = HealthSentinel(policy="rollback", checkpoint_manager=mgr)
    sent.attach(trainer, net=net)
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    x, y = _batch(0)
    step(x, y, batch_size=BS)  # compile outside the armed guard
    mgr.save(1, net=net, trainer=trainer)
    saved = _params_np(net)
    with faults.inject("hang_step"):
        out = step(x, y, batch_size=BS)  # stalls -> rollback -> skipped
    assert out is None
    _assert_bitwise(saved, _params_np(net))
    step(x, y, batch_size=BS)  # training continues


def test_capture_oom_step_elastic_sharded():
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import elastic

    mx.random.seed(7)
    net = mx.gluon.nn.Dense(NOUT, in_units=NIN, prefix="capoom_")
    net.initialize()
    tr = ShardedTrainer(net, lambda p, l: ((p - l) ** 2), optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1},
                        mesh=create_mesh({"dp": 1}, jax.devices()[:1]))
    step = capture.capture(tr)
    x = np.arange(8 * NIN, dtype=np.float32).reshape(8, NIN) / 64
    y = np.ones((8, NOUT), np.float32)
    with faults.inject("oom_step", times=1) as f:
        loss = step(x, y)
    assert f.fired == 1 and np.isfinite(float(loss))
    assert tr._elastic_n == 2  # sticky microbatch accumulation
    step(x, y)
    assert elastic.stats()["elastic_shrinks"] >= 1
    # the elastic grad/apply programs compiled through the capture path
    assert capture.stats()["capture_misses"] >= 2


def test_capture_peer_death_recover(tmp_path, monkeypatch):
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer
    from mxnet_tpu.resilience import watchdog

    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 virtual devices")
    # recovery recompiles on the shrunk mesh inside the guarded step
    monkeypatch.setenv("MXNET_TPU_WATCHDOG_STEP_TIMEOUT", "120")
    dp = 4
    mx.random.seed(13)
    net = mx.gluon.nn.Dense(NOUT, in_units=NIN, prefix="cappeer_")
    net.initialize()
    mgr = CheckpointManager(tmp_path, keep_n=3)
    tr = ShardedTrainer(net, lambda p, l: ((p - l) ** 2), optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1},
                        mesh=create_mesh({"dp": dp}, jax.devices()[:dp]),
                        checkpoint_manager=mgr)
    step = capture.capture(tr)
    x = np.arange(8 * NIN, dtype=np.float32).reshape(8, NIN) / 64
    y = np.ones((8, NOUT), np.float32)
    step(x, y)
    mgr.save(1, trainer=tr)
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with faults.inject("peer_death"):
            loss = step(x, y)  # dies -> shrinks -> restores -> re-runs
    watchdog.reset_peers()
    assert int(tr.mesh.shape.get("dp", 0)) == dp // 2
    assert np.isfinite(float(loss))
    step(x, y)  # training continues on the survivors
    assert watchdog.stats()["watchdog_peer_recoveries"] >= 1
    # the shrunk-mesh rebuild is a recorded re-capture, never silent
    assert any("rebind" in e["reason"] for e in capture.retrace_log())


# ----------------------------------------------------------- AOT cache

def _simple_fn():
    import jax.numpy as jnp

    def f(a, b):
        return jnp.tanh(a) @ b + 1.0

    rs = np.random.RandomState(0)
    return f, (rs.rand(4, 4).astype(np.float32),
               rs.rand(4, 4).astype(np.float32))


def _artifact_paths(cache_root):
    return sorted(
        os.path.join(cache_root, "programs", n)
        for n in os.listdir(os.path.join(cache_root, "programs")))


def test_aot_cache_round_trip(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    f, args = _simple_fn()
    ex = capture.aot_compile(f, label="t", fingerprint="fp",
                             example_args=args)
    cold = np.asarray(ex(*args))
    s = capture.stats()
    assert s["aot_cache_misses"] == 1 and s["aot_cache_writes"] == 1
    assert len(_artifact_paths(tmp_path)) == 1

    capture.reset_stats()
    ex2 = capture.aot_compile(f, label="t", fingerprint="fp",
                              example_args=args)
    warm = np.asarray(ex2(*args))
    s = capture.stats()
    assert s["aot_cache_hits"] == 1 and s["aot_cache_misses"] == 0
    assert np.array_equal(cold, warm)


def test_aot_cache_stale_version_falls_back(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    f, args = _simple_fn()
    ex = capture.aot_compile(f, label="t", fingerprint="fp",
                             example_args=args)
    want = np.asarray(ex(*args))
    [path] = _artifact_paths(tmp_path)
    # rewrite the header as if an older jax had produced the artifact
    with open(path, "rb") as fh:
        blob = fh.read()
    magic = b"MXTPUAOT1\n"
    hlen = int.from_bytes(blob[len(magic):len(magic) + 4], "big")
    header = json.loads(blob[len(magic) + 4:len(magic) + 4 + hlen])
    header["jax"] = "0.0.0"
    hbytes = json.dumps(header, sort_keys=True).encode()
    with open(path, "wb") as fh:
        fh.write(magic + len(hbytes).to_bytes(4, "big") + hbytes
                 + blob[len(magic) + 4 + hlen:])

    capture.reset_stats()
    ex2 = capture.aot_compile(f, label="t", fingerprint="fp",
                              example_args=args)
    s = capture.stats()
    assert s["aot_cache_stale"] == 1 and s["aot_cache_hits"] == 0
    assert s["aot_cache_writes"] == 1  # recompiled in place
    assert np.array_equal(want, np.asarray(ex2(*args)))


@pytest.mark.parametrize("how", ["flip_payload", "truncate", "garbage"])
def test_aot_cache_corrupt_artifact_falls_back(tmp_path, monkeypatch, how):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    f, args = _simple_fn()
    ex = capture.aot_compile(f, label="t", fingerprint="fp",
                             example_args=args)
    want = np.asarray(ex(*args))
    [path] = _artifact_paths(tmp_path)
    with open(path, "rb") as fh:
        blob = bytearray(fh.read())
    if how == "flip_payload":
        blob[-1] ^= 0xFF
    elif how == "truncate":
        blob = blob[:len(blob) // 2]
    else:
        blob = b"not an artifact"
    with open(path, "wb") as fh:
        fh.write(bytes(blob))

    capture.reset_stats()
    ex2 = capture.aot_compile(f, label="t", fingerprint="fp",
                              example_args=args)
    s = capture.stats()
    assert s["aot_cache_corrupt"] == 1 and s["aot_cache_hits"] == 0
    assert np.array_equal(want, np.asarray(ex2(*args)))


def test_aot_cache_size_cap_evicts(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE_MAX_MB", "0.000001")
    f, args = _simple_fn()
    capture.aot_compile(f, label="t", fingerprint="fp1", example_args=args)
    capture.aot_compile(f, label="t", fingerprint="fp2", example_args=args)
    assert capture.stats()["aot_cache_evictions"] >= 1


def test_aot_cache_salt_changes_key(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    cache = capture.compile_cache()
    k1 = cache.key("t", "fp", ("sig",))
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE_SALT", "v2")
    assert cache.key("t", "fp", ("sig",)) != k1


def test_aot_fingerprint_keys_computation_structure(tmp_path, monkeypatch):
    """Identical param avals, different math: an activation or loss-body
    change MUST miss the cache — a hit would silently serve the wrong
    compiled program."""
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))

    def run(act, loss_fn):
        mx.random.seed(3)
        net = mx.gluon.nn.Dense(NOUT, in_units=NIN, activation=act,
                                prefix="capfp_")
        net.initialize()
        net(mx.nd.zeros((2, NIN)))
        trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.1})
        step = capture.capture(trainer, net=net, loss_fn=loss_fn)
        x, y = _batch(0)
        return step(x, y, batch_size=BS).asnumpy()

    l_relu = run("relu", _loss_fn)
    capture.reset_stats()
    l_tanh = run("tanh", _loss_fn)
    s = capture.stats()
    assert s["aot_cache_hits"] == 0 and s["aot_cache_misses"] >= 1
    assert not np.array_equal(l_relu, l_tanh)
    capture.reset_stats()
    run("tanh", lambda out, y: ((out - y) ** 2).mean())  # new loss body
    s = capture.stats()
    assert s["aot_cache_hits"] == 0 and s["aot_cache_misses"] >= 1


def test_stall_without_rollback_restores_opt_bookkeeping(monkeypatch):
    """A stalled captured step with no rollback sentinel re-raises — and
    must un-advance the scalar replay's num_update/Adam-t so a caller
    that catches the stall keeps bitwise parity with eager."""
    from mxnet_tpu.resilience import StallError

    monkeypatch.setenv("MXNET_TPU_WATCHDOG_STEP_TIMEOUT", "0.5")
    monkeypatch.setenv("MXNET_TPU_FAULT_HANG_CAP", "10")
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    x, y = _batch(0)
    step(x, y, batch_size=BS)
    assert trainer._optimizer.num_update == 1
    states = trainer.get_states_bytes()
    with faults.inject("hang_step"):
        with pytest.raises(StallError):
            step(x, y, batch_size=BS)
    assert trainer._optimizer.num_update == 1
    assert trainer.get_states_bytes() == states


def test_captured_trainer_aot_warm_bitwise(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    net, trainer = _build_gluon()
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    for k in range(3):
        x, y = _batch(k)
        step(x, y, batch_size=BS)
    cold = _params_np(net)
    assert capture.stats()["aot_cache_writes"] >= 1

    capture.reset_stats()
    net, trainer = _build_gluon()  # "new process": fresh everything
    step = capture.capture(trainer, net=net, loss_fn=_loss_fn)
    for k in range(3):
        x, y = _batch(k)
        step(x, y, batch_size=BS)
    assert capture.stats()["aot_cache_hits"] >= 1
    _assert_bitwise(cold, _params_np(net))


def test_predictor_aot_cache_cold_start(tmp_path, monkeypatch):
    from mxnet_tpu import serving

    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", str(tmp_path))
    mx.random.seed(5)
    net = mx.gluon.nn.Dense(NOUT, in_units=NIN)
    net.initialize()
    x = np.random.RandomState(3).rand(2, NIN).astype(np.float32)
    pred = serving.Predictor.from_block(net, input_shapes={"data": (NIN,)},
                                        batch_sizes=(4,))
    cold = pred.predict(x)[0]
    assert capture.stats()["aot_cache_writes"] >= 1

    capture.reset_stats()
    pred2 = serving.Predictor.from_block(net, input_shapes={"data": (NIN,)},
                                         batch_sizes=(4,))
    warm = pred2.predict(x)[0]
    assert capture.stats()["aot_cache_hits"] >= 1
    assert np.array_equal(cold, warm)


# ------------------------------------------------------------- counters

def test_capture_counters_in_dispatch_stats():
    stats = profiler.dispatch_stats()
    for key in capture.stats():
        assert key in stats, key


# ------------------------------------------------------------ bench gates

@pytest.mark.slow
def test_capture_bench_gates():
    """Acceptance: captured step <= eager-bulk step, and a warm AOT
    cache makes the cold-start compile >= 5x faster
    (tools/capture_bench.py, same JSON convention as dispatch_bench)."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_TPU_COMPILE_CACHE", None)
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "capture_bench.py"),
         "--steps", "20", "--trials", "3"],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"stderr:\n{r.stderr[-3000:]}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "capture_step_speedup"
    assert out["extra"]["step_gate_ok"] and out["extra"]["coldstart_gate_ok"]
