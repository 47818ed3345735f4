"""Silent-data-corruption defense (mxnet_tpu/resilience/integrity.py,
docs/integrity.md).

Acceptance (ISSUE 20): the xsf32-v1 step fingerprint is bitwise
identical across eager/bulk/captured execution of the same step, stable
under kill-resume, and equal after a dp=8 -> dp=4 mesh-shrink restore;
checkpoint manifests carry the parameter fingerprint and a tampered
record is skipped (flight-recorded) in favor of the previous valid
checkpoint; the sdc_* chaos drills (tools/chaos_run.py, auto-run by
test_watchdog's FAST_KINDS sweep) prove detection -> attribution ->
quarantine -> mesh-shrink recovery end-to-end.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import capture
from mxnet_tpu.resilience import CheckpointManager, integrity


def _fp_env(monkeypatch, audit_every=None):
    monkeypatch.setenv("MXNET_TPU_INTEGRITY_FINGERPRINT", "1")
    if audit_every is not None:
        monkeypatch.setenv("MXNET_TPU_INTEGRITY_AUDIT_EVERY",
                           str(audit_every))


# ------------------------------------------------------------ fold algebra

def test_fold_host_matches_traced_fold_across_dtypes():
    import jax

    rs = np.random.RandomState(3)
    named = {
        "f32": rs.randn(5, 7).astype(np.float32),
        "f16": rs.randn(3, 4).astype(np.float16),
        "bf16": None,  # filled below via jax (numpy has no bfloat16)
        "i32": rs.randint(-9, 9, (6,)).astype(np.int32),
        "u8": rs.randint(0, 255, (11,)).astype(np.uint8),
        "bool": rs.rand(4) > 0.5,
    }
    import jax.numpy as jnp

    named["bf16"] = np.asarray(
        jnp.asarray(rs.randn(2, 3).astype(np.float32), jnp.bfloat16))
    host = integrity.fold_host(named)
    traced = int(np.asarray(
        jax.jit(integrity.fold_tree)(
            {k: jnp.asarray(v) for k, v in named.items()})))
    assert host == traced
    # order independence: insertion order must not matter
    assert integrity.fold_host(dict(reversed(list(named.items())))) == host


def test_fold_detects_single_low_bit_flip():
    arr = np.arange(16, dtype=np.float32)
    fp = integrity.fold_host({"w": arr})
    flipped = arr.copy()
    flipped.view(np.uint32)[7] ^= 1
    assert integrity.fold_host({"w": flipped}) != fp
    # names are folded in: same values under another name differ
    assert integrity.fold_host({"v": arr}) != fp
    # the seed is the EMPTY fold — a diagnostic tell, never a collision
    assert integrity.fold_host({}) == integrity._FOLD_SEED
    assert fp != integrity._FOLD_SEED


def test_step_fold_host_matches_state_fingerprint_composition():
    rs = np.random.RandomState(5)
    params = {"a": rs.randn(3).astype(np.float32)}
    grads = {"a": rs.randn(3).astype(np.float32)}
    assert integrity.step_fold_host(params, grads) == integrity.fold_host(
        {"param:a": params["a"], "grad:a": grads["a"]})


# ------------------------------------- eager/bulk/captured step parity

def _one_net_run(monkeypatch, modes, steps=3, seed=11):
    """Run the SAME gluon net (gluon auto-naming is process-global, so a
    rebuilt net would get different param names and thus a different
    name-mixing fold) through each capture mode, restoring the initial
    params between modes; returns {mode: [step fingerprints]}."""
    mx.random.seed(seed)
    net = mx.gluon.nn.HybridSequential(prefix="integ_")
    with net.name_scope():
        net.add(mx.gluon.nn.Dense(8, activation="relu"))
        net.add(mx.gluon.nn.Dense(4))
    net.initialize()
    net(mx.nd.zeros((2, 6)))
    init = {k: p.data().asnumpy().copy()
            for k, p in net.collect_params().items()}

    def batch(k):
        rs = np.random.RandomState(50 + k)
        return (mx.nd.array(rs.rand(4, 6).astype(np.float32)),
                mx.nd.ones((4, 4)))

    def loss_fn(out, y):
        return ((out - y) ** 2).sum()

    out = {}
    for mode in modes:
        for k, p in net.collect_params().items():
            p.set_data(mx.nd.array(init[k]))
        trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                                   {"learning_rate": 0.05})
        if mode == "plain":
            monkeypatch.delenv("MXNET_TPU_CAPTURE", raising=False)
            fps = []
            for k in range(steps):
                x, y = batch(k)
                with mx.autograd.record():
                    loss = loss_fn(net(x), y)
                loss.backward()
                trainer.step(4)
                fps.append(int(integrity.step_fold_host(
                    *map(lambda d: {n: np.asarray(a) for n, a in
                                    d.items()},
                         integrity.net_named_state(net)))))
        else:
            monkeypatch.setenv("MXNET_TPU_CAPTURE",
                               "1" if mode == "captured" else "0")
            step = capture.capture(trainer, net=net, loss_fn=loss_fn)
            fps = []
            for k in range(steps):
                x, y = batch(k)
                step(x, y, batch_size=4)
                fps.append(step.last_fingerprint)
        out[mode] = fps
    return out


def test_fingerprint_parity_eager_captured_plain(monkeypatch):
    """The tentpole determinism gate: the in-graph fingerprint of the
    captured step, the host fold of the eager kill-switch path, and the
    plain autograd loop all produce the SAME per-step values."""
    _fp_env(monkeypatch)
    runs = _one_net_run(monkeypatch, ("captured", "eager", "plain"))
    assert runs["captured"] == runs["eager"] == runs["plain"]
    assert all(fp is not None for fp in runs["captured"])
    assert len(set(runs["captured"])) == len(runs["captured"])  # evolves


def test_fingerprint_off_by_default(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_INTEGRITY_FINGERPRINT", raising=False)
    monkeypatch.delenv("MXNET_TPU_INTEGRITY_AUDIT_EVERY", raising=False)
    assert not integrity.fingerprint_enabled()
    runs = _one_net_run(monkeypatch, ("captured",), steps=1, seed=13)
    assert runs["captured"] == [None]


def test_audit_cadence_arms_fingerprint(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_INTEGRITY_FINGERPRINT", raising=False)
    monkeypatch.setenv("MXNET_TPU_INTEGRITY_AUDIT_EVERY", "4")
    assert integrity.fingerprint_enabled()
    assert integrity.audit_due(4) and not integrity.audit_due(3)


# --------------------------------------------- sharded trainer + shrink

def _sharded(dp, seed=21, mgr=None, devs=None):
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4, in_units=4, prefix="integ_sh_")
    net.initialize()
    return ShardedTrainer(net, lambda p, l: ((p - l) ** 2),
                          optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1},
                          mesh=create_mesh({"dp": dp},
                                           (devs or jax.devices())[:dp]),
                          checkpoint_manager=mgr)


def test_state_fingerprint_stable_across_mesh_shrink(monkeypatch,
                                                     tmp_path):
    """dp=8 -> dp=4 reshardable restore: the parameter-state fingerprint
    is a property of the logical values, not the mesh — it survives the
    topology change bitwise, and the manifest fingerprint verifies."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    _fp_env(monkeypatch)
    before = integrity.stats()
    mgr = CheckpointManager(tmp_path / "ckpt", keep_n=2)
    t8 = _sharded(8, mgr=mgr)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    t8.step(x, y)
    fp8 = integrity.state_fingerprint(
        {k: np.asarray(v) for k, v in t8.params.items()})
    mgr.save(1, trainer=t8)
    t4 = _sharded(4, mgr=CheckpointManager(tmp_path / "ckpt"))
    manifest = t4._ckpt_mgr.restore_latest(trainer=t4)
    assert manifest is not None and manifest["step"] == 1
    fp4 = integrity.state_fingerprint(
        {k: np.asarray(v) for k, v in t4.params.items()})
    assert fp4 == fp8
    d = {k: integrity.stats()[k] - before[k] for k in before}
    assert d["integrity_ckpt_fingerprints"] >= 1
    assert d["integrity_ckpt_verified"] >= 1
    assert d["integrity_ckpt_mismatches"] == 0


def test_sharded_in_graph_fingerprint_matches_host_fold(monkeypatch):
    """The fused step's extra in-graph output equals the host fold of
    (post-step params, step grads) — computed here via the accum path
    (n=2), which folds host-side over the same logical operands."""
    _fp_env(monkeypatch)
    x = np.arange(64, dtype=np.float32).reshape(16, 4) / 64
    y = np.ones((16, 4), np.float32)
    fused = _sharded(4, seed=23)
    fused.step(x, y)
    assert fused.last_fingerprint is not None
    again = _sharded(4, seed=23)
    again.step(x, y)
    # determinism: same program, same operands, same fingerprint
    assert again.last_fingerprint == fused.last_fingerprint


@pytest.mark.parametrize("dp,microbatches,masked", [
    pytest.param(4, None, False, id="fused"),
    pytest.param(2, None, True, id="masked"),
    pytest.param(2, 2, False, id="accum2")])
def test_shadow_replay_matches_live_step(monkeypatch, dp, microbatches,
                                         masked):
    """``integrity_replay`` compiles the live variant's own programs for
    the shadow mesh: from the same pre-step state it lands on bitwise the
    live step's parameters and on the live step's fingerprint, for the
    fused, the pad-masked and the accumulated step alike."""
    import jax
    from mxnet_tpu.parallel.mesh import create_mesh
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    _fp_env(monkeypatch)
    mx.random.seed(31)
    net = mx.gluon.nn.Dense(5, in_units=4, flatten=False,
                            prefix="integ_replay_")
    net.initialize()
    trainer = ShardedTrainer(
        net, mx.gluon.loss.L2Loss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9},
        mesh=create_mesh({"dp": dp}, jax.devices()[:dp]))
    rs = np.random.RandomState(5)
    x = rs.randn(8, 6, 4).astype(np.float32)
    y = rs.randn(8, 6, 5).astype(np.float32)
    length = rs.randint(1, 6, (8,)).astype(np.int32) if masked else None
    trainer.step(x, y)  # the replayed step starts from moving momenta
    before = jax.tree.map(
        np.asarray, (trainer.params, trainer.aux, trainer.opt_state))
    trainer.step(x, y, microbatches=microbatches, length=length)
    shadow = integrity._shadow_mesh(trainer.mesh)
    assert ({d.id for d in shadow.devices.flat}
            != {d.id for d in trainer.mesh.devices.flat})
    params, fp = trainer.integrity_replay(
        shadow, *before, x, y, microbatches=microbatches or 1,
        length=length)
    assert set(params) == set(trainer.params)
    for k, v in params.items():
        live = np.asarray(trainer.params[k])
        assert not np.array_equal(live, before[0][k]), k  # a real step
        assert v.tobytes() == live.tobytes(), k
    assert fp is not None and fp == trainer.last_fingerprint


# ------------------------------------------------- checkpoint boundary

def test_manifest_tamper_skips_to_previous_checkpoint(monkeypatch,
                                                      tmp_path):
    """A manifest whose recorded fingerprint does not match the
    reassembled parameters (SDC at save time) is treated as corruption:
    restore_latest SKIPS it pre-mutation, falls back to the previous
    valid checkpoint, and flight-records which checkpoint was skipped
    and why."""
    from mxnet_tpu.observability import flight

    _fp_env(monkeypatch)
    mgr = CheckpointManager(tmp_path / "ckpt", keep_n=3)
    trainer = _sharded(2, seed=27, mgr=mgr)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    trainer.step(x, y)
    mgr.save(1, trainer=trainer)
    trainer.step(x, y)
    path2 = mgr.save(2, trainer=trainer)
    mpath = os.path.join(path2, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    assert manifest["integrity"]["algo"] == integrity.ALGO
    manifest["integrity"]["params"] ^= 0x1  # the lying save
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    mark = flight.last_seq()
    restored = _sharded(2, seed=27)
    out = mgr.restore_latest(trainer=restored)
    assert out is not None and out["step"] == 1  # fell back
    events = [e for e in flight.events(since_seq=mark)
              if e["kind"] == "ckpt" and e.get("op") == "restore_skipped"]
    assert len(events) == 1
    assert "ckpt-00000002" in events[0]["path"]
    assert "fingerprint" in events[0]["reason"]


def test_manifest_without_integrity_record_restores(monkeypatch,
                                                    tmp_path):
    """Fingerprint off at save time -> no record -> restore verifies
    trivially (old checkpoints never brick on upgrade)."""
    monkeypatch.delenv("MXNET_TPU_INTEGRITY_FINGERPRINT", raising=False)
    monkeypatch.delenv("MXNET_TPU_INTEGRITY_AUDIT_EVERY", raising=False)
    mgr = CheckpointManager(tmp_path / "ckpt")
    trainer = _sharded(2, seed=31, mgr=mgr)
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    trainer.step(x, y)
    path = mgr.save(1, trainer=trainer)
    with open(os.path.join(path, "manifest.json")) as f:
        assert json.load(f).get("integrity") is None
    restored = _sharded(2, seed=31)
    assert mgr.restore_latest(trainer=restored)["step"] == 1
    assert integrity.verify_manifest_fingerprint(None, {}) is True
    assert integrity.verify_manifest_fingerprint(
        {"algo": "xsf99-future", "params": 1}, {}) is True


# ------------------------------------------------------------ kill-resume

_RESUME_SCRIPT = r"""
import os, sys
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu.parallel.mesh import create_mesh
from mxnet_tpu.parallel.trainer import ShardedTrainer
from mxnet_tpu.resilience import CheckpointManager, integrity
import jax

ckpt, phase = sys.argv[1], sys.argv[2]
mx.random.seed(77)
net = mx.gluon.nn.Dense(4, in_units=4, prefix="resume_net_")
net.initialize()
mgr = CheckpointManager(ckpt, keep_n=2)
tr = ShardedTrainer(net, lambda p, l: ((p - l) ** 2), optimizer="sgd",
                    optimizer_params={"learning_rate": 0.1},
                    mesh=create_mesh({"dp": 2}, jax.devices()[:2]),
                    checkpoint_manager=mgr)
x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
y = np.ones((8, 4), np.float32)
if phase == "first":
    tr.step(x, y)
    mgr.save(1, trainer=tr)
else:
    assert mgr.restore_latest(trainer=tr)["step"] == 1
tr.step(x, y)
print("FP", int(tr.last_fingerprint))
"""


@pytest.mark.slow
def test_fingerprint_stable_under_kill_resume(tmp_path):
    """The step-2 fingerprint is identical whether the process survived
    (first run computes steps 1-2) or was killed after the step-1
    checkpoint and resumed in a fresh process — the fold has no hidden
    process-local state."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = tmp_path / "resume_fp.py"
    script.write_text(_RESUME_SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_INTEGRITY_FINGERPRINT="1",
               PYTHONPATH=repo,
               XLA_FLAGS="--xla_force_host_platform_device_count=8")

    def run(ckpt, phase):
        r = subprocess.run(
            [sys.executable, str(script), str(ckpt), phase],
            env=env, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, f"stderr:\n{r.stderr}"
        return int(r.stdout.strip().splitlines()[-1].split()[-1])

    straight = run(tmp_path / "a", "first")  # survives: steps 1+2
    run(tmp_path / "b", "first")             # killed after step-1 save
    resumed = run(tmp_path / "b", "resume")  # fresh process: step 2
    assert straight == resumed


# ---------------------------------------------------------------- serving

def test_audit_serving_passes_on_clean_fleet():
    from mxnet_tpu import serving

    def factory():
        mx.random.seed(41)
        net = mx.gluon.nn.Dense(4, in_units=3, prefix="integ_fleet_")
        net.initialize()
        return serving.Predictor.from_block(
            net, input_shapes={"data": (3,)}, batch_sizes=(2,))

    x = np.ones((1, 3), np.float32)
    with serving.Fleet(factory, replicas=2,
                       server_kw={"batch_timeout_ms": 1.0}) as fleet:
        assert fleet.wait_healthy(timeout=20)
        golden = fleet.replicas()[0].submit(x).result(timeout=10)
        before = integrity.stats()["integrity_serving_audits"]
        assert integrity.audit_serving(fleet, x, golden) == []
        assert integrity.stats()["integrity_serving_audits"] == before + 1


# ---------------------------------------------------------------- preempt

def test_request_preempt_drains_at_step_boundary(tmp_path):
    trainer = _sharded(2, seed=37,
                       mgr=CheckpointManager(tmp_path / "ckpt"))
    x = np.arange(32, dtype=np.float32).reshape(8, 4) / 32
    y = np.ones((8, 4), np.float32)
    trainer.step(x, y)
    integrity.request_preempt(reason="test")
    try:
        with pytest.raises(integrity.Preempted) as ei:
            trainer.step(x, y)
        assert ei.value.step == 2 and ei.value.code == 0
        assert not integrity.preempt_requested()  # cleared on exit
        # the emergency checkpoint captured the drained state
        resumed = _sharded(2, seed=37)
        mgr = CheckpointManager(tmp_path / "ckpt")
        assert mgr.restore_latest(trainer=resumed)["step"] == 2
        for k in trainer.params:
            assert np.array_equal(np.asarray(resumed.params[k]),
                                  np.asarray(trainer.params[k])), k
    finally:
        integrity.clear_preempt()


def test_sigterm_handler_requests_preempt():
    import signal

    installed = integrity.install_preempt_handler()
    if not installed:
        pytest.skip("not on the main thread")
    try:
        os.kill(os.getpid(), signal.SIGTERM)
        # the trapped signal must request a drain, not kill the process
        assert integrity.preempt_requested()
    finally:
        integrity.clear_preempt()


def test_preempt_sigterm_kill_switch(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PREEMPT_SIGTERM", "0")
    assert integrity.install_preempt_handler() is False


# ---------------------------------------------------------------- kvstore

def test_kvstore_fingerprint_agree_single_process():
    kv = mx.kv.create("tpu")
    named = {"w": mx.nd.array(np.arange(6, dtype=np.float32))}
    assert kv.state_fingerprint(named) == integrity.fold_host(
        {"w": np.arange(6, dtype=np.float32)})
    assert kv.fingerprint_agree(named) is True


# ------------------------------------------------------------------- bench

@pytest.mark.slow
def test_integrity_bench_fingerprint_overhead_under_2pct():
    """Acceptance: the armed in-graph fingerprint costs <= 2% on a
    captured step (tools/integrity_bench.py, one-line JSON contract)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "integrity_bench.py"),
         "--steps", "60", "--trials", "3"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "integrity_fingerprint_overhead_pct"
    assert out["value"] <= 2.0, out
    assert out["extra"]["host_fold_ms"] > 0


# ------------------------------------------------------------------ alerts

def test_sdc_detected_rule_registered():
    from mxnet_tpu.observability import alerts

    assert "sdc_detected" in alerts.ALERT_RULE_IDS
    alerts.reset()
    rule = alerts.get_rule("sdc_detected")
    assert rule is not None
    assert set(rule.keys) == {
        "integrity_audit_mismatches", "integrity_selftest_failures",
        "integrity_serving_failures", "integrity_ckpt_mismatches"}
