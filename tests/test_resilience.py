"""Resilient training runtime: CheckpointManager, HealthSentinel, fault
harness, hardened init_distributed, and DataLoader worker respawn
(docs/resilience.md). All tier-1 (CPU, no TPU)."""
import os
import time
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience
from mxnet_tpu.resilience import (CheckpointManager, CheckpointCorruptError,
                                  HealthSentinel, NumericHealthError, faults)


@pytest.fixture(autouse=True)
def _clean_resilience():
    faults.reset()
    resilience.reset_stats()
    yield
    faults.reset()


def _make_net(seed=0):
    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize(init=mx.initializer.Xavier())
    return net


def _make_trainer(net, momentum=0.9):
    return mx.gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": momentum})


def _step(net, trainer, k=0):
    x = mx.nd.array(np.arange(6, dtype=np.float32).reshape(2, 3) + k)
    y = mx.nd.ones((2, 4))
    with mx.autograd.record():
        loss = ((net(x) - y) ** 2).sum()
    loss.backward()
    trainer.step(2)


def _params_np(net):
    # keyed by hierarchy-relative names (what checkpoints store), so two
    # independently-built nets compare by role, not by auto-name counter
    return {k: v.asnumpy().copy()
            for k, v in net._collect_params_with_prefix().items()}


def _assert_params_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


# ---------------------------------------------------------------------------
# CheckpointManager
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip_bitwise(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    for k in range(3):
        _step(net, trainer, k)
    mgr = CheckpointManager(tmp_path, keep_n=3)
    mgr.save(3, net=net, trainer=trainer, epoch=1, extra={"note": "t"})
    saved_params = _params_np(net)
    saved_states = trainer.get_states_bytes()
    rng_before = mx.random.generator_key().asnumpy().copy()

    _step(net, trainer, 9)  # diverge
    mx.random.seed(777)     # clobber RNG
    manifest = mgr.restore_latest(net=net, trainer=trainer)
    assert manifest["step"] == 3 and manifest["epoch"] == 1
    assert manifest["extra"] == {"note": "t"}
    _assert_params_equal(saved_params, _params_np(net))
    assert trainer.get_states_bytes() == saved_states
    np.testing.assert_array_equal(rng_before,
                                  mx.random.generator_key().asnumpy())


def test_checkpoint_retention_prunes_oldest(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=2)
    for step in (1, 2, 3, 4):
        mgr.save(step, net=net, trainer=trainer)
    assert [s for s, _ in mgr.list_checkpoints()] == [3, 4]


def test_restore_skips_corrupt_falls_back(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=5)
    mgr.save(1, net=net, trainer=trainer)
    good = _params_np(net)
    _step(net, trainer, 1)
    path2 = mgr.save(2, net=net, trainer=trainer)
    # corrupt the newest checkpoint's payload on disk (truncate one of
    # the v2 per-array shard files)
    import glob

    ppath = sorted(glob.glob(os.path.join(path2, "arrays", "*.bin")),
                   key=os.path.getsize)[-1]
    with open(ppath, "r+b") as f:
        f.truncate(os.path.getsize(ppath) // 2)
    with pytest.warns(UserWarning, match="corrupt checkpoint"):
        manifest = mgr.restore_latest(net=net, trainer=trainer)
    assert manifest["step"] == 1
    _assert_params_equal(good, _params_np(net))
    stats = resilience.stats()
    assert stats["ckpt_restore_skipped"] == 1


def test_enospc_fault_leaves_previous_intact(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=5)
    mgr.save(1, net=net, trainer=trainer)
    with faults.inject("ckpt_enospc"):
        with pytest.raises(OSError) as ei:
            mgr.save(2, net=net, trainer=trainer)
    assert "injected" in str(ei.value)
    # nothing published, no temp junk, ckpt 1 still valid
    assert [s for s, _ in mgr.list_checkpoints()] == [1]
    assert not [n for n in os.listdir(tmp_path) if ".tmp" in n]
    assert mgr.latest_valid()[0] == 1


def test_partial_write_fault_detected_by_crc(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=5)
    mgr.save(1, net=net, trainer=trainer)
    with faults.inject("ckpt_partial_write"):
        mgr.save(2, net=net, trainer=trainer)  # publishes a corrupt ckpt
    assert [s for s, _ in mgr.list_checkpoints()] == [1, 2]
    with pytest.warns(UserWarning, match="CRC32|truncated"):
        step, _, _ = mgr.latest_valid()
    assert step == 1


def test_crash_between_payload_and_manifest_restores_prior(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=5)
    mgr.save(1, net=net, trainer=trainer)
    good = _params_np(net)
    _step(net, trainer, 1)
    with faults.inject("ckpt_crash_before_manifest"):
        with pytest.raises(faults.SimulatedCrash):
            mgr.save(2, net=net, trainer=trainer)
    # the interrupted checkpoint never published; restore returns 1
    manifest = mgr.restore_latest(net=net, trainer=trainer)
    assert manifest["step"] == 1
    _assert_params_equal(good, _params_np(net))


def test_kill_and_resume_bitwise_identical(tmp_path):
    """Acceptance: a job killed mid-checkpoint resumes from the last valid
    checkpoint and, after the same number of effective steps, holds
    bitwise-identical parameters AND optimizer state to an uninterrupted
    run."""
    total_steps = 6
    # --- reference: uninterrupted run
    net = _make_net(seed=0)
    trainer = _make_trainer(net)
    for k in range(total_steps):
        _step(net, trainer, k)
    ref_params = _params_np(net)
    ref_states = trainer.get_states_bytes()

    # --- crashed run: checkpoint after every step, die during the 4th save
    net = _make_net(seed=0)
    trainer = _make_trainer(net)
    mgr = CheckpointManager(tmp_path, keep_n=2)
    died_after = None
    with faults.inject("ckpt_crash_before_manifest", at_step=3):
        try:
            for k in range(total_steps):
                _step(net, trainer, k)
                mgr.save(k + 1, net=net, trainer=trainer)
        except faults.SimulatedCrash:
            died_after = k  # noqa: B023 - loop var captured at crash
    assert died_after == 3  # crash while checkpointing step 4

    # --- resume in a "fresh process": new net/trainer, different init
    net = _make_net(seed=12345)
    trainer = _make_trainer(net)
    manifest = mgr.restore_latest(net=net, trainer=trainer)
    assert manifest["step"] == 3  # last valid checkpoint
    for k in range(manifest["step"], total_steps):
        _step(net, trainer, k)
    _assert_params_equal(ref_params, _params_np(net))
    assert trainer.get_states_bytes() == ref_states


def test_checkpoint_resave_same_step_overwrites(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=3)
    mgr.save(1, net=net, trainer=trainer)
    _step(net, trainer, 1)
    newest = _params_np(net)
    mgr.save(1, net=net, trainer=trainer)  # resumed runs re-save steps
    assert [s for s, _ in mgr.list_checkpoints()] == [1]
    assert not [n for n in os.listdir(tmp_path) if ".old" in n]
    mgr.restore_latest(net=net, trainer=trainer)
    _assert_params_equal(newest, _params_np(net))


def test_restore_latest_empty_returns_none(tmp_path):
    net = _make_net()
    assert CheckpointManager(tmp_path).restore_latest(net=net) is None


def test_debris_gc_resurrects_and_removes(tmp_path):
    """Stale temp dirs from a dead writer are removed; a step stranded
    mid-publish (moved aside but never replaced) is renamed back."""
    import shutil

    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=5)
    path1 = mgr.save(1, net=net, trainer=trainer)
    # simulate a kill between move-aside and publish (dead pid 999999)
    os.replace(path1, str(tmp_path / ".ckpt-00000001.old.999999"))
    # and a stale temp dir from another dead writer
    junk = tmp_path / ".ckpt-00000002.tmp.999999"
    junk.mkdir()
    (junk / "params.npz").write_bytes(b"partial")
    manifest = mgr.restore_latest(net=net, trainer=trainer)
    assert manifest is not None and manifest["step"] == 1  # resurrected
    assert not junk.exists()
    assert [s for s, _ in mgr.list_checkpoints()] == [1]
    shutil.rmtree(tmp_path / "ckpt-00000001")


# ---------------------------------------------------------------------------
# Atomic trainer states (satellite)
# ---------------------------------------------------------------------------

def test_save_states_atomic_crash_keeps_old_file(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    fname = str(tmp_path / "trainer.states")
    trainer.save_states(fname)
    good = open(fname, "rb").read()
    _step(net, trainer, 1)
    with faults.inject("ckpt_enospc"):
        with pytest.raises(OSError):
            trainer.save_states(fname)
    assert open(fname, "rb").read() == good  # untouched, not truncated
    # and the round trip restores bitwise
    trainer.load_states(fname)
    assert trainer.get_states_bytes() == good


# ---------------------------------------------------------------------------
# HealthSentinel policies
# ---------------------------------------------------------------------------

def test_sentinel_raise_policy():
    net = _make_net()
    trainer = _make_trainer(net)
    HealthSentinel(policy="raise").attach(trainer)
    with faults.inject("nan_grad"):
        with pytest.raises(NumericHealthError, match="non-finite"):
            _step(net, trainer)


def test_sentinel_skip_batch_leaves_params_and_training_continues():
    net = _make_net()
    trainer = _make_trainer(net)
    HealthSentinel(policy="skip_batch").attach(trainer)
    _step(net, trainer, 0)
    before = _params_np(net)
    with faults.inject("nan_grad"):
        _step(net, trainer, 1)  # poisoned step: must be a no-op
    _assert_params_equal(before, _params_np(net))
    _step(net, trainer, 2)      # healthy step: training continues
    after = _params_np(net)
    assert any(not np.array_equal(before[k], after[k]) for k in before)
    stats = resilience.stats()
    assert stats["health_skipped_steps"] == 1
    assert stats["sentinel_nonfinite"] == 1


def test_sentinel_rollback_restores_previous_step(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    mgr = CheckpointManager(tmp_path, keep_n=2)
    HealthSentinel(policy="rollback").attach(trainer, net=net,
                                             checkpoint_manager=mgr)
    _step(net, trainer, 0)
    mgr.save(1, net=net, trainer=trainer)
    snapshot = _params_np(net)
    states = trainer.get_states_bytes()
    with faults.inject("nan_grad"):
        _step(net, trainer, 1)  # NaN -> rollback to checkpoint 1
    _assert_params_equal(snapshot, _params_np(net))
    assert trainer.get_states_bytes() == states
    assert resilience.stats()["sentinel_rollbacks"] == 1


def test_sentinel_rollback_without_manager_or_net_rejected(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    with pytest.raises(ValueError, match="CheckpointManager"):
        HealthSentinel(policy="rollback").attach(trainer)
    # manager alone isn't enough: restoring optimizer state without the
    # parameters would silently leave an inconsistent model
    mgr = CheckpointManager(tmp_path)
    with pytest.raises(ValueError, match="net"):
        HealthSentinel(policy="rollback").attach(trainer,
                                                 checkpoint_manager=mgr)


def test_sentinel_failed_rollback_is_fatal_not_counted(tmp_path):
    """A rollback with no valid checkpoint raises and must NOT count as a
    skipped step or a rollback."""
    net = _make_net()
    trainer = _make_trainer(net)
    mgr = CheckpointManager(tmp_path)  # empty: nothing to roll back to
    HealthSentinel(policy="rollback").attach(trainer, net=net,
                                             checkpoint_manager=mgr)
    with faults.inject("nan_grad"):
        with pytest.raises(NumericHealthError, match="no valid checkpoint"):
            _step(net, trainer)
    stats = resilience.stats()
    assert stats["sentinel_rollbacks"] == 0
    assert stats["health_skipped_steps"] == 0


def test_sentinel_grad_norm_threshold():
    net = _make_net()
    trainer = _make_trainer(net)
    HealthSentinel(policy="raise", grad_norm_threshold=1e-12).attach(trainer)
    with pytest.raises(NumericHealthError, match="grad norm"):
        _step(net, trainer)


def test_sentinel_check_loss():
    net = _make_net()
    trainer = _make_trainer(net)
    s = HealthSentinel(policy="skip_batch").attach(trainer)
    assert s.check_loss(mx.nd.array([1.0]))
    assert not s.check_loss(mx.nd.array([float("nan")]))
    assert resilience.stats()["health_skipped_steps"] == 1


def test_sentinel_env_policy(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_HEALTH_POLICY", "skip_batch")
    assert HealthSentinel().policy == "skip_batch"
    monkeypatch.setenv("MXNET_TPU_HEALTH_POLICY", "bogus")
    with pytest.raises(ValueError, match="MXNET_TPU_HEALTH_POLICY"):
        HealthSentinel()


def test_amp_overflow_shares_skip_counter():
    from mxnet_tpu import amp, profiler

    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    amp.init(target_dtype="float16")
    try:
        amp.init_trainer(trainer)
        g = net.collect_params()[next(iter(net.collect_params()))].grad()
        g._set_data((g * float("nan"))._data)
        assert amp.unscale(trainer) is False
        stats = profiler.dispatch_stats()
        assert stats["health_skipped_steps"] == 1
        assert stats["amp_overflow_skips"] == 1
    finally:
        amp.reset()


# ---------------------------------------------------------------------------
# init_distributed hardening (satellite)
# ---------------------------------------------------------------------------

def test_init_distributed_validates_env():
    from mxnet_tpu.kvstore import dist as kd

    with pytest.raises(kd.DistConfigError, match="out of range"):
        kd.init_distributed("h:9000", num_processes=2, process_id=2)
    with pytest.raises(kd.DistConfigError, match="positive"):
        kd.init_distributed("h:9000", num_processes=0, process_id=0)
    with pytest.raises(kd.DistConfigError, match="host:port"):
        kd.init_distributed("hostonly", num_processes=2, process_id=0)
    with pytest.raises(kd.DistConfigError, match="1..65535"):
        kd.init_distributed("h:70000", num_processes=2, process_id=0)
    with pytest.raises(kd.DistConfigError, match="not an integer"):
        kd.init_distributed("h:port", num_processes=2, process_id=0)
    assert not kd._initialized


def test_init_distributed_bad_env_vars(monkeypatch):
    from mxnet_tpu.kvstore import dist as kd

    monkeypatch.setenv("MXNET_TPU_COORDINATOR", "h:9000")
    monkeypatch.setenv("DMLC_NUM_WORKER", "two")
    monkeypatch.setenv("DMLC_WORKER_ID", "0")
    with pytest.raises(kd.DistConfigError, match="DMLC_NUM_WORKER"):
        kd.init_distributed()
    assert not kd._initialized


def test_init_distributed_not_configured_returns_false(monkeypatch):
    from mxnet_tpu.kvstore import dist as kd

    for var in ("MXNET_TPU_COORDINATOR", "DMLC_PS_ROOT_URI",
                "DMLC_NUM_WORKER", "DMLC_WORKER_ID"):
        monkeypatch.delenv(var, raising=False)
    assert kd.init_distributed() is False


def test_init_distributed_timeout_with_backoff(monkeypatch, tmp_path):
    """Acceptance: unreachable coordinator fails within the configured
    deadline (no hang) after exponential-backoff retries. The bootstrap
    reads an injected clock, so the bounds hold on a loaded host: every
    delay is the one the loop chose, and the time that passes is the
    time it slept. Its rank claim goes under ``tmp_path``: a claim lives
    as long as its process, and ``tests/test_watchdog.py`` claims rank 0
    of the same endpoint from another xdist worker (whichever file came
    second got a DistConfigError in place of the TimeoutError)."""
    import tempfile
    import types

    from mxnet_tpu.kvstore import dist as kd

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))
    clock = types.SimpleNamespace(now=100.0, slept=[])
    clock.monotonic = lambda: clock.now

    def sleep(seconds):
        clock.slept.append(seconds)
        clock.now += seconds

    clock.sleep = sleep
    monkeypatch.setattr(kd, "time", clock)
    with faults.inject("dist_connect_timeout", times=None) as fault:
        with pytest.raises(TimeoutError, match="coordinator"):
            kd.init_distributed("127.0.0.1:9", num_processes=2, process_id=0,
                                timeout=2.0, max_retries=3, backoff=0.1)
    assert fault.fired == 4          # initial attempt + 3 backoff retries
    # jittered over the upper half of an exponential ceiling
    assert len(clock.slept) == 3
    for delay, ceiling in zip(clock.slept, (0.1, 0.2, 0.4)):
        assert ceiling / 2 <= delay <= ceiling
    assert clock.now - 100.0 < 2.0   # bounded, no indefinite hang
    assert not kd._initialized
    # with retries to spare the deadline ends it: never a sleep past it
    clock.now, clock.slept = 100.0, []
    with faults.inject("dist_connect_timeout", times=None) as fault:
        with pytest.raises(TimeoutError, match="within 2.0s"):
            kd.init_distributed("127.0.0.1:9", num_processes=2, process_id=0,
                                timeout=2.0, max_retries=60, backoff=0.1)
    assert 4 < fault.fired < 60
    assert clock.now - 100.0 == pytest.approx(2.0)
    assert not kd._initialized


def test_init_distributed_real_unreachable_coordinator_bounded():
    """No fault harness: a non-coordinator rank probing a genuinely
    unreachable endpoint must fail with TimeoutError in bounded time —
    and must NOT reach jax's fatal-abort handshake path."""
    from mxnet_tpu.kvstore import dist as kd

    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="coordinator"):
        kd.init_distributed("127.0.0.1:9", num_processes=2, process_id=1,
                            timeout=2.0, max_retries=2, backoff=0.1)
    assert time.monotonic() - t0 < 10.0
    assert not kd._initialized


def test_init_distributed_deterministic_error_not_retried(monkeypatch):
    """Non-connectivity RuntimeErrors from jax.distributed must surface
    immediately, not after a backoff schedule dressed as a timeout."""
    from mxnet_tpu.kvstore import dist as kd

    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("mismatched number of processes across ranks")

    monkeypatch.setattr(kd, "_jax_dist_init", boom)
    with pytest.raises(RuntimeError, match="mismatched"):
        kd.init_distributed("127.0.0.1:9100", num_processes=2, process_id=0,
                            timeout=30.0, max_retries=5, backoff=0.1)
    assert len(calls) == 1  # no retries
    assert not kd._initialized


# ---------------------------------------------------------------------------
# fault harness itself
# ---------------------------------------------------------------------------

def test_faults_step_addressing():
    f = faults.arm("nan_grad", at_step=2, times=2)
    try:
        fired = [faults.maybe_nan_grads([]) is not None and f.fired
                 for _ in range(5)]
        # fires on calls 2 and 3 only (0-based), capped by times=2
        assert f.calls == 5 and f.fired == 2
    finally:
        faults.disarm("nan_grad")


def test_faults_env_install(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_FAULTS",
                       "nan_grad@3,ckpt_enospc@0:*,dist_connect_timeout@1:2")
    try:
        faults._install_from_env()
        assert faults.get("nan_grad").at_step == 3
        assert faults.get("ckpt_enospc").times is None
        assert faults.get("dist_connect_timeout").at_step == 1
        assert faults.get("dist_connect_timeout").times == 2
    finally:
        faults.reset()


# ---------------------------------------------------------------------------
# DataLoader worker respawn (satellite)
# ---------------------------------------------------------------------------

class _DieOnceDataset:
    """__getitem__(3) kills the worker process the first time it is ever
    asked for (flag file arbitrates across processes)."""

    def __init__(self, n, flag):
        self.n = n
        self.flag = flag

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == 3 and not os.path.exists(self.flag):
            open(self.flag, "w").close()
            os._exit(1)
        return np.full((2,), i, dtype=np.float32)


class _AlwaysDieDataset:
    def __init__(self, n):
        self.n = n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        if i == 3:
            os._exit(1)
        return np.full((2,), i, dtype=np.float32)


def test_dataloader_respawns_dead_worker(tmp_path):
    from mxnet_tpu import profiler
    from mxnet_tpu.gluon.data import dataloader as dl_mod
    from mxnet_tpu.gluon.data.dataloader import DataLoader

    dl_mod.reset_stats()
    ds = _DieOnceDataset(12, str(tmp_path / "died.flag"))
    loader = DataLoader(ds, batch_size=2, num_workers=2, timeout=60)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        got = [b.asnumpy() for b in loader]
    assert len(got) == 6
    values = sorted(int(row[0]) for b in got for row in b)
    assert values == list(range(12))  # every batch delivered despite death
    assert any("respawned" in str(x.message) for x in w)
    # the respawn also lands in the one-call resilience counter surface
    assert profiler.dispatch_stats()["dataloader_respawns"] >= 1


def test_dataloader_respawn_budget_exhausted(tmp_path):
    from mxnet_tpu.gluon.data.dataloader import DataLoader

    loader = DataLoader(_AlwaysDieDataset(12), batch_size=2, num_workers=1,
                        timeout=60, max_worker_respawns=1)
    with pytest.raises(RuntimeError, match="respawn budget"):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for _ in loader:
                pass


# ---------------------------------------------------------------------------
# ShardedTrainer states + sharded checkpoints
# ---------------------------------------------------------------------------

def _sharded_trainer():
    from mxnet_tpu.parallel.trainer import ShardedTrainer

    net = mx.gluon.nn.Dense(4, in_units=4)
    net.initialize()
    return ShardedTrainer(net, lambda p, l: ((p - l) ** 2), optimizer="sgd",
                          optimizer_params={"learning_rate": 0.1,
                                            "momentum": 0.9})


def test_sharded_trainer_states_roundtrip_keeps_sharding(tmp_path):
    import jax

    st = _sharded_trainer()
    x = np.ones((8, 4), np.float32)
    y = np.ones((8, 4), np.float32)
    st.step(x, y)
    st.step(x, y)
    fname = str(tmp_path / "sharded.states")
    st.save_states(fname)
    before = jax.tree.map(np.asarray, st.opt_state)
    st.step(x, y)  # diverge
    st.load_states(fname)
    after = jax.tree.map(np.asarray, st.opt_state)
    jax.tree.map(np.testing.assert_array_equal, before, after)
    # every leaf came back with its original NamedSharding (NOT replicated)
    flags = jax.tree.map(
        lambda leaf, sh: leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        if hasattr(leaf, "sharding") else True,
        st.opt_state, st._opt_sharding())
    assert all(jax.tree.leaves(flags))
    # wrong-model states fail loudly instead of silently loading
    other = _sharded_trainer()
    other._optimizer_params = {}
    with pytest.raises(ValueError, match="opt_state leaf"):
        from mxnet_tpu.parallel.trainer import ShardedTrainer
        net2 = mx.gluon.nn.Dense(2, in_units=2)
        net2.initialize()
        st2 = ShardedTrainer(net2, lambda p, l: ((p - l) ** 2),
                             optimizer="sgd",
                             optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9})
        st2.load_states(fname)


def test_sharded_checkpoint_roundtrip(tmp_path):
    st = _sharded_trainer()
    x = np.ones((8, 4), np.float32)
    y = np.ones((8, 4), np.float32)
    st.step(x, y)
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, trainer=st)
    params_before = {k: np.asarray(v) for k, v in st.params.items()}
    st.step(x, y)
    manifest = mgr.restore_latest(trainer=st)
    assert manifest["kind"] == "sharded"
    for k in params_before:
        np.testing.assert_array_equal(params_before[k],
                                      np.asarray(st.params[k]), err_msg=k)


# ---------------------------------------------------------------------------
# Estimator CheckpointHandler + callback
# ---------------------------------------------------------------------------

def _fit_data(n=4):
    x = mx.nd.array(np.random.RandomState(0).rand(8, 3).astype(np.float32))
    y = mx.nd.array(np.random.RandomState(1).randint(
        0, 2, size=(8,)).astype(np.float32))
    return [(x, y)] * n


def test_estimator_checkpoint_handler_atomic_and_resume(tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import CheckpointHandler, Estimator
    from mxnet_tpu.gluon.loss import SoftmaxCrossEntropyLoss

    net = _make_net()
    est = Estimator(net, SoftmaxCrossEntropyLoss(),
                    trainer=_make_trainer(net))
    handler = CheckpointHandler(str(tmp_path), atomic=True, keep_n=2)
    est.fit(_fit_data(), epochs=3, event_handlers=[handler])
    assert handler.manager is not None
    steps = [s for s, _ in handler.manager.list_checkpoints()]
    assert steps == [1, 2]  # keep_n retention

    net2 = _make_net(seed=7)
    est2 = Estimator(net2, SoftmaxCrossEntropyLoss(),
                     trainer=_make_trainer(net2))
    resume = CheckpointHandler(str(tmp_path), atomic=True, keep_n=2,
                               resume=True)
    est2.fit(_fit_data(), epochs=1, event_handlers=[resume])
    assert resume.resumed_manifest is not None
    assert resume.resumed_manifest["step"] == 2
    # post-resume checkpoints continue past the restored step, so the
    # newest state stays the newest checkpoint and pruning drops oldest
    assert [s for s, _ in resume.manager.list_checkpoints()] == [2, 3]


@pytest.mark.slow
def test_resilience_bench_sentinel_overhead_under_5pct():
    """Acceptance: sentinel per-step overhead <= 5% on the eager CPU path
    (tools/resilience_bench.py, same JSON convention as dispatch_bench)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "resilience_bench.py"),
         "--steps", "100"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stderr:\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["metric"] == "resilience_sentinel_overhead_pct"
    assert out["value"] <= 5.0, out
    assert out["extra"]["ckpt_save_ms_1m"] > 0


def test_resilient_checkpoint_callback(tmp_path):
    net = _make_net()
    trainer = _make_trainer(net)
    _step(net, trainer)
    mgr = CheckpointManager(tmp_path, keep_n=3)
    cb = mx.callback.resilient_checkpoint(mgr, net, trainer=trainer, period=2)
    for epoch in range(4):
        cb(epoch)
    assert [s for s, _ in mgr.list_checkpoints()] == [2, 4]
