"""Multi-process distributed KVStore.

Capability parity with the reference's multi-node path (`kvstore='dist_sync'`,
src/kvstore/kvstore_dist.h:44 worker + kvstore_dist_server.h server,
launched by tools/launch.py:33-44 with the DMLC_* env protocol), re-designed
for TPU: there is no parameter server — every worker participates in a
synchronous allreduce over a one-device-per-process mesh, lowered by XLA to
Gloo on CPU hosts and to ICI/DCN collectives on TPU pods. The server-side
optimizer becomes "every worker applies the same update to the same
allreduced gradient", which yields bitwise-identical weights on all workers
(the property the reference's dist_sync tests assert:
tests/nightly/dist_sync_kvstore.py:30).

Bootstrap env protocol (DMLC names kept for launcher compatibility):
  DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT  — coordinator address
  DMLC_NUM_WORKER                       — number of processes
  DMLC_WORKER_ID                        — this process's rank
(or the single var MXNET_TPU_COORDINATOR="host:port".)
"""
from __future__ import annotations

import logging
import os
import random as _random_mod
import threading
import time

import numpy as _np

from ..observability import flight as _obs_flight
from ..resilience import faults as _faults
from ..resilience import watchdog as _watchdog
from .kvstore import KVStore, KVStoreTPU, _pairs

__all__ = ["KVStoreDist", "init_distributed", "is_distributed",
           "DistConfigError"]

_log = logging.getLogger("mxnet_tpu.kvstore.dist")

_init_lock = threading.Lock()
_initialized = False

# Per-process RNG for retry jitter (module-level so tests can seed it).
_jitter = _random_mod.Random()


class DistConfigError(ValueError):
    """Invalid DMLC_*/coordinator configuration, caught before touching
    jax.distributed (whose errors surface deep inside the runtime)."""


def _coordinator_from_env():
    addr = os.environ.get("MXNET_TPU_COORDINATOR")
    if addr:
        return addr
    uri = os.environ.get("DMLC_PS_ROOT_URI")
    if uri:
        port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
        return f"{uri}:{port}"
    return None


def _env_int(name):
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return None
    try:
        return int(raw)
    except ValueError:
        raise DistConfigError(
            f"{name}={raw!r} is not an integer; fix the launcher "
            "environment (tools/launch.py sets these)") from None


def _validate_config(coordinator, num_processes, process_id):
    """Fail fast with actionable messages instead of a hang or an opaque
    error deep inside jax.distributed."""
    if num_processes <= 0:
        raise DistConfigError(
            f"DMLC_NUM_WORKER must be a positive integer, got "
            f"{num_processes}")
    if not 0 <= process_id < num_processes:
        raise DistConfigError(
            f"DMLC_WORKER_ID={process_id} is out of range for "
            f"DMLC_NUM_WORKER={num_processes} (ranks are 0.."
            f"{num_processes - 1}); every worker needs a distinct rank")
    host, sep, port = str(coordinator).rpartition(":")
    if not sep or not host:
        raise DistConfigError(
            f"coordinator address {coordinator!r} must be 'host:port' "
            "(set MXNET_TPU_COORDINATOR or DMLC_PS_ROOT_URI/"
            "DMLC_PS_ROOT_PORT)")
    try:
        port_n = int(port)
    except ValueError:
        raise DistConfigError(
            f"coordinator port {port!r} in {coordinator!r} is not an "
            "integer (check DMLC_PS_ROOT_PORT)") from None
    if not 1 <= port_n <= 65535:
        raise DistConfigError(
            f"coordinator port {port_n} in {coordinator!r} is outside "
            "1..65535 (check DMLC_PS_ROOT_PORT)")


def _claim_pid_alive(pid):
    try:
        os.kill(int(pid), 0)
    except (OSError, ValueError, TypeError):
        return False
    return True


def _claim_dir(coordinator):
    path = os.environ.get("MXNET_TPU_DIST_CLAIM_DIR")
    if path:
        return path
    import hashlib
    import tempfile

    # one claim namespace per coordinator endpoint, so two unrelated
    # jobs on the same machine never contest each other's ranks
    slug = hashlib.sha1(str(coordinator).encode("utf-8")).hexdigest()[:12]
    return os.path.join(tempfile.gettempdir(),
                        f"mxnet_tpu-dist-claims-{slug}")


def _claim_rank(coordinator, num_processes, process_id):
    """Reject duplicate ranks BEFORE the jax.distributed handshake.

    Two workers launched with the same DMLC_WORKER_ID otherwise race
    inside the coordination service: one wins, the other hangs or aborts
    with an opaque barrier error long after launch. Each worker claims
    its rank by creating ``rank-<id>.claim`` (O_EXCL, body = claimant
    pid) in a per-coordinator directory; a live claim by another process
    is a structured :class:`DistConfigError` naming both the contested
    rank and the claimant, while claims whose pid is dead are stale
    debris from a previous job and are replaced silently. The claim is
    on-machine only — cross-host duplicates still fail inside jax, but
    every launcher this repo ships (tools/launch.py) colocates workers,
    which is exactly where the footgun lives."""
    directory = _claim_dir(coordinator)
    path = os.path.join(directory, f"rank-{int(process_id)}.claim")
    os.makedirs(directory, exist_ok=True)
    for _ in range(2):  # second pass only after unlinking a stale claim
        try:
            fd = os.open(path, os.O_WRONLY | os.O_CREAT | os.O_EXCL, 0o644)
        except FileExistsError:
            try:
                with open(path, "r", encoding="utf-8") as fh:
                    claimant = fh.read().strip()
            except OSError:
                claimant = ""
            if claimant == str(os.getpid()):
                return path  # our own earlier claim (retried bootstrap)
            if claimant and _claim_pid_alive(claimant):
                raise DistConfigError(
                    f"DMLC_WORKER_ID={int(process_id)} is already claimed "
                    f"by live process pid={claimant} for coordinator "
                    f"{coordinator} (claim file {path}); every worker "
                    f"needs a distinct rank in 0..{int(num_processes) - 1} "
                    "— check the launcher's DMLC_WORKER_ID assignments")
            try:  # stale claim (dead pid / unreadable) — reap and retry
                os.unlink(path)
            except OSError:
                pass
            continue
        try:
            os.write(fd, str(os.getpid()).encode("ascii"))
        finally:
            os.close(fd)
        return path
    raise DistConfigError(
        f"DMLC_WORKER_ID={int(process_id)} claim file {path} is being "
        "contested faster than stale claims can be reaped; two workers "
        "are racing for the same rank")


def init_distributed(coordinator=None, num_processes=None, process_id=None,
                     timeout=None, max_retries=None, backoff=None):
    """Initialize the jax distributed runtime (idempotent).

    Replaces the reference's ps-lite Van/tracker bootstrap: a single TCP
    coordination service (jax.distributed) instead of scheduler+server
    processes. The reference's ps-lite Van retried sends forever; here a
    missing peer fails LOUDLY in bounded time instead of hanging:

    - ``timeout`` — hard wall-clock deadline in seconds for the whole
      bootstrap, retries included (env ``MXNET_TPU_DIST_TIMEOUT``,
      default 300);
    - ``max_retries`` — connect attempts beyond the first (env
      ``MXNET_TPU_DIST_RETRIES``, default 60 so the deadline, not the
      retry count, is what normally bounds startup skew between ranks),
      spaced by exponential backoff starting at ``backoff`` seconds
      (env ``MXNET_TPU_DIST_BACKOFF``, default 1.0, capped at 30).
      Each delay is jittered uniformly over the upper half of its
      exponential ceiling, decorrelating the ranks: after a coordinator
      blip, N workers that failed in the same instant would otherwise
      all retry in lockstep and thundering-herd the recovering endpoint.
      Every retry is logged (logger ``mxnet_tpu.kvstore.dist``) with the
      attempt number, the chosen delay, and the last error.

    Non-coordinator ranks first PROBE the coordinator's TCP endpoint
    under this retry/deadline loop and only then enter
    jax.distributed.initialize. This matters: the coordination client
    LOG(FATAL)s and aborts the whole process when the handshake times
    out, so the unreachable-peer case must be caught before jax ever
    sees it. Rank 0 hosts the service and needs no probe.

    Raises DistConfigError for invalid env combinations and TimeoutError
    when the coordinator stays unreachable past the deadline.
    """
    global _initialized
    with _init_lock:
        if _initialized:
            return True
        coordinator = coordinator or _coordinator_from_env()
        if num_processes is None:
            num_processes = _env_int("DMLC_NUM_WORKER") or None
        if process_id is None:
            process_id = _env_int("DMLC_WORKER_ID")
        if coordinator is None or num_processes is None or process_id is None:
            return False  # not launched as a distributed job
        _validate_config(coordinator, num_processes, process_id)
        _claim_rank(coordinator, num_processes, process_id)
        if timeout is None:
            timeout = float(os.environ.get("MXNET_TPU_DIST_TIMEOUT", "300"))
        if max_retries is None:
            max_retries = int(os.environ.get("MXNET_TPU_DIST_RETRIES", "60"))
        if backoff is None:
            backoff = float(os.environ.get("MXNET_TPU_DIST_BACKOFF", "1.0"))
        import jax

        deadline = time.monotonic() + timeout
        attempt = 0
        last_err = None
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                _faults.maybe_dist_connect_fault()
                if process_id != 0:
                    _probe_coordinator(coordinator, min(remaining, 10.0))
                _jax_dist_init(jax, coordinator, num_processes, process_id,
                               remaining)
                _initialized = True
                return True
            except RuntimeError as e:
                # The user may have called jax.distributed.initialize()
                # at program start themselves — that's fine, use theirs.
                if "already initialized" in str(e).lower():
                    _initialized = True
                    return True
                # only connectivity-flavored RuntimeErrors are worth
                # retrying; deterministic failures (mismatched process
                # counts, bad state) must surface immediately, not after
                # a full backoff schedule dressed up as a TimeoutError
                if not _is_connect_error(e):
                    raise
                last_err = e
                _safe_shutdown(jax)
            except (TimeoutError, ConnectionError, OSError) as e:
                last_err = e
                _safe_shutdown(jax)
            attempt += 1
            if attempt > max_retries:
                break
            ceiling = min(backoff * (2 ** (attempt - 1)), 30.0)
            # jitter over [ceiling/2, ceiling] so ranks decorrelate
            # instead of hammering the coordinator in lockstep
            delay = min(_jitter.uniform(ceiling / 2.0, ceiling),
                        max(0.0, deadline - time.monotonic()))
            _log.warning(
                "init_distributed: worker %s/%s attempt %d/%d failed "
                "(%r); next retry in %.2fs",
                process_id, num_processes, attempt, max_retries + 1,
                last_err, max(0.0, delay))
            if delay > 0:
                time.sleep(delay)
        raise TimeoutError(
            f"init_distributed: worker {process_id}/{num_processes} could "
            f"not reach coordinator {coordinator} within {timeout:.1f}s "
            f"({attempt} attempt(s), exponential backoff from "
            f"{backoff:.1f}s). Last error: {last_err!r}. Check that the "
            "coordinator process is up and DMLC_PS_ROOT_URI/"
            "DMLC_PS_ROOT_PORT (or MXNET_TPU_COORDINATOR) point at it.")


def _is_connect_error(e):
    msg = str(e).lower()
    return any(m in msg for m in ("deadline", "unavailable", "timed out",
                                  "timeout", "connect", "refused",
                                  "unreachable"))


def _probe_coordinator(coordinator, timeout):
    """Bounded TCP reachability check of the coordinator endpoint. Raises
    ConnectionError (retryable) instead of letting the XLA coordination
    client hit its fatal-abort path on an unreachable peer."""
    import socket

    host, _, port = coordinator.rpartition(":")
    try:
        sock = socket.create_connection((host, int(port)), timeout=timeout)
        sock.close()
    except OSError as e:
        raise ConnectionError(
            f"coordinator {coordinator} is not accepting connections "
            f"({e})") from e


def _safe_shutdown(jax):
    """Best-effort teardown of a half-initialized distributed runtime so
    the next initialize attempt doesn't trip 'should only be called
    once'."""
    try:
        jax.distributed.shutdown()
    except Exception:
        pass


def _jax_dist_init(jax, coordinator, num_processes, process_id, remaining):
    """One bootstrap attempt, bounded by the remaining deadline."""
    # CPU hosts run cross-process collectives over Gloo; without this
    # the CPU backend refuses multiprocess computations outright. Must
    # land before the backend initializes (it does: nothing may touch
    # jax before jax.distributed.initialize).
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coordinator, num_processes=num_processes,
        process_id=process_id,
        initialization_timeout=max(1, int(remaining)))


def is_distributed():
    import jax

    return _initialized or jax.process_count() > 1


class _WorkerRing:
    """One-device-per-process mesh + cached allreduce executables."""

    def __init__(self):
        import jax
        from jax.sharding import Mesh

        per_process = {}
        for d in jax.devices():
            per_process.setdefault(d.process_index, d)
        self.devices = [per_process[p] for p in sorted(per_process)]
        self.mesh = Mesh(_np.array(self.devices), ("worker",))
        self.n = len(self.devices)
        self._local = per_process[jax.process_index()]
        self._fns = {}

    def allreduce(self, arr):
        """Sum `arr` (same shape on every worker) across all workers.

        Accepts host numpy (returns numpy) or a local device array
        (returns the replicated result's local device buffer — the
        gradient never round-trips through the host, so on a pod the
        reduction rides ICI end-to-end; the numpy path exists for
        host-resident values like the barrier's token).

        Runs under the collective watchdog: a peer that died mid-run
        surfaces as PeerLostError naming the rank, and a reduction that
        makes no progress within MXNET_TPU_WATCHDOG_COLLECTIVE_TIMEOUT
        raises StallError instead of blocking the slice forever."""
        with _watchdog.collective_guard(
                detail=f"kvstore('dist').allreduce{tuple(arr.shape)}"):
            _faults.maybe_hang("hang_collective")
            return self._allreduce(arr)

    def _allreduce(self, arr):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        device_in = isinstance(arr, jax.Array)
        if not device_in:
            arr = _np.ascontiguousarray(arr)
        shape = tuple(arr.shape)
        key = (shape, _np.dtype(arr.dtype).str)
        if key not in self._fns:
            sharding = NamedSharding(self.mesh, P("worker"))
            out_sharding = NamedSharding(self.mesh, P())
            fn = jax.jit(lambda g: jnp.sum(g, axis=0),
                         out_shardings=out_sharding)
            self._fns[key] = (fn, sharding)
        fn, sharding = self._fns[key]
        local = jax.device_put(
            arr.reshape((1,) + shape), self._local)
        global_arr = jax.make_array_from_single_device_arrays(
            (self.n,) + shape, sharding, [local])
        out = fn(global_arr)
        if device_in:
            return out.addressable_shards[0].data
        return _np.asarray(out)


class KVStoreDist(KVStoreTPU):
    """Synchronous multi-process allreduce store (`dist`/`dist_sync`)."""

    def __init__(self, kind="dist_sync"):
        super().__init__(kind)
        init_distributed()
        self._ring = None  # built lazily so single-process use stays cheap

    def push(self, key, value, priority=0):
        # bypass KVStoreTPU's collective guard: here the real collective
        # is the worker-ring allreduce inside _global_merge, which owns
        # the guard — one guard + one hang_collective/peer_death hook
        # consultation per COLLECTIVE (i.e. per key on a multi-key
        # push), never a doubled-up wrapper around the same reduction,
        # keeping the fault harness's step addressing deterministic
        KVStore.push(self, key, value, priority)

    def _get_ring(self):
        if self._ring is None:
            self._ring = _WorkerRing()
        return self._ring

    @property
    def num_workers(self):
        import jax

        return jax.process_count()

    def init(self, key, value):
        """All workers converge on rank-0's initial value (the reference's
        'worker 0 initializes the server' semantics, kvstore_dist.h)."""
        super().init(key, value)
        if self.num_workers > 1:
            import jax

            scale = 1.0 if jax.process_index() == 0 else 0.0
            for k in (_pairs(key, value)[0]):
                v = self._data[k]
                synced = self._get_ring().allreduce(
                    v.asnumpy() * _np.asarray(scale, v.asnumpy().dtype))
                self._data[k] = _from_np(synced, v)

    def _global_merge(self, merged):
        """Cross-worker allreduce inserted into the base push path —
        device-resident: the NDArray's jax buffer goes straight into the
        collective and the result wraps back without touching the host."""
        if self.num_workers > 1:
            from ..ndarray.ndarray import NDArray

            summed = self._get_ring().allreduce(merged.data_)
            merged = NDArray(summed, getattr(merged, "_ctx", None))
        return merged

    def barrier(self):
        if self.num_workers > 1:
            self._get_ring().allreduce(_np.zeros((1,), _np.float32))

    def fingerprint_agree(self, named):
        """Do ALL workers' replicas of ``named`` fold to the same
        xsf32-v1 fingerprint? Decides with the ring's sum allreduce
        alone: the 32-bit fingerprint splits into 16-bit halves (so
        every channel stays exact in float64), and both the sum and the
        square-sum of each half are reduced — by strict convexity,
        ``sum(x_i) == n*x`` AND ``sum(x_i^2) == n*x^2`` holds on a rank
        only when every ``x_i`` equals its own ``x``, so the verdict is
        exact and symmetric on every rank (no probabilistic hashing).
        Counts a mismatch into the integrity layer's checkpoint/
        boundary counters and flight-records it."""
        fp = self.state_fingerprint(named)
        if self.num_workers <= 1:
            return True
        from ..resilience import integrity as _integrity

        halves = _np.array([fp & 0xFFFF, fp >> 16], _np.float64)
        vec = _np.concatenate([halves, halves * halves])
        total = self._get_ring().allreduce(vec)
        agree = bool(_np.array_equal(total, vec * float(self.num_workers)))
        if not agree:
            _integrity._STATS["integrity_ckpt_mismatches"] += 1
            _integrity._MET_MISMATCHES.inc(surface="checkpoint")
            _obs_flight.record("integrity", op="kv_disagree",
                               rank=self.rank, fingerprint=fp)
        return agree


def _from_np(arr, like):
    from ..ndarray import ndarray as _nd

    return _nd.array(arr, dtype=arr.dtype, ctx=like.context)
