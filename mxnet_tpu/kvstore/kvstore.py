"""KVStore — parameter aggregation across devices and hosts.

Parity: include/mxnet/kvstore.h + src/kvstore/ (KVStoreLocal, CommDevice,
KVStoreNCCL, KVStoreDist) and python/mxnet/kvstore/. TPU-native design
(SURVEY.md §2.3): `kvstore='tpu'` replaces KVStoreNCCL — its push/pull is an
XLA allreduce; within one process it sums per-device shards, across hosts it
rides `jax.distributed` global arrays over ICI/DCN. The async parameter
server ('dist_async', ps-lite server-side optimizer) has no collective
equivalent and is intentionally dropped: 'dist_sync' / 'dist' map onto the
synchronous allreduce path (documented divergence, SURVEY.md §7 hard part 6).
"""
from __future__ import annotations

import warnings

from ..base import MXNetError
from ..ndarray.ndarray import NDArray, zeros as nd_zeros
from ..resilience import faults as _faults
from ..resilience import watchdog as _watchdog

__all__ = ["KVStore", "KVStoreLocal", "KVStoreDevice", "KVStoreTPU", "create"]


def create(name="local"):
    name = name.lower()
    if name in ("local", "local_update_cpu", "local_allreduce_cpu"):
        return KVStoreLocal("local")
    if name in ("device", "local_allreduce_device"):
        return KVStoreDevice("device")
    if name in ("tpu", "nccl", "horovod"):
        return KVStoreTPU("tpu")
    if name.startswith("dist"):
        if "async" in name:
            warnings.warn(
                "kvstore 'dist_async' has no TPU equivalent (ps-lite "
                "asynchronous server is dropped); using synchronous "
                "allreduce semantics instead.")
        from .dist import KVStoreDist

        return KVStoreDist(name)
    raise MXNetError(f"unknown kvstore type {name!r}")


class KVStore:
    """Base synchronous store (kvstore.h:59)."""

    def __init__(self, kind):
        self._kind = kind
        self._data = {}
        self._updater = None
        self._optimizer = None
        self._compression = None

    @property
    def type(self):
        return self._kind

    @property
    def rank(self):
        import jax

        return jax.process_index()

    @property
    def num_workers(self):
        import jax

        return jax.process_count()

    def init(self, key, value):
        keys, values = _pairs(key, value)
        for k, v in zip(keys, values):
            v0 = v[0] if isinstance(v, (list, tuple)) else v
            self._data[k] = v0.copy()

    def broadcast(self, key, value, out=None):
        self.init(key, value)
        if out is not None:
            self.pull(key, out)

    def push(self, key, value, priority=0):
        from ..ndarray.sparse import BaseSparseNDArray

        keys, values = _pairs(key, value)
        for k, v in zip(keys, values):
            merged = self._reduce(v if isinstance(v, (list, tuple)) else [v])
            if self._compression is not None and \
                    not isinstance(merged, BaseSparseNDArray):
                # compress this worker's contribution before it leaves the
                # host (worker->server leg in the reference)
                merged = self._compression.compress(k, merged)
            merged = self._global_merge(merged)
            from ..ndarray.sparse import RowSparseNDArray

            if k not in self._data:
                self._data[k] = (merged.tostype("default")
                                 if isinstance(merged, RowSparseNDArray)
                                 else merged.copy())
                continue
            if self._updater is not None:
                self._updater(_key_int(k), merged, self._data[k])
            else:
                # no updater: the store holds the latest reduced value
                # (kvstore_local.h:208 PushImpl — reduce then assign)
                if isinstance(merged, RowSparseNDArray):
                    merged = merged.tostype("default")
                self._data[k]._set_data(merged._data)

    def pull(self, key, out=None, priority=0, ignore_sparse=True):
        import jax

        from ..ops import registry as _registry

        keys, outs = _pairs(key, out)
        for k, o in zip(keys, outs):
            if k not in self._data:
                raise MXNetError(f"key {k} was not initialized")
            targets = o if isinstance(o, (list, tuple)) else [o]
            # the store buffer is now shared with the pull targets: a
            # donated in-place update (update_on_kvstore optimizer) on the
            # store cell must not delete the targets' buffer. _force()
            # (dense cells only) resolves any lazy value so the CONCRETE
            # buffer gets marked.
            store = self._data[k]
            if hasattr(store, "_force"):
                _registry.mark_shared(store._force())
            src = self._data[k]._data
            src_dev = _single_device(src)
            for t in targets:
                dev = t.context.jax_device()
                if src_dev is not None and dev != src_dev:
                    # a target on another device gets its own copy THERE;
                    # sharing the store's buffer would leave a cell whose
                    # context names one device and whose data sits on
                    # another
                    t._set_data(jax.device_put(src, dev))
                else:
                    t._set_data(src)

    def pushpull(self, key, value, out=None, priority=0):
        self.push(key, value, priority)
        if out is not None:
            self.pull(key, out, priority)

    def row_sparse_pull(self, key, out=None, priority=0, row_ids=None):
        """Pull only the requested rows as a RowSparseNDArray
        (kvstore_local.h:268 PullRowSparseImpl). The store holds dense
        values; the row gather is an XLA program."""
        from ..ndarray.ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray

        if row_ids is None:
            raise MXNetError("row_sparse_pull requires row_ids")
        keys, outs = _pairs(key, out)
        # A single key always gets row_ids verbatim; only a multi-key pull
        # interprets a list as per-key id sets (a plain Python list of ints
        # for one key would otherwise be zipped element-per-key).
        if isinstance(key, (str, int)):
            ids_list = [row_ids]
        elif isinstance(row_ids, (list, tuple)) and \
                len(row_ids) == len(keys):
            ids_list = list(row_ids)
        else:
            ids_list = [row_ids] * len(keys)
        results = []
        for k, o, ids in zip(keys, outs, ids_list):
            if k not in self._data:
                raise MXNetError(f"key {k} was not initialized")
            import jax.numpy as jnp

            val = self._data[k]
            idx = ids._data.astype(jnp.int32) if isinstance(ids, NDArray) \
                else jnp.asarray(ids, jnp.int32)
            rsp = RowSparseNDArray(
                NDArray(val._data[idx], val._ctx),
                NDArray(idx, val._ctx),
                val.shape, val._ctx)
            if o is not None:
                targets = o if isinstance(o, (list, tuple)) else [o]
                for t in targets:
                    t.data = rsp.data
                    t.indices = rsp.indices
            results.append(rsp)
        return results[0] if len(results) == 1 else results

    def set_gradient_compression(self, compression_params):
        """Enable lossy gradient compression on push (2-bit quantization
        with error feedback; kvstore/compression.py). Raises on unsupported
        configs instead of silently accepting them."""
        from .compression import GradientCompression

        self._compression = GradientCompression(compression_params)

    def set_optimizer(self, optimizer):
        from ..optimizer import get_updater

        self._optimizer = optimizer
        self._updater = get_updater(optimizer)

    def _set_updater(self, updater):
        self._updater = updater

    def set_updater(self, updater):
        self._updater = updater

    def barrier(self):
        pass

    def save_optimizer_states(self, fname, dump_optimizer=False):
        if self._updater is None:
            raise MXNetError("no updater is set")
        with open(fname, "wb") as f:
            f.write(self._updater.get_states(dump_optimizer))

    def load_optimizer_states(self, fname):
        if self._updater is None:
            raise MXNetError("no updater is set")
        with open(fname, "rb") as f:
            self._updater.set_states(f.read())

    def _global_merge(self, merged):
        """Hook for cross-process aggregation; identity for local stores
        (KVStoreDist overrides with an allreduce)."""
        return merged

    def _reduce(self, values):
        from ..ndarray.sparse import RowSparseNDArray, _rsp_add

        merged = values[0]
        if len(values) > 1:
            if isinstance(merged, RowSparseNDArray):
                for v in values[1:]:
                    merged = _rsp_add(merged, v)
                return merged
            acc = merged.copy()
            for v in values[1:]:
                acc._set_data((acc + v.as_in_context(acc.context))._data)
            return acc
        return merged


class KVStoreLocal(KVStore):
    """Single-process store; reduce on host (src/kvstore/kvstore_local.h)."""


class KVStoreDevice(KVStoreLocal):
    """Reduce stays on accelerator (CommDevice, comm.h:451). With PJRT the
    adds run on-device already; this class exists for API parity."""


class KVStoreTPU(KVStore):
    """Allreduce store over the TPU mesh (replaces KVStoreNCCL/KVStoreDist).

    Single-host: per-device values are summed on device. Multi-host: values
    are jax global arrays; the sum lowers to an ICI/DCN allreduce via
    jax.distributed. The fast path for training is not push/pull at all —
    Trainer/Module lower the gradient sum into the jitted step as a psum
    (see parallel/), exactly as the north star prescribes.

    Every push runs under the collective watchdog
    (MXNET_TPU_WATCHDOG_COLLECTIVE_TIMEOUT) with peer-liveness
    bookkeeping: a dead peer surfaces as PeerLostError naming the rank,
    a wedged reduction as StallError — never an infinite block.
    """

    def push(self, key, value, priority=0):
        with _watchdog.collective_guard(
                detail=f"kvstore('{self._kind}').push({key!r})"):
            _faults.maybe_hang("hang_collective")
            super().push(key, value, priority)

    def excise_dead_peers(self, ranks=None):
        """Re-admit the store's collectives after dead ranks have been
        excised from the job — the kvstore-side hook of elastic peer
        recovery. ``PeerLostError`` bookkeeping is sticky by design (a
        dead rank must keep failing fast, never block), so once an
        elastic restart has rebuilt the worker set without the dead
        ranks (``parallel.ShardedTrainer`` mesh-shrink resume does this
        automatically; ``serving.fleet.ReplicaSupervisor`` does it per
        re-admitted replica; an operator replacing a worker does it by
        hand), call this to clear the bookkeeping and let push/pull
        serve again.

        ``ranks=None`` (the historical form) clears every dead rank;
        passing an iterable clears only those ranks — one recovered
        replica must not silently re-admit a peer that is still dead.
        Returns the ranks that were actually cleared."""
        dead = _watchdog.dead_peers()
        if ranks is None:
            cleared = dead
        else:
            wanted = {int(r) for r in ranks}
            cleared = [r for r in dead if r in wanted]
        _watchdog.reset_peers(cleared if ranks is not None else None)
        return cleared

    def _reduce(self, values):
        if len(values) == 1:
            return values[0]
        import jax
        import jax.numpy as jnp

        acc = values[0]._data
        acc_dev = _single_device(acc)
        for v in values[1:]:
            d = v._data
            if acc_dev is not None and \
                    _single_device(d) not in (None, acc_dev):
                # a value committed to another device: a jitted add
                # refuses operands on different devices, so bring it
                # over the interconnect first
                d = jax.device_put(d, acc_dev)
            acc = jnp.add(acc, d)
        return NDArray(acc, values[0].context)

    def state_fingerprint(self, named):
        """xsf32-v1 fold of ``named`` ({name: NDArray or array}) — this
        worker's local view of a replicated state, as one 32-bit
        integer (``resilience.integrity``)."""
        import numpy as np

        from ..resilience import integrity as _integrity

        items = named.items() if hasattr(named, "items") else named
        host = {str(k): np.asarray(v.asnumpy() if hasattr(v, "asnumpy")
                                   else v)
                for k, v in items}
        return int(_integrity.fold_host(host))

    def fingerprint_agree(self, named):
        """Do all workers hold bit-identical replicas of ``named``? A
        worker whose copy silently diverged (an SDC'd broadcast or a
        corrupted local apply) is invisible to loss curves — this is
        the cross-rank boundary check of the integrity layer. On a
        single-process store the replicas ARE the same buffers, so
        agreement is trivial; ``KVStoreDist`` overrides with a real
        worker-ring comparison."""
        self.state_fingerprint(named)  # folding must succeed everywhere
        return True


def _single_device(data):
    """The one device a concrete array lives on. None for a tracer (inside
    a captured step placement belongs to the program) and for a global
    array spanning several devices (its sharding already says where)."""
    import jax

    if isinstance(data, jax.core.Tracer):
        return None
    devs = data.devices()
    return next(iter(devs)) if len(devs) == 1 else None


def _pairs(key, value):
    if isinstance(key, (str, int)):
        return [key], [value]
    if value is None:
        return list(key), [None] * len(key)
    return list(key), list(value)


def _key_int(k):
    try:
        return int(k)
    except (TypeError, ValueError):
        return k
