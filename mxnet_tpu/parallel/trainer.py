"""Sharded training step over a device mesh.

The TPU-native replacement for DataParallelExecutorGroup + kvstore push/pull
(SURVEY.md §2.3): one jitted step function holds forward, backward, gradient
allreduce, and optimizer update. Parameters/batches carry NamedShardings on
the mesh; the gradient reduction over the 'dp' axis is inserted by XLA
(GSPMD) because the loss is a mean over the globally-sharded batch — the
explicit-NCCL push/pull of the reference collapses into compiler-placed ICI
collectives. Tensor-parallel shardings are expressed as parameter
PartitionSpec rules.
"""
from __future__ import annotations

import re

from ..observability import trace as _obs_trace
from .functional import functional_call, param_arrays, aux_arrays, RNG_KEY
from .mesh import create_mesh
from .optim import make_update_fn

__all__ = ["ShardedTrainer", "make_update_fn"]

# the attributes that hold each step variant's live executables
_VARIANT_EXECUTABLES = {"fused": ("_step",), "masked": ("_step_masked",),
                        "accum": ("_grads_fn", "_apply_fn")}


def _step_functions(compute_loss, update, fp_on):
    """The sharded training step, written once: ``(fused, grads, apply)``.
    Every step program, live or shadow-replayed, is one of these three
    compiled for a mesh (``ShardedTrainer._programs``), so the variants
    cannot drift apart.

    ``length`` (optional, (B,) int32 per-row valid token counts) masks
    pad tokens out of the loss: the mask is built in-graph from an iota
    compare, so the program stays ONE executable across calls (length
    values are runtime data), and enters as a normalized per-token
    sample weight, making the scalar loss exactly
    sum(loss*mask)/sum(mask) — bitwise-equal to weighting with an
    explicitly precomputed host-side mask."""
    import jax
    import jax.numpy as jnp

    from ..resilience import integrity as _integrity

    def loss_of(params, aux, x, y, length):
        if length is None:
            return compute_loss(params, aux, x, y)
        t = int(x.shape[1])
        mask = (jnp.arange(t, dtype=jnp.int32)[None, :]
                < length.astype(jnp.int32)[:, None]
                ).astype(jnp.float32)
        # normalize so the loss's final mean over B*T elements
        # becomes the mean over the sum(mask) REAL tokens
        w = (mask * (float(mask.size) / jnp.sum(mask)))[..., None]
        return compute_loss(params, aux, x, y, w)

    def grads(params, aux, x, y, length=None):
        (loss, new_aux), g = jax.value_and_grad(
            loss_of, has_aux=True)(params, aux, x, y, length)
        return g, new_aux, loss

    def apply(params, grads, opt_state):
        # the scope names the update's ops in the compiled program, where
        # a trace tells them from forward (``jvp``) and backward
        # (``transpose(jvp)``) ops
        with jax.named_scope("optimizer"):
            return update(params, grads, opt_state)

    def fused(params, aux, opt_state, x, y, length=None):
        g, new_aux, loss = grads(params, aux, x, y, length)
        new_params, new_opt = apply(params, g, opt_state)
        if fp_on:
            # in-graph step fingerprint (resilience.integrity): one extra
            # uint32 output of the SAME program — zero extra executables
            return (new_params, new_aux, new_opt, loss,
                    _integrity.step_fold(new_params, g))
        return new_params, new_aux, new_opt, loss

    return fused, grads, apply


class ShardedTrainer:
    """Compiles a full training step over a mesh.

    Parameters
    ----------
    net : initialized gluon Block (params already materialized)
    loss_fn : gluon Loss or callable(pred_nd, label_nd)->NDArray
    optimizer, optimizer_params : like gluon.Trainer
    mesh : jax.sharding.Mesh (default: all-devices 'dp' mesh)
    param_rules : list of (regex, PartitionSpec) — first match wins;
        unmatched params are replicated. This is where tp/pp/ep shardings
        plug in.
    batch_axis_name : mesh axis the batch dimension is sharded over.
    dtype : compute dtype policy. None = model dtype (fp32). 'bfloat16'
        (or 'float16') casts params/activations for forward+backward —
        fp32 master weights and optimizer state, bf16 MXU math — the TPU
        counterpart of the reference's AMP (contrib/amp/amp.py:251).
    checkpoint_manager : resilience.CheckpointManager, optional — arms
        the elastic mesh-shrink resume: a PeerLostError raised inside
        ``step`` is survived by rebuilding a smaller mesh from the
        surviving ranks and reloading the latest reshardable checkpoint
        onto it (docs/resilience.md). Without one, a dead peer stays
        terminal. ``enable_recovery`` attaches it after construction.
    """

    def __init__(self, net, loss_fn, optimizer="sgd", optimizer_params=None,
                 mesh=None, param_rules=(), batch_axis_name="dp",
                 dtype=None, remat=None, checkpoint_manager=None):
        import jax

        from ..remat import mirror_enabled, resolve_policy

        self.net = net
        self.loss_fn = loss_fn
        self._fwd = functional_call(net, train=True)
        # remat: False disables, None follows MXNET_BACKWARD_DO_MIRROR,
        # True/str/callable select a jax.checkpoint policy (remat.py) —
        # the backward then recomputes non-saved activations, trading
        # FLOPs for peak HBM (reference gradient mirroring)
        if remat is None:
            remat = mirror_enabled()
        if remat:
            self._fwd = jax.checkpoint(
                self._fwd, policy=resolve_policy(remat))
        # the optimizer state's creation and the placement of parameters,
        # aux and state on the mesh: set-up a trace can account for
        setup = _obs_trace.start_span("setup.trainer")
        self.params = param_arrays(net)
        self.aux = aux_arrays(net)
        self._compute_dtype = dtype
        self._optimizer = optimizer
        self._optimizer_params = dict(optimizer_params or {})
        init, update = make_update_fn(optimizer, dict(self._optimizer_params))
        self.opt_state = init(self.params)
        self._update = update
        self._rules = [(re.compile(pat), spec) for pat, spec in param_rules]
        # one mesh axis name, or a tuple of names when the batch dim is
        # sharded over several (dp×fsdp — SpecLayout.batch_axes())
        self._batch_axis = (batch_axis_name if isinstance(batch_axis_name,
                                                          str)
                            else tuple(batch_axis_name))
        # elastic recovery (resilience.elastic): the manager the
        # mesh-shrink resume reloads state from on PeerLostError; without
        # one, a dead peer stays terminal (enable_recovery attaches late)
        self._ckpt_mgr = checkpoint_manager
        self.last_recovery = None
        # pod topology (parallel.mesh.PodTopology): set by bind_pod/
        # for_pod when the mesh spans host failure domains; None means
        # rank-level elastic recovery only
        self._pod = None
        self._bind_mesh(mesh if mesh is not None else create_mesh())
        self._place()
        setup.end(params=len(self.params))
        # elastic execution state (resilience.elastic): current sticky
        # accumulation count and a monotonically increasing step counter
        # for crash reports (the executables live in _bind_mesh state)
        self._elastic_n = 1
        self._step_count = 0
        # SDC defense (resilience.integrity): the last step's in-graph
        # fingerprint output (lazy — host-read only on access) and the
        # SIGTERM preemption trap (finish the step, checkpoint, drain)
        self._last_fp_out = None
        from ..resilience import integrity as _integrity

        _integrity.install_preempt_handler()

    def _spec_for(self, name):
        from jax.sharding import PartitionSpec as P

        for pat, spec in self._rules:
            if pat.match(name):
                return spec
        return P()

    def _batch_axis_names(self):
        """The batch axes as a tuple (a single name normalizes)."""
        ba = self._batch_axis
        return (ba,) if isinstance(ba, str) else tuple(ba)

    def _batch_shards(self):
        """How many ways the batch dim splits on the CURRENT mesh: the
        product of the batch axes' extents (dp alone, or dp×fsdp when
        the batch is sharded over both)."""
        import math

        return math.prod(int(self.mesh.shape.get(a, 1))
                         for a in self._batch_axis_names())

    def _bind_mesh(self, mesh):
        """(Re)derive every mesh-dependent binding — NamedShardings for
        params/aux/batch/opt_state, the multi-process flag, and the
        compiled step executables (invalidated: they bake the old mesh
        in). Used at construction and by the peer-loss mesh-shrink
        resume; does NOT move any arrays (placement is _place or a
        restore)."""
        self.mesh = mesh
        self._shardings = self._shardings_on(mesh)
        (self._param_sharding, self._aux_sharding,
         self._batch_sharding) = self._shardings[:3]
        self._multiproc = self._is_multiprocess()
        self._drop_executables()

    def _drop_executables(self):
        """Forget the compiled step programs: the next step rebuilds (and
        re-captures) them under the current mesh, hyperparameters and
        kernel schedule table."""
        self._step = None
        self._step_masked = None
        self._grads_fn = None
        self._apply_fn = None

    def enable_recovery(self, checkpoint_manager):
        """Attach the CheckpointManager the elastic mesh-shrink resume
        reloads state from when a peer dies (docs/resilience.md). The
        manager should already hold (or be about to receive) reshardable
        v2 checkpoints of THIS trainer. Returns self for chaining."""
        self._ckpt_mgr = checkpoint_manager
        return self

    def _place(self):
        import numpy as np

        import jax
        import jax.numpy as jnp

        multiproc = self._multiproc
        if multiproc:
            # Host values must first be made CONSISTENT across processes:
            # each worker initializes from its own random stream, and
            # divergent "replicated" buffers silently train divergent
            # models (losses still agree — each rank's contribution enters
            # the same psum — but the weights drift apart; caught by the
            # dryrun's bitwise cross-rank check). The reference's dist
            # kvstore init broadcasts rank-0 values (kvstore_dist.h Init);
            # ONE pytree-level broadcast covers params+aux+opt_state
            # instead of one collective per leaf.
            from jax.experimental import multihost_utils

            host_tree = jax.tree.map(
                np.asarray, (self.params, self.aux, self.opt_state))
            self.params, self.aux, self.opt_state = \
                multihost_utils.broadcast_one_to_all(host_tree)

        def put(v, sharding):
            if multiproc:
                # every process now holds identical full host values; build
                # each local shard directly — device_put would attempt a
                # cross-host transfer
                arr = np.asarray(v)
                return jax.make_array_from_callback(
                    arr.shape, sharding, lambda idx: arr[idx])
            # device_put may alias the input buffer when placement already
            # matches; always copy so step donation never deletes a buffer
            # the net (or another trainer) still references. Init-only cost.
            return jax.device_put(jnp.array(v, copy=True), sharding)

        self.params = {k: put(v, self._param_sharding[k])
                       for k, v in self.params.items()}
        self.aux = {k: put(v, self._aux_sharding[k])
                    for k, v in self.aux.items()}
        self.opt_state = jax.tree.map(put, self.opt_state,
                                      self._opt_sharding())

    def _shardings_on(self, mesh):
        """``(params, aux, batch, opt_state)`` shardings on ``mesh``: the
        live mesh's (``_bind_mesh``) and the integrity shadow mesh's come
        from here. Param-shaped opt_state leaves (momenta, adam moments,
        master copies) follow their parameter's sharding; everything
        else (step counter, rng keys) is replicated."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        param = {k: NamedSharding(mesh, self._spec_for(k))
                 for k in self.params}
        repl = NamedSharding(mesh, P())

        def shard_for(name, leaf):
            p = self.params.get(name)
            if p is not None and hasattr(leaf, "shape") \
                    and tuple(leaf.shape) == tuple(p.shape):
                return param[name]
            return repl

        state = {
            k: jax.tree.map(lambda v, _k=k: shard_for(_k, v), s)
            for k, s in self.opt_state["state"].items()}
        opt = {**{k: repl for k in self.opt_state if k != "state"},
               "state": state}
        return (param, {k: repl for k in self.aux},
                NamedSharding(mesh, P(self._batch_axis)), opt)

    def _opt_sharding(self):
        """Sharding pytree for opt_state on the current mesh. Used both
        for placement and for the step's in/out shardings — the two MUST
        agree, or the donated state input aliases an
        incompatibly-sharded output buffer (XLA INTERNAL size-mismatch)."""
        return self._shardings[3]

    def _make_compute_loss(self):
        """The traced loss closure shared by the fused step and the
        elastic (grad-accumulation) executables — one definition so both
        paths compute bitwise-identical gradients."""
        import jax.numpy as jnp

        fwd = self._fwd
        loss_fn = self.loss_fn
        cdtype = self._compute_dtype

        from ..ndarray.ndarray import NDArray
        from ..jit import TraceSession

        def cast_in(tree):
            if cdtype is None:
                return tree
            return {k: (v.astype(cdtype)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in tree.items()}

        def compute_loss(params, aux, x, y, w=None):
            # AMP policy: bf16 params/activations in fwd+bwd; the cast sits
            # inside the grad so gradients land back in fp32 master dtype.
            # aux (BN moving stats, rng key) stays uncast: stats only feed
            # the f32 EMA update, and casting them to bf16 forces layout
            # copies into the BN-statistics fusions (PERF.md round 4)
            cp = cast_in(params)
            ca = aux
            if cdtype is not None and jnp.issubdtype(x.dtype, jnp.floating):
                x_c = x.astype(cdtype)
            else:
                x_c = x
            out, new_aux = fwd(cp, ca, x_c)
            if cdtype is not None:
                out = out.astype(jnp.float32)
                new_aux = {k: (v.astype(aux[k].dtype)
                               if jnp.issubdtype(aux[k].dtype, jnp.floating)
                               else v)
                           for k, v in new_aux.items()}
            with TraceSession(name_blocks=True) as sess:
                out_nd, y_nd = NDArray(out), NDArray(y)
                sess.note_created(out_nd)
                sess.note_created(y_nd)
                if w is None:
                    loss = loss_fn(out_nd, y_nd)
                else:
                    # per-token sample weight (pad masking): gluon losses
                    # broadcast_mul it into the per-element loss before
                    # their mean, so a weight normalized to sum to the
                    # element count turns the final .mean() into
                    # sum(l*mask)/sum(mask)
                    w_nd = NDArray(w)
                    sess.note_created(w_nd)
                    loss = loss_fn(out_nd, y_nd, w_nd)
            return loss.data_.mean(), new_aux

        return compute_loss

    def _capture_fingerprint(self):
        """Structural identity of this trainer's step programs for the
        capture/AOT compile path (mxnet_tpu.capture): everything that
        changes the traced program — params, optimizer + hyperparams
        (baked into make_update_fn here, unlike the gluon trainer's
        dynamic operands), mesh topology, sharding rules, compute dtype.
        A changed fingerprint is a re-capture, recorded in the retrace
        forensics; an unchanged one re-links the on-disk AOT artifact."""
        from .. import capture as _capture
        from ..resilience import integrity as _integrity

        parts = {
            "params": sorted((k, tuple(v.shape), str(v.dtype))
                             for k, v in self.params.items()),
            "aux": sorted((k, tuple(v.shape), str(v.dtype))
                          for k, v in self.aux.items()),
            # param avals alone can't distinguish relu from tanh or one
            # lambda loss body from another (docs/capture.md key schema)
            "net_struct": _capture.net_sig(self.net),
            "loss_code": _capture.code_sig(self.loss_fn),
            "optimizer": (str(self._optimizer),
                          sorted(self._optimizer_params.items())),
            "loss": getattr(self.loss_fn, "__qualname__",
                            type(self.loss_fn).__name__),
            "mesh": {str(a): int(s) for a, s in
                     zip(self.mesh.axis_names, self.mesh.devices.shape)},
            # host grouping changes the collective layout over a pod
            # (same axis sizes, different failure domains / ICI order)
            "pod": None if self._pod is None else
                   (int(self._pod.num_hosts),
                    int(self._pod.devices_per_host)),
            "rules": [(p.pattern, str(s)) for p, s in self._rules],
            "dtype": self._compute_dtype,
            "batch_axis": self._batch_axis,
            # kernel builders resolve Pallas block sizes from the tuned
            # schedule table at trace time (tune/), so a table edit is a
            # program change: fold the table token in so the next step()
            # re-traces instead of reusing the stale captured program
            "schedule": _capture._schedule_token(),
            # the in-graph step fingerprint adds an output to the traced
            # program (resilience.integrity) — an AOT artifact compiled
            # with the other setting must never false-hit
            "integrity": _integrity.fingerprint_enabled(),
        }
        return _capture.fingerprint(parts)

    def _capture_exec(self, fn, label, **kwargs):
        """Compile one step-program through the capture path (AOT
        persistence + retrace forensics + capture counters), noting a
        re-capture when the program fingerprint moved since the last
        build (mesh shrink, set_learning_rate)."""
        from .. import capture as _capture

        fp = self._capture_fingerprint()
        prev = getattr(self, "_capture_fp", None)
        if prev is not None and prev != fp:
            _capture.note_recapture(
                label, prev, fp,
                reason="step program rebind (mesh, hyperparameters or "
                       "kernel schedule table changed)")
        self._capture_fp = fp
        self._sched_token = _capture._schedule_token()
        return _capture.CapturedExec(fn, label=label, fingerprint=fp,
                                     **kwargs)

    def _programs(self, variant, shardings):
        """``[(label, function, jit arguments)]`` of one variant of the
        step — ``"fused"``, ``"masked"`` (one extra ``length`` operand)
        or ``"accum"`` (a NON-donating gradient program, whose params
        every microbatch and any further retry reuse, and an apply
        program for the single optimizer update) — on the mesh whose
        ``_shardings_on`` are given. The live executables and the
        integrity shadow replay both compile exactly this."""
        from ..resilience import integrity as _integrity

        param, aux, batch, opt = shardings
        # armed at build time; the capture fingerprint folds the flag so
        # an AOT artifact compiled without it can never false-hit
        fp_on = _integrity.fingerprint_enabled()
        fused, grads, apply = _step_functions(
            self._make_compute_loss(), self._update, fp_on)
        if variant == "accum":
            # gradients land in the parameter shardings so accumulation
            # never reshards; the microbatch shapes key the signature: an
            # elastic shrink re-captures at the smaller batch and the
            # re-capture lands in the retrace forensics instead of
            # recompiling silently
            return [
                ("sharded_grads", grads, dict(
                    in_shardings=(param, aux, batch, batch),
                    out_shardings=(param, aux, None), sig_argnums=(2, 3))),
                ("sharded_apply", apply, dict(
                    in_shardings=(param, param, opt),
                    out_shardings=(param, opt)))]
        label, operands = {"fused": ("sharded_step", 2),
                           "masked": ("sharded_step_masked", 3)}[variant]
        # opt_state shardings are pinned on BOTH sides: donation aliases
        # each state input buffer to its output, which is only valid when
        # the output keeps the input's sharding (XLA propagation would
        # otherwise shard tp-param momenta and break the aliasing)
        return [(label, fused, dict(
            in_shardings=(param, aux, opt) + (batch,) * operands,
            out_shardings=(param, aux, opt, None)
            + ((None,) if fp_on else ()),
            donate_argnums=(0, 1, 2),
            sig_argnums=tuple(range(3, 3 + operands))))]

    def _build(self, variant):
        """The live executables of ``variant``, compiled through the
        capture path on first use (and again after ``_drop_executables``):
        ``(_step,)``, ``(_step_masked,)`` or ``(_grads_fn, _apply_fn)``."""
        attrs = _VARIANT_EXECUTABLES[variant]
        if getattr(self, attrs[0]) is None:
            programs = self._programs(variant, self._shardings)
            for attr, (label, fn, kwargs) in zip(attrs, programs):
                setattr(self, attr, self._capture_exec(fn, label, **kwargs))
        return tuple(getattr(self, a) for a in attrs)

    @classmethod
    def for_multihost(cls, net, loss_fn, optimizer="sgd",
                      optimizer_params=None, axes=None, coordinator=None,
                      num_processes=None, process_id=None, **kwargs):
        """Build a trainer over a GLOBAL mesh spanning every process of a
        multi-host job (the pod entry point: jax.distributed bootstrap +
        all-devices mesh — the TPU-native replacement for the reference's
        dist_sync worker group).

        Bootstraps jax.distributed from args or the DMLC_* env protocol
        (kvstore/dist.py) if not already initialized. `axes` is the mesh
        axes dict (default: pure data parallel over all global devices).
        In `step`, each process feeds its LOCAL batch shard (numpy) —
        shards are assembled into the global batch along the dp axis.
        """
        from ..kvstore.dist import init_distributed

        init_distributed(coordinator, num_processes, process_id)
        import jax

        devs = jax.devices()
        axes = dict(axes or {"dp": len(devs)})
        mesh = create_mesh(axes, devs)
        return cls(net, loss_fn, optimizer, optimizer_params, mesh=mesh,
                   **kwargs)

    @classmethod
    def for_pod(cls, net, loss_fn, optimizer="sgd", optimizer_params=None,
                axes=None, coordinator=None, num_processes=None,
                process_id=None, topology=None, **kwargs):
        """Build a trainer over a pod with HOST-level failure domains
        (docs/distributed.md). Like ``for_multihost`` — jax.distributed
        bootstraps from args or the DMLC_* env protocol when the job is
        multi-process — but the mesh device order is host-major
        (``parallel.mesh.pod_mesh``), the watchdog's pod liveness layer
        is configured with this process's place in it, and a lost host
        recovers by excising its WHOLE device slice in one pod-wide
        mesh shrink. A single process partitions its local devices into
        ``MXNET_TPU_POD_HOSTS`` simulated host groups instead, so the
        same recovery logic runs in-process (CI's simulated pod)."""
        from ..kvstore.dist import init_distributed
        from .mesh import pod_mesh

        init_distributed(coordinator, num_processes, process_id)
        mesh, topo = pod_mesh(axes, topology=topology)
        trainer = cls(net, loss_fn, optimizer, optimizer_params,
                      mesh=mesh, **kwargs)
        return trainer.bind_pod(topo)

    def bind_pod(self, topology):
        """Attach a ``parallel.mesh.PodTopology``: folds the host
        grouping into the capture fingerprint, enables host-domain
        recovery in ``step``, and declares this process's place to the
        watchdog's pod liveness layer (heartbeats + dead-host
        detection). Returns self for chaining."""
        from ..resilience import watchdog as _watchdog

        self._pod = topology
        if topology is not None:
            _watchdog.configure_pod(topology.num_hosts, topology.this_host)
        return self

    @property
    def pod(self):
        """The bound PodTopology, or None off-pod."""
        return self._pod

    def set_learning_rate(self, lr):
        """Change the learning rate (gluon Trainer.set_learning_rate
        parity). Hyperparameters are baked into the compiled step, so the
        next step() recompiles — schedule changes at epoch boundaries, not
        per step (use a lr_scheduler-style optimizer for per-step decay)."""
        self._optimizer_params["learning_rate"] = float(lr)
        _, update = make_update_fn(self._optimizer,
                                   dict(self._optimizer_params))
        self._update = update
        self._drop_executables()  # rebuild (and recompile) with the new rate

    @property
    def learning_rate(self):
        return self._optimizer_params.get("learning_rate")

    @property
    def batch_sharding(self):
        """NamedSharding of the step's batch operands on the CURRENT
        mesh (re-derived on a mesh shrink) — the overlap handshake with
        the streaming input layer: ``io.stream.DevicePrefetcher.
        for_trainer`` places each prefetched batch with exactly this
        sharding, so ``step``'s own placement check
        (``is_equivalent_to``) skips the redundant device_put and the
        captured step consumes an already-resident batch."""
        return self._batch_sharding

    def _is_multiprocess(self):
        import jax

        return any(d.process_index != jax.process_index()
                   for d in self.mesh.devices.flat)

    def step(self, x, y, microbatches=None, length=None):
        """Run one sharded training step; returns the scalar loss.

        ``length`` (optional, (B,) int32 — ``StreamBatch.length``'s
        per-row valid token counts) masks pad tokens out of the loss:
        the step computes sum(loss*mask)/sum(mask) over the real tokens
        via a separate masked executable whose mask is built in-graph
        from an iota compare, so repeated masked calls stay ONE
        executable (length values are runtime data). The masked path is
        fused-only: combine it with ``microbatches`` > 1 and it raises.

        On a multi-process mesh, `x`/`y` are this process's LOCAL shard of
        the global batch (assembled with
        jax.make_array_from_process_local_data); single-process meshes
        take the full batch.

        ``microbatches=N`` executes the step as N accumulated
        microbatches (one optimizer update). Left at None, the step runs
        fused — and on ``RESOURCE_EXHAUSTED`` the elastic layer
        (resilience.elastic) transparently retries with doubling
        accumulation until it fits; the shrink is sticky for subsequent
        steps. The whole step runs under the step watchdog
        (MXNET_TPU_WATCHDOG_STEP_TIMEOUT).

        With a checkpoint manager attached (``checkpoint_manager=`` /
        ``enable_recovery``), a ``PeerLostError`` raised here — the
        ``peer_death`` fault, ``watchdog.mark_peer_dead``, or a
        collective stall with known-dead ranks — is survived in place:
        the mesh shrinks to the survivors, the latest reshardable
        checkpoint reloads onto it, sticky accumulation re-arms, and
        THIS batch re-runs (``last_recovery`` carries the restored
        manifest so schedule-aware drivers can rewind their data
        pipeline when the checkpoint cadence is coarser than one step).
        """
        with _obs_trace.span("train.sharded_step",
                             step=self._step_count + 1):
            return self._step_impl(x, y, microbatches, length)

    def _step_impl(self, x, y, microbatches, length=None):
        import warnings

        import jax

        from ..ndarray.ndarray import NDArray
        from ..resilience import elastic as _elastic
        from ..resilience import faults as _faults
        from ..resilience import watchdog as _watchdog

        # a schedule-table edit is a program change (kernel builders read
        # Pallas block sizes from the table at trace time): drop the
        # stale executables so the next build re-traces under the new
        # table — the retrace lands in the capture forensics, and the
        # AOT key (which folds the same token) can never false-hit
        if self._step is not None or self._step_masked is not None \
                or self._grads_fn is not None:
            from .. import capture as _capture

            if _capture._schedule_token() != getattr(self, "_sched_token",
                                                     None):
                self._drop_executables()
        if length is not None and microbatches is not None \
                and int(microbatches) != 1:
            raise ValueError(
                "length= (pad masking) runs the fused step only; "
                "accumulated microbatches would re-normalize the mask "
                "per slice — request microbatches=None")
        if isinstance(x, NDArray):
            x = x.data_
        if isinstance(y, NDArray):
            y = y.data_
        if isinstance(length, NDArray):
            length = length.data_
        with _obs_trace.span("sharded.h2d"):
            if self._multiproc:
                import numpy as np

                def assemble(a):
                    # a single-device local array (NDArray.data_) is still
                    # a process-local shard: pull to host and assemble
                    # globally
                    if isinstance(a, jax.Array) and \
                            a.sharding.num_devices > 1:
                        return a  # already a global array
                    return jax.make_array_from_process_local_data(
                        self._batch_sharding, np.asarray(a))

                x = assemble(x)
                y = assemble(y)
                if length is not None:
                    length = assemble(length)
            else:
                # skip the put when the batch already sits on the mesh
                # with the right sharding (the steady-state training
                # loop) — the redundant device_put costs ~0.5% of step
                # time (PERF.md round-5 wrapper A/B)
                bs = self._batch_sharding
                if not (isinstance(x, jax.Array) and
                        x.sharding.is_equivalent_to(bs, x.ndim)):
                    x = jax.device_put(x, bs)
                if not (isinstance(y, jax.Array) and
                        y.sharding.is_equivalent_to(bs, y.ndim)):
                    y = jax.device_put(y, bs)
                if length is not None and not (
                        isinstance(length, jax.Array) and
                        length.sharding.is_equivalent_to(bs, length.ndim)):
                    length = jax.device_put(length, bs)
        self._step_count += 1
        _watchdog.note_step(self._step_count)
        from ..resilience import integrity as _integrity

        # retained pre-step snapshot for the shadow-replay audit (None
        # unless this step is on the audit cadence)
        snap = _integrity.snapshot_step(self, x, y)
        rows = int(x.shape[0])
        shards = self._batch_shards()

        def fit_count(k):
            # largest accumulation count <= k that divides the batch into
            # whole microbatches splittable over the CURRENT dp shards
            # (a short tail batch, or a just-shrunk mesh, must fall back,
            # never drop rows)
            while k > 1 and (rows % k or (rows // k) % max(1, shards)):
                k //= 2
            return max(1, k)

        if microbatches is not None:
            n = int(microbatches)
            if n < 1 or rows % n or (rows // n) % max(1, shards):
                raise ValueError(
                    f"microbatches={n} does not divide the {rows}-row "
                    f"batch into whole microbatches splittable over "
                    f"{shards} dp shard(s); accumulation must never "
                    "silently drop tail rows")
        else:
            # sticky n was validated against the batch size that OOMed
            n = fit_count(self._elastic_n)
        if length is not None:
            n = 1  # masked path is fused-only (no mask re-normalization
            # per microbatch slice); an OOM here surfaces, never shrinks
        while True:
            try:
                # one guard per ATTEMPT: a legitimate elastic retry
                # (recompile + N microbatch launches) gets a fresh
                # deadline rather than being killed mid-recovery by the
                # budget the failed fused attempt already spent
                with _watchdog.guard("step",
                                     detail="parallel.ShardedTrainer.step",
                                     step=self._step_count):
                    _watchdog.check_peers(
                        detail="parallel.ShardedTrainer.step")
                    _faults.maybe_hang("hang_step")
                    # a pod host wedged (not crashed) at the collective
                    # entry: the stall converts to a dead-host verdict
                    # via the watchdog's pod liveness layer
                    _faults.maybe_hang("host_hang_collective")
                    _faults.maybe_oom_step()
                    with _obs_trace.span("sharded.execute",
                                         microbatches=n):
                        if n <= 1:
                            # built here, not before the loop: a mesh
                            # rebound mid-retry dropped the executable
                            fused, = self._build(
                                "fused" if length is None else "masked")
                            batch = (x, y) if length is None \
                                else (x, y, length)
                            outs = fused(self.params, self.aux,
                                         self.opt_state, *batch)
                            (self.params, self.aux, self.opt_state,
                             loss) = outs[:4]
                            self._last_fp_out = \
                                outs[4] if len(outs) > 4 else None
                        else:
                            loss = self._accum_step(n, x, y)
                    # SDC fault hooks land AFTER the step (corrupting the
                    # new state) and the shadow-replay audit runs INSIDE
                    # the attempt loop: a transient verdict rolls back and
                    # retries this batch, a sticky-device verdict raises
                    # PeerLostError into the same mesh-shrink recovery
                    # path as a dead peer
                    if self._last_fp_out is not None:
                        _integrity.note_fingerprint_step()
                    self.params = _faults.maybe_sdc_bitflip_param(
                        self.params)
                    self.params = _faults.maybe_sdc_sticky_param(
                        self.params, self.mesh)
                    if snap is not None:
                        verdict = _integrity.audit_step(
                            self, snap, n=n, length=length,
                            live_fp=self._last_fp_out)
                        if verdict == "retry":
                            continue
                        snap = None
                break
            except _watchdog.PeerLostError as e:
                # a dead peer is unrecoverable in place — but with a
                # checkpoint manager attached the run survives it: shrink
                # the mesh to the survivors, reload the latest
                # reshardable checkpoint onto it, and re-run this batch
                if self._ckpt_mgr is None \
                        or not _elastic.mesh_shrink_enabled() \
                        or (self._multiproc and self._pod is None):
                    # multi-process recovery needs host failure domains
                    # (bind_pod/for_pod): without the pod topology there
                    # is no survivable shrink of a global mesh
                    raise
                x, y = self._recover_peer_loss(e, x, y)
                snap = None  # pre-step snapshot is stale after a
                # checkpoint restore — the re-run batch is not audited
                if length is not None:
                    length = jax.device_put(length, self._batch_sharding)
                shards = self._batch_shards()
                if microbatches is not None:
                    if rows % n or (rows // n) % max(1, shards):
                        raise ValueError(
                            f"explicit microbatches={n} no longer splits "
                            f"the {rows}-row batch over the shrunk "
                            f"{shards}-shard mesh; request a compatible "
                            "schedule") from e
                else:
                    n = fit_count(max(n, self._elastic_n))
                continue
            except Exception as e:
                if microbatches is not None or length is not None \
                        or not (_elastic.enabled()
                                and _elastic.is_oom_error(e)):
                    # explicit schedules are the caller's contract —
                    # elastic retry applies only to the implicit path
                    raise
                if self._multiproc:
                    # microbatch slicing of a non-fully-addressable
                    # global batch is an eager cross-process op jax
                    # cannot run; surface the REAL OOM rather than a
                    # masked addressability error mid-retry
                    warnings.warn(
                        "step OOM on a multi-process mesh: elastic "
                        "microbatch retry is single-process only "
                        "(docs/resilience.md) — lower the per-host "
                        "batch or request microbatches= explicitly "
                        "at a size every process can slice locally")
                    raise
                _elastic._STATS["elastic_oom_events"] += 1
                self._check_state_alive(e)
                nxt = _elastic.next_microbatches(n, rows, shards)
                if nxt is None:
                    raise
                _elastic._STATS["elastic_shrinks"] += 1
                warnings.warn(
                    f"training step OOM at {n} microbatch(es) over a "
                    f"{rows}-row batch; retrying as {nxt} accumulated "
                    f"microbatches of {rows // nxt} rows")
                n = nxt
        if microbatches is None and n > self._elastic_n:
            self._elastic_n = n  # sticky: don't re-OOM every step (a
            # short tail batch's fallback must not discard the shrink)
        if _integrity.preempt_requested() or _faults.maybe_preempt():
            # SIGTERM (or a drilled preempt): the in-flight step is done —
            # emergency checkpoint, drain, exit cleanly
            _integrity.preempt_exit(self, loss=loss)
        return loss

    def _check_state_alive(self, cause):
        """A fused step donates params/aux/opt_state; if the failure
        happened after donation invalidated any of them, a retry would
        compute on deleted buffers. Surface that explicitly instead."""
        import jax

        leaves = (list(self.params.values()) + list(self.aux.values())
                  + jax.tree_util.tree_leaves(self.opt_state))
        for v in leaves:
            if getattr(v, "is_deleted", lambda: False)():
                raise RuntimeError(
                    "step failed after its donated inputs were "
                    "invalidated; elastic retry is impossible — "
                    "restore from the last checkpoint "
                    "(resilience.CheckpointManager.restore_latest)"
                ) from cause

    @staticmethod
    def _host_local_batch(arr):
        """A batch operand safe to re-place on a shrunk mesh. On a real
        pod the assembled global batch is NOT fully addressable and
        jax cannot reshard it onto the survivors' smaller mesh — fall
        back to this host's own rows (its addressable shards, in batch
        order), which is exactly what this process fed ``step``."""
        import jax

        if not isinstance(arr, jax.Array) or arr.is_fully_addressable:
            return arr
        import numpy as np

        shards = {tuple(sl.start or 0 for sl in s.index):
                  np.asarray(s.data) for s in arr.addressable_shards}
        return np.concatenate(
            [shards[k] for k in sorted(shards)], axis=0)

    def _recover_peer_loss(self, err, x, y):
        """Mesh-shrink resume: rebuild a smaller mesh from the surviving
        ranks, reload the latest (reshardable, v2) checkpoint onto it,
        re-arm the sticky elastic accumulation so the per-device
        microbatch stays where it last fit, and return the batch
        re-placed for the new mesh so the caller retries this step.
        The recovery is logged, counted (``watchdog_peer_recoveries``,
        ``elastic_mesh_shrinks``), and stamped into the crash report
        (``watchdog.note_peer_recovery``). Raises if no viable smaller
        mesh or no valid checkpoint exists — then the PeerLostError was
        genuinely terminal."""
        import warnings

        import jax

        from ..resilience import elastic as _elastic
        from ..resilience import watchdog as _watchdog
        from .mesh import MeshShrinkError, shrink_mesh

        if self._pod is not None:
            hosts = (list(getattr(err, "hosts", ()) or ())
                     or _watchdog.dead_hosts())
            if hosts:
                return self._recover_host_loss(err, x, y, hosts)
        dead = _watchdog.dead_peers() or list(getattr(err, "ranks", ()))
        old_axes = {str(a): int(s) for a, s in
                    zip(self.mesh.axis_names, self.mesh.devices.shape)}
        try:
            new_mesh = shrink_mesh(self.mesh, dead,
                                   batch_axis=self._batch_axis)
        except MeshShrinkError:
            raise err  # nothing viable left: the loss really is terminal
        import math

        batch_names = self._batch_axis_names()
        old_dp = math.prod(int(old_axes.get(a, 1)) for a in batch_names)
        new_axes = {str(a): int(s) for a, s in
                    zip(new_mesh.axis_names, new_mesh.devices.shape)}
        new_dp = math.prod(int(new_axes.get(a, 1)) for a in batch_names)
        self._bind_mesh(new_mesh)
        # the excised ranks are no longer part of the job: re-admit the
        # collectives (kvstore guards included) before the restore's
        # device_puts and the retried step
        _watchdog.reset_peers()
        manifest = self._ckpt_mgr.restore_latest(trainer=self)
        if manifest is None:
            raise RuntimeError(
                f"peer rank(s) {dead} lost and no valid checkpoint exists "
                f"to reload onto the shrunk {new_dp}-shard mesh; cannot "
                "recover") from err
        self._elastic_n = _elastic.rearm_microbatches(
            self._elastic_n, old_dp, new_dp)
        _elastic._STATS["elastic_mesh_shrinks"] += 1
        _watchdog.note_peer_recovery(err, manifest, old_axes, new_axes)
        self.last_recovery = manifest
        axis_label = "x".join(batch_names)
        warnings.warn(
            f"peer rank(s) {dead} lost: resumed from checkpoint step "
            f"{manifest.get('step')} on a mesh shrunk "
            f"{old_dp} -> {new_dp} '{axis_label}' shard(s); "
            "this step re-runs on the survivors (capacity is reduced — "
            "see the crash report)")
        bs = self._batch_sharding
        x, y = self._host_local_batch(x), self._host_local_batch(y)
        return jax.device_put(x, bs), jax.device_put(y, bs)

    def _recover_host_loss(self, err, x, y, hosts):
        """Host-domain mesh-shrink resume (docs/distributed.md): the
        whole failure domain — every device rank of the dead host(s) —
        leaves the mesh in ONE shrink. The coordinated restart:
        survivors barrier (so nobody restores against a checkpoint a
        faster peer is about to supersede), the global mesh is rebuilt
        host-major from the surviving hosts (renumbered 0..k-1), the
        watchdog pod layer is re-declared for the smaller pod at the
        next generation, and the latest reshardable v2 checkpoint is
        reloaded onto the shrunk topology. Raises when no host-aligned
        shrink exists or no valid checkpoint survives — then the loss
        was genuinely terminal."""
        import math
        import warnings

        import jax

        from ..resilience import elastic as _elastic
        from ..resilience import watchdog as _watchdog
        from .mesh import MeshShrinkError, shrink_mesh_hosts

        hosts = sorted({int(h) for h in hosts})
        old_axes = {str(a): int(s) for a, s in
                    zip(self.mesh.axis_names, self.mesh.devices.shape)}
        try:
            _watchdog.pod_barrier()
        except _watchdog.PeerLostError as late:
            # a survivor died before making the barrier: fold it into
            # this recovery instead of recovering twice
            hosts = sorted(set(hosts) | set(getattr(late, "hosts", ())))
        try:
            new_mesh, new_topo, kept = shrink_mesh_hosts(
                self.mesh, hosts, self._pod,
                batch_axis=self._batch_axis)
        except MeshShrinkError:
            raise err  # no host-aligned smaller mesh: genuinely terminal
        batch_names = self._batch_axis_names()
        old_dp = math.prod(int(old_axes.get(a, 1)) for a in batch_names)
        new_axes = {str(a): int(s) for a, s in
                    zip(new_mesh.axis_names, new_mesh.devices.shape)}
        new_dp = math.prod(int(new_axes.get(a, 1)) for a in batch_names)
        gen = (_watchdog.pod_info() or {}).get("generation", 0) + 1
        self._bind_mesh(new_mesh)
        self._pod = new_topo
        if getattr(self._ckpt_mgr, "_pod", None) is not None:
            # the manager's distributed commit must follow the shrunk,
            # renumbered topology too
            self._ckpt_mgr.bind_pod(new_topo)
        # the dead generation's bookkeeping must not leak into the
        # renumbered pod: fresh peer set, fresh host registry/heartbeats
        _watchdog.reset_peers()
        _watchdog.configure_pod(new_topo.num_hosts, new_topo.this_host,
                                generation=gen)
        manifest = self._ckpt_mgr.restore_latest(trainer=self)
        if manifest is None:
            raise RuntimeError(
                f"pod host(s) {hosts} lost and no valid checkpoint "
                f"exists to reload onto the shrunk {new_dp}-shard mesh; "
                "cannot recover") from err
        self._elastic_n = _elastic.rearm_microbatches(
            self._elastic_n, old_dp, new_dp)
        _elastic._STATS["elastic_mesh_shrinks"] += 1
        _watchdog.note_peer_recovery(err, manifest, old_axes, new_axes)
        self.last_recovery = manifest
        axis_label = "x".join(batch_names)
        warnings.warn(
            f"pod host(s) {hosts} lost: resumed from checkpoint step "
            f"{manifest.get('step')} on a pod shrunk to host(s) "
            f"{list(kept)} ({old_dp} -> {new_dp} '{axis_label}' "
            "shard(s)); this step re-runs on the survivors (capacity is "
            "reduced — see the crash report)")
        bs = self._batch_sharding
        x, y = self._host_local_batch(x), self._host_local_batch(y)
        return jax.device_put(x, bs), jax.device_put(y, bs)

    def _accumulate(self, n, programs, batch_sharding, state, x, y, live):
        """One optimizer update from n accumulated microbatches, on the
        live executables or the shadow replay's (``programs`` =
        ``(grads, apply)``): grads are computed per microbatch on the
        SAME params, summed, divided by n (mean-of-means == full-batch
        mean for equal slices), then applied once. aux chains through
        microbatches sequentially. Returns ``(params, aux, opt_state,
        loss, fingerprint or None)``."""
        import jax
        import jax.numpy as jnp

        from ..resilience import faults as _faults
        from ..resilience import integrity as _integrity

        grads_fn, apply_fn = programs
        params, aux, opt_state = state
        mb = int(x.shape[0]) // n
        acc = None
        loss_sum = None
        for i in range(n):
            sl = slice(i * mb, (i + 1) * mb)
            # an eager slice of a dp-sharded batch comes back replicated;
            # re-place it so the grad executable sees the batch sharding
            x_i = jax.device_put(x[sl], batch_sharding)
            y_i = jax.device_put(y[sl], batch_sharding)
            grads, aux, loss = grads_fn(params, aux, x_i, y_i)
            acc = grads if acc is None else jax.tree.map(jnp.add, acc, grads)
            loss_sum = loss if loss_sum is None else loss_sum + loss
        inv = 1.0 / n
        acc = jax.tree.map(lambda g: g * inv, acc)
        if live:
            acc = _faults.maybe_sdc_bitflip_grad(acc)
        params, opt_state = apply_fn(params, acc, opt_state)
        fp = None
        if _integrity.fingerprint_enabled():
            # the accumulated path has no single fused executable to grow
            # an output on — fold the same fingerprint host-side over the
            # applied params and the accumulated (divided) grads
            import numpy as np

            fp = np.uint32(_integrity.step_fold_host(
                {k: np.asarray(v) for k, v in params.items()},
                {k: np.asarray(v) for k, v in acc.items()}))
        return params, aux, opt_state, loss_sum / n, fp

    def _accum_step(self, n, x, y):
        """The live step as n accumulated microbatches (``_accumulate``).
        Bitwise identical to an explicit step(..., microbatches=n)."""
        from ..resilience import elastic as _elastic

        _elastic._STATS["elastic_accum_steps"] += 1
        (self.params, self.aux, self.opt_state, loss,
         self._last_fp_out) = self._accumulate(
            n, self._build("accum"), self._batch_sharding,
            (self.params, self.aux, self.opt_state), x, y, live=True)
        return loss

    def get_states_bytes(self):
        """Serialize opt_state (host-side npz keyed by pytree path) — the
        byte form consumed by resilience.CheckpointManager and
        save_states."""
        import io

        import numpy as np

        import jax

        flat, _ = jax.tree_util.tree_flatten_with_path(self.opt_state)
        entries = {jax.tree_util.keystr(path): np.asarray(leaf)
                   for path, leaf in flat}
        buf = io.BytesIO()
        np.savez(buf, **entries)
        return buf.getvalue()

    def set_states_bytes(self, data):
        """Restore opt_state from get_states_bytes output. Every leaf is
        re-placed with its original NamedSharding (via _opt_sharding), so
        sharded optimizer state comes back sharded — loading it
        replicated would break step donation aliasing AND silently
        multiply per-device memory."""
        import io

        import numpy as np

        f = np.load(io.BytesIO(data), allow_pickle=False)
        self.set_states_arrays({k: f[k] for k in f.files})

    def set_states_arrays(self, mapping):
        """Restore opt_state from a {keystr: host array} mapping (the
        form v2 reshardable checkpoints reassemble shard payloads into).
        Each leaf is re-placed with THIS trainer's NamedSharding on its
        CURRENT mesh — which is exactly how checkpoint state saved on a
        different dp-shard count lands correctly after a mesh shrink.
        Validates the mapping covers the opt_state tree exactly."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        stored = dict(mapping)
        shardings = self._opt_sharding()

        def restore(path, leaf, sh):
            key = jax.tree_util.keystr(path)
            if key not in stored:
                raise ValueError(
                    f"trainer states file is missing opt_state leaf {key} "
                    "(saved from a different optimizer/model?)")
            arr = stored.pop(key)
            if tuple(arr.shape) != tuple(np.shape(leaf)):
                raise ValueError(
                    f"opt_state leaf {key} has shape {arr.shape} in the "
                    f"states file but {np.shape(leaf)} in this trainer")
            return jax.device_put(jnp.asarray(arr), sh)

        new_state = jax.tree_util.tree_map_with_path(
            restore, self.opt_state, shardings)
        if stored:
            raise ValueError(
                "trainer states file has extra opt_state leaves "
                f"{sorted(stored)[:3]} (saved from a different "
                "optimizer/model?)")
        self.opt_state = new_state

    def save_states(self, fname):
        """Save optimizer state to a file, atomically (temp + fsync +
        rename); counterpart of gluon Trainer.save_states."""
        from ..resilience.checkpoint import atomic_write_bytes

        atomic_write_bytes(fname, self.get_states_bytes())

    def load_states(self, fname):
        """Load optimizer state saved by save_states, restoring each
        leaf's mesh sharding."""
        with open(fname, "rb") as f:
            self.set_states_bytes(f.read())

    def sync_to_net(self):
        """Write the sharded parameter state back into the gluon net
        (collapsed to one device so eager ops keep working)."""
        import jax

        from .functional import RNG_KEY
        from .. import random as _random

        dev = self.mesh.devices.flat[0]
        multiproc = self._multiproc

        def fetch(v):
            if multiproc:
                # replicated values: the local shard IS the full array;
                # cross-process-sharded params would need an allgather
                shard = v.addressable_shards[0]
                if shard.data.shape != v.shape:
                    raise NotImplementedError(
                        "sync_to_net on a multi-host mesh supports "
                        "replicated params only; allgather sharded params "
                        "explicitly")
                return jax.device_put(shard.data, jax.local_devices()[0])
            return jax.device_put(v, dev)

        for name, p in self.net.collect_params().items():
            if name in self.params:
                p.data()._set_data(fetch(self.params[name]))
            elif name in self.aux:
                p.data()._set_data(fetch(self.aux[name]))
        if RNG_KEY in self.aux:
            _random.generator_key()._set_data(fetch(self.aux[RNG_KEY]))

    @property
    def last_fingerprint(self):
        """uint32 in-graph fingerprint of the last executed step, or None
        when fingerprinting is off (resilience.integrity). Reading it is
        the only host sync — the step itself never blocks on it."""
        if self._last_fp_out is None:
            return None
        import numpy as np

        return int(np.asarray(self._last_fp_out))

    def integrity_replay(self, mesh, params, aux, opt_state, x, y,
                         microbatches=1, length=None):
        """Re-execute ONE training step from host-side pre-step state on
        an alternate same-shape mesh (the shadow slice of the SDC audit,
        resilience.integrity.audit_step). Compiles the live variant's own
        programs (``_programs``: fused, pad-masked, or n-microbatch
        accumulation — the variants are not bitwise-interchangeable,
        their grad arithmetic differs) for the shadow mesh, which keeps
        the live mesh's shape and axis names so GSPMD emits the same
        collective structure and float reduction order. Returns ``(host
        new_params dict, uint32 fingerprint or None)``. The trainer's own
        state, mesh, and executables are untouched; replay executables
        are plain non-donating jits cached per (shadow devices, variant,
        capture fingerprint)."""
        import numpy as np

        import jax

        from ..resilience import integrity as _integrity

        n = max(1, int(microbatches))
        variant = ("masked" if length is not None
                   else "fused" if n <= 1 else "accum")
        key = (tuple(int(d.id) for d in mesh.devices.flat), n,
               length is not None, _integrity.fingerprint_enabled(),
               self._capture_fingerprint())
        cached = getattr(self, "_replay_cache", None)
        if cached is None or cached[0] != key:
            shardings = self._shardings_on(mesh)
            fns = tuple(
                jax.jit(fn, in_shardings=kwargs["in_shardings"],
                        out_shardings=kwargs["out_shardings"])
                for _, fn, kwargs in self._programs(variant, shardings))
            cached = self._replay_cache = (key, shardings, fns)
        _, (param_sh, aux_sh, batch_sh, opt_sh), fns = cached

        def put(tree, shardings):
            return jax.tree.map(
                lambda leaf, sh: jax.device_put(np.asarray(leaf), sh),
                tree, shardings)

        state = (put(params, param_sh), put(aux, aux_sh),
                 put(opt_state, opt_sh))
        batch = tuple(jax.device_put(np.asarray(a), batch_sh)
                      for a in (x, y) + (() if length is None
                                         else (length,)))
        if variant == "accum":
            outs = self._accumulate(n, fns, batch_sh, state, *batch,
                                    live=False)
        else:
            outs = fns[0](*state, *batch)
        fp = outs[4] if len(outs) > 4 else None
        return ({k: np.asarray(v) for k, v in outs[0].items()},
                None if fp is None else int(np.asarray(fp)))
