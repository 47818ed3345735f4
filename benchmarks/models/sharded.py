"""What the training builders share: the program's normal training path.

    zoo constructor -> net.initialize() on the chip from the seed
      -> parallel.ShardedTrainer(mesh, bf16 policy) -> capture.capture()

the checks that the step stayed on that path (one captured executable,
no eager fallback, no elastic out-of-memory retry as microbatches),
taken from ``chip_smoke.py``'s train phase, and the comparison with the
float32 reference that decides ``correct`` in training.
"""
from __future__ import annotations


class TrainJob:
    """What ``loops/train_steps.py`` drives, and what it checks."""

    def __init__(self, net, trainer, step, items_per_step, make_ring,
                 reference_weights, reference_fn, train, positions=None,
                 statistics=None):
        self.net = net
        self.trainer = trainer
        self.step = step                        # step(x, y) -> device loss
        self.items_per_step = items_per_step    # global batch, in items
        self.make_ring = make_ring              # (seed, n) -> [(x, y)] * n
        self.reference_weights = reference_weights  # () -> the plain tree
        # (weights, x, y, positions) -> (loss, logits at positions,
        # (means, variances) of the batch in the normalisation layers
        # that keep them, or None)
        self.reference_fn = reference_fn
        self.train = train      # the configuration's "train" group
        # (seed) -> (B, P) positions of each row whose logits are
        # compared; None where a row has one set of logits, all compared
        self.positions = positions
        # (the forward pass's moved auxiliary state) -> (means,
        # variances) as the reference gives them; None without such layers
        self.statistics = statistics
        self._forward = self._reference = None

    def _replicated(self, tree):
        """On every chip of the mesh, so that a function jitted over a
        batch that is sharded there can read it; the compiler partitions
        the plain program as it does the step."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.device_put(
            tree, NamedSharding(self.trainer.mesh, PartitionSpec()))

    def initial_params(self):
        """The net's parameters as it was initialised (the trainer works
        on copies), by the program's names."""
        from mxnet_tpu import parallel

        return parallel.param_arrays(self.net)

    def policy_forward(self):
        """(params, aux, x, positions) -> (float32 logits, batch
        statistics or None) of the net's training-mode forward under
        the configuration's precision policy: the function
        ``ShardedTrainer`` differentiates (``parallel.functional_call``),
        with floating parameters and inputs cast to ``compute_dtype``
        and the auxiliary state left as it is, as the trainer has them,
        so the kernels see what they see in the step."""
        import jax.numpy as jnp

        from mxnet_tpu import parallel

        fwd = parallel.functional_call(self.net, train=True)
        dtype = self.train["compute_dtype"]

        def cast(v):
            if dtype and jnp.issubdtype(v.dtype, jnp.floating):
                return v.astype(dtype)
            return v

        def outputs(params, aux, x, positions):
            out, moved = fwd({k: cast(v) for k, v in params.items()}, aux,
                             cast(x))
            if positions is not None:
                out = jnp.take_along_axis(out, positions[:, :, None], axis=1)
            return (out.astype(jnp.float32),
                    self.statistics(moved) if self.statistics else None)

        return outputs

    def program_outputs(self, params, x, positions):
        import jax

        from mxnet_tpu import parallel

        if self._forward is None:
            self._forward = jax.jit(self.policy_forward())
        return self._forward(
            self._replicated(params),
            self._replicated(parallel.aux_arrays(self.net)), x, positions)

    def check(self, first_loss, x, y, seed, params=None):
        """The first step against the float32 reference on the net's
        initial weights and the batch (x, y): its loss, and what the
        forward pass under the training policy gives. The loss alone
        cannot see the trunk (at initialisation it is ln(classes) to a
        few thousandths whatever the blocks compute), so logits are
        compared one by one: the largest difference finds a fault in one
        place (a mask, a block), the median one everywhere (a
        precision). Where layers keep statistics of the batch, those are
        compared too: averages over the whole batch, they hold to the
        reference far closer than a logit does after fifty layers of
        bf16. ``params`` stands in for the initial weights on the
        program's side only (``degrade.py``). Returns ``notes`` (the
        reasons it is not correct), ``said`` (the line for the log) and
        the differences."""
        import math

        import jax
        import numpy as np

        positions = self.positions(seed) if self.positions else None
        if self._reference is None:
            self._reference = jax.jit(self.reference_fn)
        ref_loss, ref_logits, ref_stats = self._reference(
            self._replicated(self.reference_weights()), x, y, positions)
        ref_loss, ref_logits = float(ref_loss), np.asarray(ref_logits)
        got, stats = self.program_outputs(
            self.initial_params() if params is None else params, x,
            positions)
        got = np.asarray(got)
        diff = np.abs(got - ref_logits)
        facts = {"loss": abs(first_loss - ref_loss),
                 "logits_max": float(diff.max()),
                 "logits_median": float(np.median(diff))}
        tol = {"loss": self.train["loss_tolerance"]["abs"],
               "logits_max": self.train["logits_tolerance"]["max"],
               "logits_median": self.train["logits_tolerance"]["median"]}
        what = {"loss": "the first-step loss",
                "logits_max": "one logit of the forward pass",
                "logits_median": "the median logit of the forward pass"}
        said = (f"first-step loss {first_loss:.6f} vs float32 reference "
                f"{ref_loss:.6f} (|diff| {facts['loss']:.6f}, tolerance "
                f"{tol['loss']}); forward pass under the training policy vs "
                f"float32 reference, |logit diff| over {diff.size} logits at "
                f"|logit| <= {np.max(np.abs(ref_logits)):.3f}: max "
                f"{facts['logits_max']:.6f} (tolerance {tol['logits_max']}),"
                f" median {facts['logits_median']:.6f} (tolerance "
                f"{tol['logits_median']})")
        if stats is not None:
            # each channel's mean in units of its standard deviation,
            # and its variance as a share of itself
            (mean, var), (ref_mean, ref_var) = (
                [np.asarray(a, np.float64) for a in pair]
                for pair in (stats, ref_stats))
            off = np.concatenate([
                np.abs(mean - ref_mean) / np.sqrt(ref_var + 1e-5),
                np.abs(var - ref_var) / (ref_var + 1e-5)])
            facts["statistics_median"] = float(np.median(off))
            tol["statistics_median"] = \
                self.train["statistics_tolerance"]["median"]
            what["statistics_median"] = \
                "the median batch statistic of the forward pass"
            said += (f"; batch statistics of {mean.size} normalised "
                     f"channels, off by a median "
                     f"{facts['statistics_median']:.6f} of their spread "
                     f"(tolerance {tol['statistics_median']}), at most "
                     f"{off.max():.6f}")
        notes = [f"{what[k]} is {facts[k]} off the float32 reference, over "
                 f"the tolerance {tol[k]}" for k in what
                 if not facts[k] <= tol[k]]
        if not math.isfinite(first_loss):
            notes.append(f"the first-step loss is {first_loss}")
        return {"notes": notes, "said": said, **facts}

    def program_counters(self):
        from mxnet_tpu import capture
        from mxnet_tpu.resilience import elastic

        s = capture.stats()
        return {"capture_misses": s["capture_misses"],
                "capture_retraces": s["capture_retraces"],
                "capture_fallback_eager": s["capture_fallback_eager"],
                "elastic_oom_events": elastic.stats()["elastic_oom_events"]}

    def path_faults(self, before):
        """Reasons the window did not run on the one captured executable
        (empty when it did)."""
        now = self.program_counters()
        faults = []
        if now["capture_misses"] != before["capture_misses"] \
                or now["capture_retraces"] != before["capture_retraces"]:
            faults.append("the step was captured again inside the window")
        if now["capture_fallback_eager"] != before["capture_fallback_eager"]:
            faults.append("a step fell back to eager execution")
        if now["elastic_oom_events"] != before["elastic_oom_events"]:
            faults.append("a step ran out of memory and was re-run as "
                          "microbatches")
        return faults


def make_trainer(net, config, traffic, devices):
    """(trainer, captured step) over ``traffic["mesh"]`` on ``devices``."""
    from mxnet_tpu import capture, gluon, parallel

    train = config["train"]
    mesh = parallel.create_mesh(dict(traffic["mesh"]), devices)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), train["optimizer"],
        dict(train["optimizer_params"]), mesh=mesh,
        dtype=train["compute_dtype"], remat=train.get("remat") or False)
    return trainer, capture.capture(trainer)
