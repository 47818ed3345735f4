"""What the training builders share: the program's normal training path.

    zoo constructor -> net.initialize() on the chip from the seed
      -> parallel.ShardedTrainer(mesh, bf16 policy) -> capture.capture()

the checks that the step stayed on that path (one captured executable,
no eager fallback, no elastic out-of-memory retry as microbatches),
taken from ``chip_smoke.py``'s train phase, and the comparison with the
float32 reference that decides ``correct`` in training.

Also what ``"routing": "balanced"`` in a traffic file asks of a
configuration that holds a share of its experts (``benchmarks/README.md``,
"A cell that holds a share of the experts"): ``TrainJob.prepare``,
between the ring and the warm-up, gives each expert layer a routing bias
under which the ring routes evenly (``balance_routing``): layer after
layer in the net's order, the published balancer's rule (the bias of an
expert with more than the mean load goes down, of one with less goes up)
is iterated on the ring's own scores until every expert's share of the
ring's assignments is within ``BALANCE_TOLERANCE`` of ``top_k /
experts``. The layer still routes over all its experts and computes its
own; the bias is then a weight like any other, which the reference is
handed. A bias is one vector a layer, so it evens the ring as a whole:
with weights from a seed one sequence's tokens route alike and
another's elsewhere, and only a ring of one batch (the traffic's
``"ring": 1``) is even in every step. Nothing holds that routing
through the window but the configuration's step size (the router
trains like every other weight); ``TrainJob.routing_check`` holds the
step's own counts to it, at the first step and at the last, and the
run is not correct where they left it.
"""
from __future__ import annotations

from benchmarks.harness.manifest import ManifestError

BALANCE_STEP = 0.05     # the first move of an expert's bias, in score
BALANCE_ROUNDS = 200    # the rule has failed where this many do not do
BALANCE_TOLERANCE = 0.01    # of the mean load: what the walk evens to
# What ``correct`` holds the step's own counter to (``routing_check``;
# PERF.md section 2 has the readings, my chip runs, PR 37). An expert
# held, at the first step, against ``tokens x top_k / experts``: sound
# runs read at most 0.020 (the step's kernels round a score or two in a
# hundred the other way from the walk), weights from a seed with no
# bias set 0.52 and more. A layer's experts held together, at the last
# step, against their share: sound runs at most 0.113 (a batch stepped
# on fifty times at 1e-7 still turns one weight in twenty-five by a
# unit of bf16), a step size of 1e-5 0.40 and more within five steps.
ROUTING_FIRST_STEP_LIMIT = 0.05
ROUTING_LAST_STEP_LIMIT = 0.25


def _caster(dtype):
    """v -> v as ``ShardedTrainer`` casts a parameter or an input under
    the policy: floating values to ``dtype`` (None: as they are)."""
    import jax.numpy as jnp

    def cast(v):
        if dtype and jnp.issubdtype(v.dtype, jnp.floating):
            return v.astype(dtype)
        return v

    return cast


class TrainJob:
    """What ``loops/train_steps.py`` drives, and what it checks."""

    def __init__(self, net, trainer, step, items_per_step, make_ring,
                 reference_weights, reference_fn, train, positions=None,
                 statistics=None, routing_parts=None):
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
        # () -> the trunk as ``Part``s in the order the tokens pass it;
        # None for a configuration without expert layers
        self.routing_parts = routing_parts
        # assignments an expert gets of one batch at an even share,
        # ``tokens x top_k / experts``: set by ``balance_routing``
        self.expert_share = None
        self._forward = self._reference = None

    def prepare(self, ring, traffic, log=print):
        """What the traffic file asks of the job once the ring exists,
        before the warm-up; nothing where it asks nothing."""
        asked = traffic.get("routing")
        if asked is None:
            return
        if asked != "balanced":
            raise ManifestError(f"traffic {traffic.get('name')!r}: routing "
                                f"{asked!r} is not known; known: 'balanced'")
        balance_routing(self, ring, log)

    def routing_check(self, first, last):
        """Holds what the traffic says of the routing to what ran, by
        the program's own counter: ``first`` and ``last`` are what the
        expert layers' ``expert_tokens`` state held after the first step
        and after the window's last, per layer (assignments to each
        expert held, tokens that chose none). Every expert held is
        within ``ROUTING_FIRST_STEP_LIMIT`` of an even share at the
        first step, and every layer's experts held together within
        ``ROUTING_LAST_STEP_LIMIT`` of theirs at the last. Returns
        ``notes``, ``compared`` and ``reported`` (the counts, for the
        result's line); all empty where the traffic asked for no
        routing."""
        import numpy as np

        if self.expert_share is None:
            return {"notes": [], "compared": {}, "reported": {}}
        if not first or not last:
            return {"notes": ["the traffic asks for a balanced routing and "
                              "the model file reads no expert_tokens"],
                    "compared": {}, "reported": {}}
        first, last = (np.asarray([c for c, _ in counts], np.float64)
                       for counts in (first, last))
        facts = {
            "routing_first_step": float(np.max(np.abs(
                first / self.expert_share - 1.0))),
            "routing_last_step": float(np.max(np.abs(
                last.sum(axis=1) / (self.expert_share * last.shape[1])
                - 1.0)))}
        limit = {"routing_first_step": ROUTING_FIRST_STEP_LIMIT,
                 "routing_last_step": ROUTING_LAST_STEP_LIMIT}
        what = {"routing_first_step": "an expert held, at the first step,",
                "routing_last_step": "a layer's experts held, at the "
                                     "window's last step,"}
        return {
            "notes": [f"{what[k]} got {facts[k]:.4f} off an even share of "
                      f"the assignments ({self.expert_share:.0f} an expert),"
                      f" over the limit {limit[k]}" for k in facts
                      if not facts[k] <= limit[k]],
            "compared": {k: {"value": facts[k], "limit": limit[k]}
                         for k in facts},
            "reported": {"held_assignments": {
                "even_share_an_expert": self.expert_share,
                "first_step": first.astype(int).tolist(),
                "last_step": last.astype(int).tolist()}}}

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
        cast = _caster(self.train["compute_dtype"])

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
        reasons it is not correct), ``said`` (the line for the log), the
        differences, and under ``compared`` each of them beside its
        limit, for the result's line."""
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
        return {"notes": notes, "said": said, **facts,
                "compared": {k: {"value": facts[k], "limit": tol[k]}
                             for k in facts}}

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

    refuse_without_bias(net, config, traffic)
    train = config["train"]
    mesh = parallel.create_mesh(dict(traffic["mesh"]), devices)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), train["optimizer"],
        dict(train["optimizer_params"]), mesh=mesh,
        dtype=train["compute_dtype"], remat=train.get("remat") or False)
    return trainer, capture.capture(trainer)


# ------------------------------------------- a share of the experts held

class Part:
    """One part of a net's trunk, as ``balance_routing`` walks it:
    ``fn`` takes what the part before it gave (the first part: the
    tokens) and is made of the gluon ``blocks``. Where the part holds an
    expert layer, ``seen`` (a block of the net) gives what that layer's
    router sees of the part's input, and ``router`` says how it scores:
    ``{"weight", "bias"}`` (the two parameters' names), ``"top_k"``,
    ``"score_func"`` ('sigmoid' or 'softmax') and ``"held"`` ((first
    expert, experts) this chip holds: the log says what one batch gives
    them)."""

    def __init__(self, blocks, fn, seen=None, router=None):
        self.blocks, self.fn = list(blocks), fn
        self.seen, self.router = seen, router


def refuse_without_bias(net, config, traffic):
    """Refuses, by name, a traffic that asks for a balanced routing of
    a configuration without a routing bias of its own. Before the
    trainer is built, so that nothing is compiled for a refused cell."""
    if "routing" not in traffic:
        return
    if not [n for n in net.collect_params().keys()
            if n.endswith("expert_bias")]:
        raise ManifestError(
            f"traffic {traffic.get('name')!r} asks for routing "
            f"{traffic['routing']!r}, and configuration "
            f"{config.get('name')!r} has no routing bias of its own (no "
            "expert_bias parameter): a balanced routing is what a "
            "per-expert bias keeps. A model without one says under "
            "'assumed' what holds its balance before it may use such a "
            "traffic (benchmarks/README.md)")


def _pure(blocks, fn):
    """(values by parameter name, x) -> fn(x), with the blocks'
    parameters and auxiliary state taken from the values: the view
    ``ShardedTrainer`` differentiates, of a part of the net."""
    from mxnet_tpu import gluon, parallel

    class _Of(gluon.Block):
        def __init__(self):
            super().__init__(prefix="")
            for blk in blocks:
                self.register_child(blk)

        def forward(self, x):
            return fn(x)

    part = _Of()
    own = set(part.collect_params().keys())
    pure = parallel.functional_call(part, train=True)
    return lambda values, x: pure(
        {k: v for k, v in values.items() if k in own}, {}, x)[0]


def balanced_bias(scores, top_k, tolerance, step=BALANCE_STEP,
                  rounds=BALANCE_ROUNDS):
    """``scores`` (assignments' tokens, experts) float32 -> (bias,
    assignments to each expert under it, rounds taken): the published
    balancer's rule, ``bias -= step * sign(load - mean load)``, on these
    tokens until every expert's load is within ``tolerance`` of the
    mean, ``tokens * top_k / experts``. An expert's step halves each
    time its load crosses the mean, so the rule settles instead of
    swinging; the bias starts at zero. Traced: call it under ``jit``."""
    import jax
    import jax.numpy as jnp

    tokens, experts = scores.shape
    mean = tokens * top_k / experts

    def loads(bias):
        moved = scores + bias
        kth = jax.lax.top_k(moved, top_k)[0][:, -1:]
        return jnp.sum(moved >= kth, axis=0, dtype=jnp.float32)

    def unsettled(state):
        _, _, _, load, done = state
        return (done < rounds) & jnp.any(jnp.abs(load - mean)
                                         > tolerance * mean)

    def move(state):
        bias, size, before, load, done = state
        side = jnp.sign(load - mean)
        size = jnp.where(side * before < 0, size / 2, size)
        bias = bias - size * side
        return bias, size, side, loads(bias), done + 1

    zero = jnp.zeros(experts, jnp.float32)
    bias, _, _, load, done = jax.lax.while_loop(
        unsettled, move, (zero, jnp.full(experts, step, jnp.float32), zero,
                          loads(zero), 0))
    return bias, load, done


def balance_routing(job, ring, log=print):
    """Sets every expert layer's routing bias, in the net and in the
    trainer's state, to what ``balanced_bias`` gives on the ring: one
    compiled walk of the trunk under the training policy (parameters
    cast as ``ShardedTrainer`` casts them, auxiliary state as it is),
    each batch of the ring in turn through a part, a layer's bias
    settled on all of them to ``BALANCE_TOLERANCE`` before the part that
    holds the layer runs. The walk is not what the window times: the
    step's own counts are held to it afterwards (``routing_check``).
    Span ``setup.balance_routing``."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from mxnet_tpu import parallel
    from mxnet_tpu.observability import trace as obs_trace

    if job.routing_parts is None:
        raise ManifestError("the model file gives no routing_parts: it "
                            "cannot take a traffic with balanced routing")
    parts = job.routing_parts()
    cast = _caster(job.train["compute_dtype"])
    tokens = jnp.stack([x for x, _ in ring])

    def walk(params, aux, tokens):
        values = {**{k: cast(v) for k, v in params.items()}, **aux}
        h, found = tokens, {}
        for part in parts:
            if part.router is not None:
                r = part.router
                seen = _pure([part.seen], part.seen)
                x = jax.lax.map(lambda one: seen(values, one), h)
                logits = jnp.einsum(
                    "...d,ed->...e", x.astype(jnp.float32),
                    values[r["weight"]].astype(jnp.float32))
                scores = jax.nn.sigmoid(logits) \
                    if r["score_func"] == "sigmoid" \
                    else jax.nn.softmax(logits, axis=-1)
                bias, load, rounds = balanced_bias(
                    scores.reshape(-1, scores.shape[-1]), r["top_k"],
                    BALANCE_TOLERANCE)
                # each batch's own assignments under the ring's bias
                moved = scores.reshape(len(ring), -1, scores.shape[-1]) + bias
                kth = jax.lax.top_k(moved, r["top_k"])[0][..., -1:]
                found[r["bias"]] = (bias, load, rounds, jnp.sum(
                    moved >= kth, axis=1, dtype=jnp.float32))
                values[r["bias"]] = bias.astype(values[r["bias"]].dtype)
            fn = _pure(part.blocks, part.fn)
            h = jax.lax.map(lambda one: fn(values, one), h)
        return found

    t0 = time.perf_counter()
    with obs_trace.span("setup.balance_routing", layers=sum(
            p.router is not None for p in parts), batches=len(ring)):
        args = (job._replicated(parallel.param_arrays(job.net)),
                job._replicated(parallel.aux_arrays(job.net)), tokens)
        lowered = jax.jit(walk).lower(*args)
        t_lowered = time.perf_counter()
        compiled = lowered.compile()
        t_compiled = time.perf_counter()
        found = jax.device_get(compiled(*args))
        t_ran = time.perf_counter()
        named = job.net.collect_params()
        held = {p.router["bias"]: p.router["held"] for p in parts
                if p.router is not None}
        for name, (bias, load, rounds, a_batch) in found.items():
            off = float(np.max(np.abs(load / load.mean() - 1.0)))
            first, count = held[name]
            here = a_batch[:, first:first + count].sum(axis=1)
            log(f"balanced routing, {name}: {int(rounds)} rounds of the "
                f"balancer's rule; over the ring's {int(load.sum())} "
                f"assignments each of {load.size} experts has "
                f"{int(load.min())}-{int(load.max())} (mean "
                f"{load.mean():.0f}, farthest {100 * off:.2f} % off); bias "
                f"{bias.min():+.4f} to {bias.max():+.4f}; the {count} experts "
                f"held get {[int(n) for n in here]} of a batch")
            if not off <= BALANCE_TOLERANCE:
                raise RuntimeError(
                    f"{name}: the balancer's rule left an expert "
                    f"{100 * off:.2f} % off the mean load after "
                    f"{int(rounds)} rounds (tolerance "
                    f"{100 * BALANCE_TOLERANCE} %)")
            job.expert_share = float(load.mean()) / len(ring)
            named[name].set_data(bias)
            job.trainer.aux[name] = jax.device_put(
                jnp.asarray(bias, job.trainer.aux[name].dtype),
                job.trainer.aux[name].sharding)
    log(f"balanced routing: {len(found)} expert layers in "
        f"{time.perf_counter() - t0:.2f} s of set-up (the walk traced and "
        f"lowered in {t_lowered - t0:.2f}, compiled or read from the cache "
        f"in {t_compiled - t_lowered:.2f}, run in {t_ran - t_compiled:.2f})")
