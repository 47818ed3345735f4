"""A traffic with ``"routing": "balanced"``
(``benchmarks/models/sharded.py``), at the tiny CPU preset of
``trinitymini_train_seq8192_balanced``: after set-up every expert's share
of the ring's assignments is even, by the program's own counter and by
the plain reference handed the job's weights; the bias does not move
while every weight trains, the router's too, and at the configuration's
step size the routing of step 9 is that of step 1; a whole run holds the
step's own counts to the routing and is not correct where the walk
parted from the net or the step size walks the routing off; the
balancer's rule on hand-made scores; and a configuration without a
routing bias is refused the traffic by name."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402
from benchmarks.models import sharded  # noqa: E402

CELL = "trinitymini_train_seq8192_balanced"
LOOP = manifest.module("loops", "train_steps")
SEEDS = (0, 3, 11)


def _cell():
    return manifest.Cell(manifest.load(), CELL).rehearse()


class _Built:
    """The cell's job at the preset as the loop has it before the
    warm-up: built, the ring made, the traffic's routing applied."""

    def __init__(self, seed):
        import jax

        self.cell = _cell()
        self.model = manifest.module("models", self.cell.config["model"])
        self.reference = manifest.module("references",
                                         self.cell.config["reference"])
        self.job = self.model.build_trainer(
            self.cell.config, self.cell.traffic, seed, jax.devices()[:1],
            self.reference)
        self.ring = self.job.make_ring(seed, LOOP.ring_of(
            self.cell.traffic))
        self.said = []
        self.job.prepare(self.ring, self.cell.traffic, self.said.append)
        config = self.cell.config
        self.experts = config["published"]["num_experts"]
        self.held = config["num_experts"]
        self.top_k = config["num_experts_per_tok"]
        self.tokens = int(self.cell.traffic["batch"]) \
            * int(self.cell.traffic["seq_len"])

    def program_counts(self):
        """Per batch of the ring, per expert layer: what the layer's
        ``expert_tokens`` state holds after the forward pass under the
        training policy, the function the step differentiates."""
        import jax
        import jax.numpy as jnp

        from mxnet_tpu import parallel

        fwd = parallel.functional_call(self.job.net, train=True)
        dtype = self.job.train["compute_dtype"]

        def cast(v):
            return v.astype(dtype) \
                if jnp.issubdtype(v.dtype, jnp.floating) else v

        @jax.jit
        def counts(params, aux, x):
            _, moved = fwd({n: cast(v) for n, v in params.items()}, aux, x)
            return [v for n, v in sorted(moved.items())
                    if n.endswith("expert_tokens")]

        params = parallel.param_arrays(self.job.net)
        aux = parallel.aux_arrays(self.job.net)
        return [[np.asarray(c)[:self.held] for c in counts(params, aux, x)]
                for x, _ in self.ring]

    def reference_counts(self, weights=None):
        """The same from the plain reference's own routing, over ALL the
        experts: its layer loop (``references/trinity.py::hidden``) with
        the choice of every expert layer kept."""
        import jax

        ref = self.reference
        sizes = self.model.reference_sizes(self.cell.config)
        weights = self.job.reference_weights() if weights is None \
            else weights

        def chosen(weights, tokens):
            eps, out = sizes["eps"], []
            with jax.default_matmul_precision("highest"):
                h = weights["embed"][tokens] * sizes["embed_scale"]
                for p, kind in zip(weights["layers"], sizes["layer_types"]):
                    window = sizes["window"] \
                        if kind == "sliding_attention" else None
                    h = h + ref.rms_norm(ref.attention(
                        ref.rms_norm(h, p["norm_a"], eps), p["attn"], sizes,
                        window), p["norm_b"], eps)
                    x = ref.rms_norm(h, p["norm_c"], eps)
                    if "moe" in p:
                        out.append(ref.route(x, p["moe"], sizes)[1])
                        fed = ref.moe(x, p["moe"], sizes)
                    else:
                        fed = ref.gated_mlp(x, p["mlp"]["gate_up_w"],
                                            p["mlp"]["down_w"])
                    h = h + ref.rms_norm(fed, p["norm_d"], eps)
            return out

        chosen = jax.jit(chosen)
        return [[np.bincount(np.asarray(c).reshape(-1),
                             minlength=self.experts)
                 for c in chosen(weights, x)] for x, _ in self.ring]


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(seed):
        if seed not in cache:
            cache[seed] = _Built(seed)
        return cache[seed]

    return get


@pytest.mark.parametrize("seed", SEEDS)
def test_after_set_up_every_experts_share_of_the_ring_is_the_deployments(
        built, seed):
    b = built(seed)
    tolerance = sharded.BALANCE_TOLERANCE
    assert tolerance == 0.01
    # the walk's own tolerance and the gates' limits are the harness's,
    # not knobs of a traffic file; and no traffic holds a router
    assert not {"routing_tolerance", "router"} & set(b.cell.traffic)
    assert len(b.ring) == b.cell.traffic["ring"] == 1
    mean = len(b.ring) * b.tokens * b.top_k / b.experts
    assert b.job.expert_share == mean
    # by the program's own counter, the experts held, under the policy
    program = np.sum(b.program_counts(), axis=0)       # (layers, held)
    assert program.shape == (4, b.held)
    assert np.all(np.abs(program / mean - 1.0)
                  <= sharded.ROUTING_FIRST_STEP_LIMIT / 2), program
    # every expert, as the balancer read them on its walk
    said = [line for line in b.said if "rounds of the balancer" in line]
    assert len(said) == 4
    for line in said:
        assert f"each of {b.experts} experts" in line
        off = float(line.split("farthest ")[1].split(" %")[0])
        assert off <= 100 * tolerance, line
    # and every expert by the float32 reference handed the same weights:
    # at width 64 bf16 turns a few choices the other way
    reference = np.sum(b.reference_counts(), axis=0)   # (layers, experts)
    assert reference.shape == (4, b.experts)
    assert np.all(np.abs(reference / mean - 1.0) <= 0.06), reference
    # before the balancer the same weights route far from it
    zeroed = b.job.reference_weights()
    for layer in zeroed["layers"]:
        if "moe" in layer:
            layer["moe"] = dict(layer["moe"], expert_bias=np.zeros(
                b.experts, np.float32))
    random = np.sum(b.reference_counts(zeroed), axis=0)
    assert np.max(np.abs(random / mean - 1.0)) > 0.2


def test_the_reference_given_the_jobs_weights_picks_the_same_experts(built):
    b = built(3)
    bias = [layer["moe"]["expert_bias"]
            for layer in b.job.reference_weights()["layers"]
            if "moe" in layer]
    assert len(bias) == 4 and all(np.any(np.asarray(v) != 0) for v in bias)
    program = np.asarray(b.program_counts())      # (ring, layers, held)
    first = b.model.held(b.cell.config)[0]
    reference = np.asarray(b.reference_counts())[
        :, :, first:first + b.held]
    apart = np.abs(program - reference).sum() / program.sum()
    assert apart <= 0.04, apart             # 1.9-2.3 % at width 64 in bf16


def _stepped(b, job, steps):
    """Held assignments a layer and expert after each of ``steps`` steps
    on the ring's batch."""
    counts = {}
    for step in range(1, steps + 1):
        job.step(*b.ring[0]).block_until_ready()
        counts[step] = np.asarray([c for c, _ in b.model.expert_tokens()])
    return counts


def test_the_bias_is_held_and_every_weight_trains():
    import jax

    b = _Built(5)               # its own: it trains
    job = b.job
    bias = [n for n in job.trainer.aux if n.endswith("expert_bias")]
    router = [n for n in job.trainer.params if n.endswith("router_weight")]
    assert len(router) == len(bias) == 4
    assert not [n for n in job.trainer.aux if "router" in n]
    before = {n: np.asarray(job.trainer.aux[n]) for n in bias}
    dense = next(n for n in job.trainer.params if n.endswith("attn_q_weight"))
    norm = next(n for n in job.trainer.params if n.endswith("norm1_weight"))
    moved_from = {n: np.asarray(job.trainer.params[n])
                  for n in (dense, norm, router[0])}
    # the bias the balancer set is the trainer's and the net's
    for n in bias:
        assert np.any(before[n] != 0)
        assert np.array_equal(
            before[n], np.asarray(job.net.collect_params()[n].data().data_))
    counts = _stepped(b, job, 10)
    for n in bias:
        assert np.array_equal(before[n], np.asarray(job.trainer.aux[n])), n
    # at the configuration's step size every master weight still moves
    # (a norm weight at 1.0 and the router's too), and the routing of
    # steps 1 and 9 is the same to a token or two: the bf16 copies the
    # forward pass sees have all but stood still
    assert b.cell.config["train"]["optimizer_params"] == {
        "learning_rate": 1e-07}
    for n, was in moved_from.items():
        assert np.any(was != np.asarray(job.trainer.params[n])), n
    mean = b.tokens * b.top_k / b.experts
    assert np.all(np.abs(counts[1] / mean - 1.0) <= 0.02), counts[1]
    assert np.abs(counts[9] - counts[1]).sum() <= 0.01 * counts[1].sum()
    # with the step size at nought to rounding, the same exactly
    for rate, same in ((1e-12, True), (1e-4, False)):
        config = dict(b.cell.config, train=dict(
            b.cell.config["train"], optimizer_params={"learning_rate": rate}))
        job = b.model.build_trainer(config, b.cell.traffic, 5,
                                    jax.devices()[:1], b.reference)
        job.prepare(b.ring, b.cell.traffic, lambda msg: None)
        counts = _stepped(b, job, 9)
        assert np.array_equal(counts[1], counts[9]) == same, rate
    # ... and at 1e-4, the step size of the benchmark's other
    # transformer cells, the weights walk the routing off
    assert np.abs(counts[9] - counts[1]).sum() > 0.05 * counts[1].sum()


def _run(cell, seed=3):
    """One whole run of the cell at the preset, as ``rehearse.py`` makes
    it: the result's line."""
    import time

    from benchmarks.harness import cell as cell_mod

    return cell_mod.run_cell(cell, seed, 2, 0, time.perf_counter(),
                             os.path.join(ROOT, ".bench_out", "rehearsal"),
                             rehearsal=True)


def test_a_run_holds_the_steps_own_counts_to_the_routing(monkeypatch):
    """``correct`` compares the program's own counter with an even
    share, at the first step by expert and at the last by layer, and
    the line reports the counts; a walk that parted from the net (here:
    a balancer that says 'even' and sets no bias) and a step size that
    walks the routing off (1e-2 here) are each not correct, and say
    which of the two numbers failed."""
    line = _run(_cell())
    assert line["correct"] and list(line)[-1] == "compared"
    compared = line["compared"]
    assert compared["routing_first_step"]["limit"] \
        == sharded.ROUTING_FIRST_STEP_LIMIT == 0.05
    assert compared["routing_last_step"]["limit"] \
        == sharded.ROUTING_LAST_STEP_LIMIT == 0.25
    assert 0 < compared["routing_first_step"]["value"] < 0.02
    assert 0 <= compared["routing_last_step"]["value"] < 0.02
    held = line["held_assignments"]
    assert held["even_share_an_expert"] == 1024 * 3 / 16
    assert np.shape(held["first_step"]) == np.shape(held["last_step"]) \
        == (4, 4)
    assert np.max(np.abs(np.asarray(held["first_step"]) / 192 - 1)) \
        == pytest.approx(compared["routing_first_step"]["value"])
    # the step size of a usual run: even at the first step, off by the
    # ninth or so
    cell = _cell()
    cell.config = dict(cell.config, train=dict(
        cell.config["train"], optimizer_params={"learning_rate": 1e-2}))
    line = _run(cell)
    assert not line["correct"]
    assert line["compared"]["routing_first_step"]["value"] < 0.02
    assert line["compared"]["routing_last_step"]["value"] > 0.25
    # a balancer that does nothing and says it did
    import jax.numpy as jnp

    def says_even(scores, top_k, tolerance, **_):
        tokens, experts = scores.shape
        return (jnp.zeros(experts, jnp.float32),
                jnp.full(experts, tokens * top_k / experts, jnp.float32), 0)

    monkeypatch.setattr(sharded, "balanced_bias", says_even)
    line = _run(_cell())
    assert not line["correct"]
    assert line["compared"]["routing_first_step"]["value"] > 0.2
    # a cell whose traffic asks for no routing compares none
    assert sharded.TrainJob.routing_check(
        type("Job", (), {"expert_share": None})(), None, None) \
        == {"notes": [], "compared": {}, "reported": {}}


def test_the_balancers_rule_on_hand_made_scores():
    """Sixteen experts whose scores lean by up to 0.3: zero bias routes
    three times the mean to the favoured ones; the rule brings every
    load within the tolerance, lowers the favoured experts' bias and
    raises the others'."""
    import jax

    rng = np.random.default_rng(7)
    lean = np.linspace(-0.15, 0.15, 16, dtype=np.float32)
    scores = (rng.uniform(0.2, 0.8, (4096, 16)).astype(np.float32)
              + lean[None, :])
    mean = 4096 * 3 / 16
    _, before, rounds = jax.jit(
        lambda s: sharded.balanced_bias(s, 3, 0.01, rounds=0))(scores)
    assert int(rounds) == 0 and float(np.max(before)) > 1.5 * mean
    bias, load, rounds = jax.jit(
        lambda s: sharded.balanced_bias(s, 3, 0.01))(scores)
    bias, load = np.asarray(bias), np.asarray(load)
    assert 0 < int(rounds) < sharded.BALANCE_ROUNDS
    assert load.sum() == 4096 * 3
    assert np.all(np.abs(load / mean - 1.0) <= 0.01), load
    assert bias[-1] < 0 < bias[0] and np.all(np.diff(bias) < 0.02)
    # the loads are those of the program's own choice under that bias
    chosen = np.argsort(-(scores + bias), axis=1)[:, :3]
    assert np.array_equal(np.bincount(chosen.reshape(-1), minlength=16),
                          load.astype(int))


def test_a_configuration_without_a_routing_bias_is_refused_the_traffic():
    import jax

    qwen = manifest.Cell(manifest.load(), "qwen3next_train_seq8192")
    qwen.rehearse()
    traffic = dict(_cell().traffic)
    assert traffic["name"] == "train_seq8192_bs1_balanced"
    model = manifest.module("models", qwen.config["model"])
    reference = manifest.module("references", qwen.config["reference"])
    with pytest.raises(manifest.ManifestError) as refused:
        model.build_trainer(qwen.config, traffic, 0, jax.devices()[:1],
                            reference)
    assert "'train_seq8192_bs1_balanced'" in str(refused.value)
    assert "'qwen3-next-80b-a3b'" in str(refused.value)
    assert "no routing bias of its own" in str(refused.value)
    # a routing it does not know is refused too, not passed over
    with pytest.raises(manifest.ManifestError, match="not known"):
        sharded.TrainJob.prepare(None, [], dict(traffic, routing="even"))


def test_the_step_is_the_whole_arithmetic():
    """Nothing is left out of the step this traffic times: ``step_mfu``
    counts what the free-routing traffic of the same shape counts (a
    router's weight gradient too), and the only keys the two traffic
    files differ in are the ring, the routing and their reasons."""
    cell = manifest.Cell(manifest.load(), CELL)
    model = manifest.module("models", cell.config["model"])
    free = manifest.Cell(manifest.load(), "qwen3next_train_seq8192").traffic
    assert free["name"] == "train_seq8192_bs1"
    assert model.flops_per_item(cell.config, cell.traffic) \
        == model.flops_per_item(cell.config, free)
    differ = {k for k in set(free) | set(cell.traffic)
              if free.get(k) != cell.traffic.get(k)}
    assert differ == {"name", "why", "ring", "ring_why", "routing",
                      "routing_why"}
