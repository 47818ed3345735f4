"""Device time under the scopes of the newer mechanisms
(``benchmarks/scopes.py``) and the readers built on it: on a trace
written with ``harness/xplane_text.py`` and a hand-made map of two
steps' names, with known answers; the counters' readers on hand-made
counts; that a program without these scopes leaves the metrics out; and
that ``qwen3next_train_seq8192``'s check catches weights at three bits
of mantissa at the tiny CPU preset."""
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import scopes  # noqa: E402
from benchmarks.harness import device, manifest, xplane, xplane_text  # noqa: E402

CELL = "qwen3next_train_seq8192"
J = "jit(sharded_step)/"
B = "net0_blocks/net0_blocks_b0/"
FWD = J + "jvp(net0)/" + B + "checkpoint/"
BWD = J + "transpose(jvp(net0))/" + B + "checkpoint/"
REMAT = BWD + "rematted_computation/"
LA = "net0_blocks_b0_linattn/linear_attention/"
MOE = "net0_blocks_b0_moe/moe/"


def _entry(op_name, called=()):
    return {"op_name": op_name, "kernel": "", "called": list(called)}


# the program's map, as ``observability.perf.op_names`` gives it
NAMES = {
    "fusion.1": _entry(FWD + "net0_blocks_b0_linattn/"
                       "net0_blocks_b0_linattn_qkvz/dot_general"),
    "fusion.2": _entry(FWD + LA + "causal_conv1d/mul"),
    "while.3": _entry(FWD + LA + "gated_delta_rule/while"),
    "fusion.4": _entry(FWD + LA + "gated_delta_rule/while/body/dot_general"),
    # the gated norm is a block of its own inside the scope
    "fusion.5": _entry(FWD + LA + "net0_blocks_b0_linattn_norm/mul"),
    "fusion.6": _entry(FWD + MOE + "moe_router/top_k"),
    # the compiler's own kernel for ragged_dot, under the compiler's name
    "ragged-dot-none.7": _entry("ragged-dot-none"),
    # no name of its own: its root's, the last inside it
    "fusion.8": _entry("", [FWD + MOE + "moe_experts/gather",
                            FWD + MOE + "net0_blocks_b0_moe_shared/mul"]),
    # what the layer recomputes runs in the backward pass
    "fusion.9": _entry(REMAT + LA + "gated_delta_rule/while/body/dot_general"),
    "fusion.10.remat": _entry(FWD + MOE + "moe_experts/ragged_dot"),
    "fusion.11": _entry(BWD + LA + "gated_delta_rule/while/body/transpose"),
    "fusion.16": _entry(BWD + MOE + "moe_experts/mul"),
    "ragged-dot-none.12": _entry("ragged-dot-none"),
    "fusion.13": _entry(J + "optimizer/mul"),
    "copy.14": _entry(""),
}
# (instruction, start us, duration us) of one 80 us step; convert.15 is
# not in the map
STEP = (("fusion.1", 0, 10), ("fusion.2", 10, 2), ("while.3", 12, 10),
        ("fusion.4", 13, 8), ("fusion.5", 22, 1), ("fusion.6", 23, 3),
        ("ragged-dot-none.7", 26, 6), ("fusion.8", 32, 4), ("fusion.9", 36, 9),
        ("fusion.10.remat", 45, 5), ("fusion.11", 50, 12),
        ("fusion.16", 62, 1), ("ragged-dot-none.12", 63, 6),
        ("fusion.13", 69, 5), ("copy.14", 74, 2), ("convert.15", 76, 1))
# ms a step: while.3's self time is its 10 us less the 8 of fusion.4; the
# two ragged-dot kernels take the phase of the named op before them
EXPECT = {("linear_attention", "forward"): 0.013,
          ("linear_attention", "backward"): 0.021,
          ("moe", "forward"): 0.013, ("moe", "backward"): 0.012,
          ("moe_router", "forward"): 0.003,
          ("moe_experts", "forward"): 0.006,
          ("moe_experts", "backward"): 0.012}


def _op(name, start, dur):
    op = "custom-call" if name.startswith("ragged") else name.split(".")[0]
    target = "tpu_custom_call" if op == "custom-call" else ""
    return (name, float(start), float(dur),
            {"op": op, "kind": "kLoop" if op == "fusion" else "",
             "target": target})


def _summary(steps=2, module="jit_sharded_step(7)"):
    from jax.profiler import ProfileData

    dev = {"ops": [], "async": [], "modules": []}
    for i in range(steps):
        t0 = 1e3 + i * 100e3
        dev["modules"].append((module, t0, 80e3, {}))
        dev["ops"] += [_op(name, t0 + s * 1e3, d * 1e3)
                       for name, s, d in STEP]
    trace = {"devices": {0: dev}, "host": []}
    return xplane.reduce(xplane.read(ProfileData.from_text_proto(
        xplane_text.to_text_proto(trace))))


class _Model:
    def __init__(self, counts):
        self._counts = counts

    def expert_tokens(self):
        return self._counts


class _Run:
    """What a reader sees of a run, without one."""

    rehearsal = False

    def __init__(self, summary, counts=None):
        found = manifest.Cell(manifest.load(), CELL)
        self.cell, self.config, self.traffic = found, found.config, \
            found.traffic
        self.trace, self.devices, self.facts = summary, [None], {}
        self.lines = []
        self.model = _Model(counts) if counts is not None else object()

    def log(self, msg):
        self.lines.append(msg)

    def peaks(self):
        return device.peaks("TPU v5 lite")


def _read(metric, run):
    return manifest.module("layer_metrics", metric).read(run)


@pytest.fixture
def program_map(monkeypatch):
    from mxnet_tpu.observability import perf

    def install(names):
        monkeypatch.setattr(perf, "ledger", lambda: {
            "sharded_step@abc": {"label": "sharded_step"}})
        monkeypatch.setattr(perf, "op_names", lambda key: names)
    install(NAMES)
    return install


def test_the_join_on_two_steps_names_with_known_answers(program_map):
    run = _Run(_summary())
    got = scopes.of_run(run)
    assert set(got) == set(EXPECT)
    for key, ms in EXPECT.items():
        assert got[key] == pytest.approx(ms), key
    assert scopes.of_run(run) is got                    # once a run
    said = "\n".join(run.lines)
    assert "linear_attention backward 0.021" in said
    assert "moe_experts forward, largest: ragged-dot-none 0.006" in said
    # a scope that no instruction lies under has nothing to read; one
    # that has forward ops only reads 0 backward
    assert scopes.scope_ms(run, "moe_router", "backward") == 0.0
    stripped = {k: _entry(v["op_name"].replace("/moe_router", ""),
                          v["called"]) for k, v in NAMES.items()}
    program_map(stripped)
    assert scopes.scope_ms(_Run(_summary()), "moe_router", "forward") is None


def test_every_reader_on_the_trace_with_known_answers(program_map):
    counts = [(np.asarray([100.0, 200.0, 60.0, 0.0] * 4), 5000.0),
              (np.asarray([90.0] * 16), 5100.0)]
    run = _Run(_summary(), counts)
    assert _read("linear_attention_fwd_ms_per_step", run) == \
        pytest.approx(0.013)
    assert _read("linear_attention_bwd_ms_per_step", run) == \
        pytest.approx(0.021)
    assert _read("moe_fwd_ms_per_step", run) == pytest.approx(0.013)
    assert _read("moe_bwd_ms_per_step", run) == pytest.approx(0.012)
    assert _read("moe_busiest_expert_tokens", run) == 200.0
    said = "\n".join(run.lines)
    assert "expert layer 0: 1440 assignments to the 16 experts held" in said
    assert "all computed, none dropped; 5000 tokens chose no held" in said
    # 1 x 8192 tokens, three linear layers: 8192 x 12416 channels x 2
    # bytes = 203.4 MB a layer at 819 GB/s, over 25.8 GFLOP at 197 TFLOP/s
    peaks = run.peaks()
    least = 3 * 8192 * (2 * 2048 + 2 * 4096 + 64) * 2 \
        / peaks["hbm_bytes_per_s"] * 1e3
    flops = 3 * 2 * 3 * 8192 * 32 * 128 * 128 / peaks["bf16_flops_per_s"] * 1e3
    assert least > flops
    assert _read("linear_attention_fwd_roofline", run) == \
        pytest.approx(100 * least / 0.013)
    # two layers of 1440 assignments: 16 experts x 3 x 2048 x 512 weights
    # and 1440 rows in and out, bf16, against 1440 x 6.3 MFLOP
    one = (16 * 3 * 2048 * 512 + 2 * 1440 * 2048) * 2 \
        / peaks["hbm_bytes_per_s"] * 1e3
    assert one > 2 * 3 * 2048 * 512 * 1440 / peaks["bf16_flops_per_s"] * 1e3
    assert _read("moe_experts_fwd_roofline", run) == \
        pytest.approx(100 * 2 * one / 0.006)


def test_a_program_without_the_scopes_or_the_counts_leaves_the_metrics_out(
        program_map):
    bare = {k: _entry(v["op_name"].replace("linear_attention/", "")
                      .replace("moe/", "").replace("moe_experts/", "")
                      .replace("moe_router/", ""), v["called"])
            for k, v in NAMES.items()
            if k != "fusion.8" and not k.startswith("ragged")}
    program_map(bare)
    run = _Run(_summary())                      # no expert_tokens either
    for metric in ("linear_attention_fwd_ms_per_step",
                   "linear_attention_bwd_ms_per_step",
                   "linear_attention_fwd_roofline", "moe_fwd_ms_per_step",
                   "moe_bwd_ms_per_step", "moe_experts_fwd_roofline",
                   "moe_busiest_expert_tokens"):
        assert _read(metric, run) is None, metric
    # and so does a run with no device trace (a rehearsal)
    assert scopes.of_run(_Run(None)) is None


def test_the_new_metrics_list_the_new_cell_only():
    """The ``linear_attention_*`` metrics list this cell alone; a
    ``moe_*`` list holds it and any other cell whose configuration has
    experts (a later expert configuration appends its own)."""
    admitted = manifest.load()
    listed = {m["name"]: m for m in admitted["per_layer"]}
    for name in ("linear_attention_fwd_ms_per_step",
                 "linear_attention_bwd_ms_per_step",
                 "linear_attention_fwd_roofline"):
        assert listed[name]["workloads"] == [CELL]
        assert listed[name]["moves"] == "train_items_per_s_per_chip"
    for name in ("moe_fwd_ms_per_step", "moe_bwd_ms_per_step",
                 "moe_experts_fwd_roofline", "moe_busiest_expert_tokens"):
        assert CELL in listed[name]["workloads"]
        assert listed[name]["moves"] == "train_items_per_s_per_chip"
        for cell in listed[name]["workloads"]:
            config = manifest.Cell(admitted, cell).config
            assert config["num_experts"] > 0 \
                and config["num_experts_per_tok"] > 0, (name, cell)
    assert CELL not in listed["attention_fwd_roofline"]["workloads"]
    for name in ("attention_fwd_ms_per_step", "attention_bwd_ms_per_step"):
        assert CELL in listed[name]["workloads"]


def test_the_configuration_keeps_the_published_widths():
    import json

    cell = manifest.Cell(manifest.load(), CELL)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog, encoding="utf-8") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    published = next(r["config"] for r in rows
                     if r["source_url"] == cell.config["source"])
    for key, value in published.items():
        if key not in cell.config["reduced"]:
            assert cell.config[key] == value, key
    assert cell.config["published"]["num_experts"] == published["num_experts"]
    assert cell.config["published"]["vocab_size"] == published["vocab_size"]
    assert cell.config["num_experts"] * \
        cell.config["deployment"]["chips_per_layer"] == \
        published["num_experts"]
    assert cell.traffic["batch"] == 1 and cell.traffic["seq_len"] == 8192


def test_the_training_check_catches_weights_at_three_bits_of_mantissa():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py"),
         "--workload", CELL, "--seed", "3", "--degrade"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "logit of the forward pass is" in proc.stdout
    assert "the degraded program is caught" in proc.stdout
    assert "weights as they are, forward pass only" in proc.stdout
    as_they_are = next(line for line in proc.stdout.splitlines()
                       if "weights as they are" in line)
    assert as_they_are.endswith("not seen")
