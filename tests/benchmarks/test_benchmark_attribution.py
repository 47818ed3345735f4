"""Device time by the program's own names (``benchmarks/attribution.py``)
and the readers built on it: on a trace written with
``harness/xplane_text.py`` and a hand-made map, with known answers; on
two recorded steps of ``gpt2m_train_seq1024`` with the program's map for
that executable, held to what the chip run printed; and, at the tiny
CPU preset, that a traced rehearsal reads the set-up metrics and leaves
the device ones out."""
import gzip
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import attribution  # noqa: E402
from benchmarks.harness import device, manifest, xplane, xplane_text  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")
DEVICE = ("forward_ms_per_step", "backward_ms_per_step",
          "optimizer_ms_per_step", "step_attributed_share",
          "attention_fwd_ms_per_step", "attention_bwd_ms_per_step",
          "attention_fwd_roofline")
SETUP = ("setup_import_s", "setup_build_s", "setup_compile_s")
J = "jit(sharded_step)/"
FWD, BWD = J + "jvp(net0)/", J + "transpose(jvp(net0))/"
# the program's map, as ``observability.perf.op_names`` gives it
NAMES = {
    # a convolution fused with the next BatchNorm's statistics
    "fusion.1": {"op_name": FWD + "net0_conv0/conv_general_dilated",
                 "kernel": "",
                 "called": [FWD + "net0_conv0/conv_general_dilated",
                            FWD + "net0_batchnorm0/reduce_sum"]},
    "flash_attention_fwd.2": {
        "op_name": FWD + "net0_attn/attention/flash_attention_fwd/"
                   "pallas_call",
        "kernel": "flash_attention_fwd", "called": []},
    "while.3": {"op_name": BWD + "net0_attn/attention/while", "kernel": "",
                "called": []},
    "fusion.4": {"op_name": BWD + "net0_attn/attention/while/body/mul",
                 "kernel": "", "called": []},
    "fusion.5": {"op_name": BWD + "net0_attn/attention/while/body/add",
                 "kernel": "", "called": []},
    # a weight gradient fused with its optimizer update: once, under
    # its root
    "fusion.6": {"op_name": BWD + "net0_conv0/conv_general_dilated",
                 "kernel": "",
                 "called": [J + "optimizer/mul",
                            BWD + "net0_conv0/conv_general_dilated"]},
    "fusion.7": {"op_name": J + "optimizer/add", "kernel": "",
                 "called": [J + "optimizer/mul", J + "optimizer/add"]},
    "copy.8": {"op_name": "", "kernel": "", "called": []},
    # a forward op XLA cloned to recompute it: it runs in the backward
    "fusion.9.remat": {"op_name": FWD + "net0_dense0/dot_general",
                       "kernel": "", "called": []},
    # no name of its own: its root's
    "fusion.10": {"op_name": "", "kernel": "",
                  "called": [FWD + "net0_layernorm0/sub",
                             FWD + "net0_layernorm0/mul"]},
}
# (instruction, start us, duration us) of one 96 us step; convert.11 is
# not in the map
STEP = (("fusion.1", 0, 30), ("flash_attention_fwd.2", 30, 10),
        ("while.3", 40, 16), ("fusion.4", 41, 5), ("fusion.5", 48, 5),
        ("fusion.6", 56, 20), ("fusion.7", 76, 8), ("copy.8", 84, 3),
        ("fusion.9.remat", 87, 4), ("fusion.10", 91, 2),
        ("convert.11", 93, 1))
EXPECT = {"forward": 0.042, "backward": 0.040, "optimizer": 0.008,
          "rest": 0.004}


def _op(name, start, dur):
    op = {"flash_attention_fwd.2": "custom-call", "while.3": "while",
          "copy.8": "copy", "convert.11": "convert"}.get(name, "fusion")
    target = "tpu_custom_call" if op == "custom-call" else ""
    return (name, float(start), float(dur),
            {"op": op, "kind": "kLoop" if op == "fusion" else "",
             "target": target})


def _summary(steps=3, module="jit_sharded_step(7)"):
    from jax.profiler import ProfileData

    dev = {"ops": [], "async": [], "modules": []}
    for i in range(steps):
        t0 = 1e3 + i * 100e3
        dev["modules"].append((module, t0, 96e3, {}))
        dev["ops"] += [_op(name, t0 + s * 1e3, d * 1e3)
                       for name, s, d in STEP]
    trace = {"devices": {0: dev}, "host": []}
    return xplane.reduce(xplane.read(ProfileData.from_text_proto(
        xplane_text.to_text_proto(trace))))


class _Run:
    """What a reader sees of a run, without one."""

    rehearsal = False

    def __init__(self, summary, cell="gpt2m_train_seq1024", spans=()):
        found = manifest.Cell(manifest.load(), cell)
        self.cell, self.config, self.traffic = found, found.config, \
            found.traffic
        self.trace, self.devices, self.facts = summary, [None], {}
        self.window, self.lines, self._spans = (10.0, 20.0), [], list(spans)

    def log(self, msg):
        self.lines.append(msg)

    def peaks(self):
        return device.peaks("TPU v5 lite")

    def program_spans(self, name=None, in_window=True):
        assert not in_window
        return [s for s in self._spans if name in (None, s["name"])]


def _read(metric, run):
    return manifest.module("layer_metrics", metric).read(run)


@pytest.fixture
def program_map(monkeypatch):
    """``NAMES`` as the ledger's only ``sharded_step`` executable."""
    from mxnet_tpu.observability import perf

    def install(names, label="sharded_step"):
        monkeypatch.setattr(perf, "ledger",
                            lambda: {f"{label}@abc": {"label": label},
                                     "sharded_grads@def":
                                         {"label": "sharded_grads"}})
        monkeypatch.setattr(perf, "op_names",
                            lambda key: names if key.startswith(label)
                            else {"fusion.1": NAMES["fusion.1"]})
    install(NAMES)
    return install


# ------------------------------------------------------------ classification

@pytest.mark.parametrize("instruction,op_name,phase", [
    ("fusion.1", FWD + "net0_conv0/conv", "forward"),
    ("fusion.1", J + "jvp()/convert_element_type", "forward"),
    ("fusion.2", BWD + "net0_conv0/conv", "backward"),
    ("fusion.2", J + "transpose(jvp())/convert_element_type", "backward"),
    ("fusion.3", J + "optimizer/mul", "optimizer"),
    ("fusion.4.remat2", FWD + "net0_conv0/conv", "backward"),
    ("fusion.5", J + "jvp(net0)/checkpoint/rematted_computation/net0_d/dot",
     "backward"),
    ("copy.6", "", "rest"),
    ("x.7", "params['w']", "rest"),
])
def test_a_name_decides_the_phase(instruction, op_name, phase):
    assert attribution.phase_of(instruction, op_name) == phase


@pytest.mark.parametrize("op_name,blocks,kind", [
    (BWD + "net0_stage1/net0_stage1_conv0/conv",
     ["net0", "net0_stage1", "net0_stage1_conv0"], "Conv2D"),
    (FWD + "net0_stage1/net0_stage1_batchnorm0/reduce_sum",
     ["net0", "net0_stage1", "net0_stage1_batchnorm0"], "BatchNorm"),
    (FWD + "net0_blocks/net0_blocks_b0/net0_blocks_b0_attn/attention/while/"
     "body/jit(_where)/select_n",
     ["net0", "net0_blocks", "net0_blocks_b0", "net0_blocks_b0_attn"],
     "attention"),
    (FWD + "net0_blocks/net0_blocks_b0/net0_blocks_b0_attn/"
     "net0_blocks_b0_attn_qkv/dot_general",
     ["net0", "net0_blocks", "net0_blocks_b0", "net0_blocks_b0_attn",
      "net0_blocks_b0_attn_qkv"], "Dense"),
    (FWD + "net0_blocks/net0_blocks_b0/net0_blocks_b0_ln1/jit(_var)/sub",
     ["net0", "net0_blocks", "net0_blocks_b0", "net0_blocks_b0_ln1"],
     "LayerNorm"),
    (FWD + "net0_embed/jit(_take)/gather", ["net0", "net0_embed"],
     "embedding"),
    (FWD + "net0_stage1/add", ["net0", "net0_stage1"],
     "residual / activation"),
    (J + "jvp(softmaxcrossentropyloss0)/jit(log_softmax)/sub",
     ["softmaxcrossentropyloss0"], "loss"),
    (J + "jvp()/convert_element_type", [],
     "outside any block (casts, loss tail)"),
    (J + "optimizer/mul", [], "optimizer"),
])
def test_a_name_gives_the_blocks_and_the_table_row(op_name, blocks, kind):
    assert attribution.blocks_of(op_name) == blocks
    assert attribution.kind_of(op_name, {"net0", "net0_stage1"}) == kind


# --------------------------------------------------- the join, known answers

def test_attribution_of_a_trace_with_known_answers(program_map):
    run = _Run(_summary())
    att = attribution.of_run(run)
    assert att.n_steps == 3 and att.unknown == 1      # convert.11
    for phase, ms in EXPECT.items():
        assert att.ms_per_step(phase) == pytest.approx(ms)
    assert att.total_ms_per_step() == pytest.approx(0.094)
    assert att.attention_ms_per_step("forward") == pytest.approx(0.010)
    assert att.attention_ms_per_step("backward") == pytest.approx(0.016)
    # fusion.6 holds two phases; fusion.1 two blocks of one phase
    assert att.mixed_phases_ns / 3 == pytest.approx(20e3)
    assert att.mixed_blocks_ns / 3 == pytest.approx(30e3)
    assert att.kernels == {"flash_attention_fwd": pytest.approx(30e3)}
    assert {k: v / 3 for k, v in att.rest_by_name.items()} == {
        "copy": pytest.approx(3e3), "convert": pytest.approx(1e3)}
    assert att.table[("backward", "Dense")] / 3 == pytest.approx(4e3)
    assert att.table[("forward", "LayerNorm")] / 3 == pytest.approx(2e3)
    assert attribution.of_run(run) is att               # once a run
    said = "\n".join(run.lines)
    assert "not attributed, by instruction: copy 0.003, convert 0.001" in said
    assert "kernel flash_attention_fwd: 0.010 ms a step" in said


def test_every_device_reader_on_the_trace_with_known_answers(program_map):
    run = _Run(_summary())
    got = {m: _read(m, run) for m in DEVICE}
    assert got["forward_ms_per_step"] == pytest.approx(0.042)
    assert got["backward_ms_per_step"] == pytest.approx(0.040)
    assert got["optimizer_ms_per_step"] == pytest.approx(0.008)
    # forward + backward + optimizer + the logged rest = the step's busy time
    step_ms = _read("step_device_ms", run)
    assert step_ms == pytest.approx(0.094)
    assert got["step_attributed_share"] == pytest.approx(100 * 90 / 94)
    assert got["attention_fwd_ms_per_step"] == pytest.approx(0.010)
    assert got["attention_bwd_ms_per_step"] == pytest.approx(0.016)
    # gpt2-medium, batch 8 x 1024: 24 layers x 17.18 GFLOP / 197 TFLOP/s
    roofline = manifest.module("layer_metrics", "attention_fwd_roofline")
    least, bound = roofline.least_ms(run.config, run.traffic, run.peaks())
    assert bound == "compute" and least == pytest.approx(2.0930, abs=1e-3)
    assert got["attention_fwd_roofline"] == pytest.approx(100 * least / 0.010)
    assert _read("custom_call_ms_per_step", run) == pytest.approx(
        got["attention_fwd_ms_per_step"])


def test_the_steps_map_is_found_by_the_modules_label(program_map):
    traced = set(NAMES)
    assert attribution.step_names("jit_sharded_step(123)", traced) is NAMES
    assert attribution.step_names("jit_decode_step(9)", traced) is None
    # a program that has the names but not this executable's: nothing to
    # read, and the run fails as for any metric
    run = _Run(_summary(module="jit_step(7)"))
    assert attribution.of_run(run) is None
    assert all(_read(m, run) is None for m in DEVICE)


def test_no_device_trace_reads_nothing():
    run = _Run(None)
    assert all(_read(m, run) is None for m in DEVICE)


def test_a_program_from_before_the_names_reads_zero(monkeypatch):
    """The harness fails a chip run whose reader returns None and cannot
    leave a metric out, and the parent commit is measured with these
    readers: there they read 0 and say so."""
    from mxnet_tpu.observability import perf

    monkeypatch.delattr(perf, "op_names")
    run = _Run(_summary(module="jit_step(7)"))
    assert {m: _read(m, run) for m in DEVICE} == dict.fromkeys(DEVICE, 0.0)
    assert {m: _read(m, run) for m in SETUP} == dict.fromkeys(SETUP, 0.0)
    assert "older than its names" in "\n".join(run.lines)


# ------------------------------------------------------------------ set-up

def _span(name, t0_s, dur_s, **attrs):
    return {"name": name, "t0_ns": int(t0_s * 1e9),
            "dur_ns": int(dur_s * 1e9), "attrs": attrs}


def test_setup_readers_take_the_union_of_spans_before_the_window():
    spans = [
        _span("setup.import", 1.0, 0.5),
        _span("setup.initialize", 2.0, 1.0, block="net0"),
        _span("setup.infer_shape", 2.5, 1.0, block="net0_a"),   # overlaps
        _span("setup.infer_shape", 4.0, 0.25, block="net0_b"),
        _span("setup.trainer", 5.0, 0.5, params=3),
        _span("capture.trace_lower", 6.0, 1.0, label="sharded_step",
              aot_hit=False),
        _span("capture.compile", 7.0, 2.0, label="sharded_step",
              cache_hit=True),
        # inside the window (a recompile): not set-up
        _span("capture.compile", 12.0, 1.0, label="sharded_step",
              cache_hit=False),
        _span("setup.initialize", 9.5, 1.0, block="late"),   # ends inside
    ]
    run = _Run(None, spans=spans)
    assert _read("setup_import_s", run) == pytest.approx(0.5)
    assert _read("setup_build_s", run) == pytest.approx(1.5 + 0.25 + 0.5)
    assert _read("setup_compile_s", run) == pytest.approx(3.0)
    said = "\n".join(run.lines)
    assert "capture.compile sharded_step (cache) 2.000 s x1" in said
    assert "capture.trace_lower sharded_step 1.000 s x1" in said
    # the program has the spans' names and recorded none: nothing to read
    assert all(_read(m, _Run(None)) is None for m in SETUP)


# ----------------------------------------------- two recorded steps of GPT-2

def test_recorded_gpt2_steps_read_what_the_chip_run_printed(program_map):
    trace = os.path.join(FIXTURES,
                         "gpt2m_train_seq1024.2steps.textproto.gz")
    with gzip.open(os.path.join(
            FIXTURES, "gpt2m_train_seq1024.op_names.json.gz"), "rt",
            encoding="utf-8") as f:
        recorded = json.load(f)
    program_map(recorded["names"])
    run = _Run(xplane.reduce(xplane.read(xplane.open_trace(trace))))
    for metric, printed in recorded["chip_run_printed"].items():
        assert _read(metric, run) == pytest.approx(printed, rel=0.02), metric
    assert set(recorded["chip_run_printed"]) == set(DEVICE)
    att = attribution.of_run(run)
    assert att.n_steps == 2 and att.unknown == 0
    named = sum(att.ms_per_step(p) for p in attribution.PHASES)
    assert named + att.ms_per_step("rest") == pytest.approx(
        _read("step_device_ms", run), rel=0.01)
    assert set(att.kernels) == {"flash_attention_fwd"}
    # the forward kernel is the only custom call there today; the scope
    # holds 2.3 ms more: a reduce over the kernel's log-sum-exp output
    # and the copy of a reshape, 24 times each
    kernel_ms = att.kernels["flash_attention_fwd"] / att.n_steps / 1e6
    assert kernel_ms == pytest.approx(
        _read("custom_call_ms_per_step", run), rel=1e-3)
    assert kernel_ms < _read("attention_fwd_ms_per_step", run) \
        < 1.04 * kernel_ms
    assert 1.0 < _read("attention_fwd_roofline", run) < 10.0


# ---------------------------------------------------------------- rehearsal

@pytest.mark.parametrize("cell", [w["name"] for w in
                                  manifest.load()["workloads"]])
def test_a_traced_rehearsal_reads_set_up_and_leaves_the_device_out(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py"),
         "--workload", cell, "--seconds", "2", "--seed", "5", "--trace", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    label = "CPU REHEARSAL, not a chip result: "
    line = json.loads(proc.stdout.strip().splitlines()[-1][len(label):])
    assert set(SETUP) <= set(line["would_report"])
    assert not set(DEVICE) & set(line["would_report"])
    assert "capture.compile sharded_step" in proc.stdout
