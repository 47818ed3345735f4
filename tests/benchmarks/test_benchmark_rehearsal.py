"""Every cell end to end on the CPU at its tiny preset: the program's
normal path, the window, the float32 reference and, traced, the
profiler, the reduction and every reader. What a program change breaks
in the benchmark's path fails here, at no chip time. Each cell runs in a
process of its own, as a chip run does; its line is labelled a rehearsal
and carries names, not values. Also: the training check catches a
degraded program, a chip run fails when a reader goes silent, and the
chip's entries refuse the CPU."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402

# the admitted cells and those kept under benchmarks/pending/
MANIFEST = manifest.load(pending=True)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def _rehearse(cell, trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py"),
         "--workload", cell, "--seconds", "2", "--seed", "3",
         "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = proc.stdout.strip().splitlines()[-1]
    label = "CPU REHEARSAL, not a chip result: "
    assert last.startswith(label)
    return json.loads(last[len(label):]), proc.stdout


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu_traced(cell):
    line, out = _rehearse(cell, 1)
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    chips = next(w["chips"] for w in MANIFEST["workloads"]
                 if w["name"] == cell)
    assert line["device"]["count"] >= chips
    # what needs no device trace is read on the CPU too
    assert "compiles_in_window" in line["would_report"]
    assert "0 compile request(s) inside the window" in out
    wanted = {m["name"] for m in MANIFEST["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert set(line["would_report"]) <= wanted


def test_an_untraced_rehearsal_reports_the_end_to_end_metrics():
    cell = "gpt2m_serve_chat_steady"
    line, _ = _rehearse(cell, 0)
    wanted = {m["name"] for m in MANIFEST["end_to_end"]
              if cell in m.get("workloads", [cell])}
    assert set(line["would_report"]) == wanted
    assert "value" not in json.dumps(line)


@pytest.mark.parametrize("cell", ["gpt2m_train_seq1024",
                                  "resnet50_train_bs256"])
def test_the_training_check_catches_weights_at_three_bits_of_mantissa(cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py"),
         "--workload", cell, "--seed", "3", "--degrade"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "logit of the forward pass is" in proc.stdout
    assert "the degraded program is caught" in proc.stdout


def test_rounding_keeps_the_asked_bits_of_mantissa():
    import numpy as np

    from benchmarks import degrade

    values = np.asarray([1.0, -0.75, 0.0, 3.1415927, -1e-3, 1234.5],
                        np.float32)
    for bits in (3, 5):
        got = np.asarray(degrade.rounder(bits)(values))
        assert got.dtype == np.float32
        assert np.array_equal(got[:3], values[:3])     # representable
        rel = np.abs(got[3:] - values[3:]) / np.abs(values[3:])
        assert np.all(rel <= 2.0 ** -(bits + 1)) and np.any(rel > 2.0 ** -9)
        mantissa, _ = np.frexp(got[3:])
        assert np.array_equal(mantissa * 2 ** (bits + 1),
                              np.round(mantissa * 2 ** (bits + 1)))
    tokens = np.arange(5, dtype=np.int32)
    assert np.array_equal(np.asarray(degrade.rounder(3)(tokens)), tokens)


class _Silent:
    """A run whose ``peak_hbm_gb`` reader has nothing to read."""
    peak_bytes = None

    def __init__(self, rehearsal):
        self.rehearsal = rehearsal
        self.cell = manifest.Cell(MANIFEST, "resnet50_train_bs256")
        self.cell.per_layer = [m for m in self.cell.per_layer
                               if m["name"] == "peak_hbm_gb"]


def test_a_silent_reader_fails_a_chip_run_and_is_left_out_of_a_rehearsal():
    from benchmarks.harness import cell as cell_mod

    assert cell_mod._read_layers(_Silent(rehearsal=True)) == {}
    with pytest.raises(RuntimeError, match="peak_hbm_gb"):
        cell_mod._read_layers(_Silent(rehearsal=False))


class _NoTrace:
    """A chip run of Qwen3-Next's cell whose scope readers find nothing
    (no device trace), over a program whose name maps are given."""
    rehearsal, trace, devices, peak_bytes = False, None, [None], None

    def __init__(self, metrics):
        self.cell = manifest.Cell(MANIFEST, "qwen3next_train_seq8192")
        self.cell.per_layer = [m for m in self.cell.per_layer
                               if m["name"] in metrics]
        self.config, self.traffic = self.cell.config, self.cell.traffic
        self.facts, self.lines, self.model = {}, [], object()

    def log(self, msg):
        self.lines.append(msg)


def test_a_reader_may_say_the_program_predates_its_scope(monkeypatch):
    """The driver runs a PR's parent with the PR's readers: a reader
    that names its ``SCOPE`` is left out where no name map of the
    program holds that scope, fails the run and is named where one does,
    and a reader that names none fails as before."""
    from benchmarks.harness import cell as cell_mod
    from mxnet_tpu.observability import perf

    def program(*op_names):
        monkeypatch.setattr(perf, "ledger", lambda: {"sharded_step@abc": {
            "label": "sharded_step"}})
        monkeypatch.setattr(perf, "op_names", lambda key: {
            f"fusion.{i}": {"op_name": name, "kernel": "", "called": []}
            for i, name in enumerate(op_names)})

    metrics = ("moe_fwd_ms_per_step", "linear_attention_bwd_roofline")
    older = "jit(sharded_step)/jvp(net0)/net0_blocks_b0_moe/dot_general"
    program(older)
    assert cell_mod.program_scopes() >= {"jvp(net0)", "dot_general"}
    run = _NoTrace(metrics)
    assert cell_mod._read_layers(run) == {}
    said = "\n".join(run.lines)
    for name in metrics:
        assert f"{name}: the program predates this metric" in said
    # the program names one of the two scopes: that reader is silent
    program(older, "jit(sharded_step)/jvp(net0)/net0_blocks_b0_moe/moe/"
            "moe_router/top_k")
    with pytest.raises(RuntimeError, match=r"\['moe_fwd_ms_per_step'\]"):
        cell_mod._read_layers(_NoTrace(metrics))
    # a name inside a fusion that has none of its own counts too
    monkeypatch.setattr(perf, "op_names", lambda key: {"fusion.1": {
        "op_name": "", "kernel": "", "called": [
            "jit(sharded_step)/jvp(net0)/b0_linattn/linear_attention/mul"]}})
    with pytest.raises(RuntimeError,
                       match=r"\['linear_attention_bwd_roofline'\]"):
        cell_mod._read_layers(_NoTrace(metrics))
    # a reader without a SCOPE has no such answer
    program(older)
    with pytest.raises(RuntimeError, match="moe_busiest_expert_tokens"):
        cell_mod._read_layers(_NoTrace(("moe_busiest_expert_tokens",)))


@pytest.mark.parametrize("entry,args", [
    ("run.py", ["--workload", "resnet50_train_bs256", "--seed", "1",
                "--seconds", "1", "--trace", "0"]),
    ("degrade.py", ["--workload", "resnet50_train_bs256", "--seed", "1"]),
    ("sweep.py", ["--workload", "gpt2m_serve_chat_steady", "--rates", "1"]),
])
def test_the_chips_entries_exit_3_without_a_chip_and_print_no_result(entry,
                                                                     args):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", entry)] + args,
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 3, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "no accelerator" in proc.stderr
    assert "{" not in proc.stdout
