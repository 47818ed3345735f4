"""``attention_bwd_roofline``: the least time by shapes for both training
cells that run attention, and the share read off two recorded steps of
``gpt2m_train_seq1024`` (PR 27's program, whose backward was still the
scan: the reader reads a program from before the backward kernel too)."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import device, manifest, xplane  # noqa: E402

FIXTURES = os.path.join(ROOT, "benchmarks", "fixtures")
READER = manifest.module("layer_metrics", "attention_bwd_roofline")


class _Run:
    """What the reader sees of a run, without one."""

    rehearsal = False

    def __init__(self, cell, summary=None):
        found = manifest.Cell(manifest.load(), cell)
        self.cell, self.config, self.traffic = found, found.config, \
            found.traffic
        self.trace, self.devices, self.facts = summary, [None], {}
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)

    def peaks(self):
        return device.peaks("TPU v5 lite")


# 10 B H T^2 D / 2 FLOPs a causal layer at 197 TFLOP/s: GPT-2 medium 24
# layers of 8 x 16 heads x 1024^2 x 64; Qwen3-Next one layer (4 built, one
# in four is attention) of 16 heads x 8192^2 x 256
@pytest.mark.parametrize("cell,shape,least", [
    ("gpt2m_train_seq1024", (16, 64, 24), 5.2325),
    ("qwen3next_train_seq8192", (16, 256, 1), 6.9767)])
def test_least_time_by_shapes(cell, shape, least):
    run = _Run(cell)
    assert READER.attention_shape(run.config) == shape
    got, bound = READER.least_ms(run.config, run.traffic, run.peaks())
    assert bound == "compute" and got == pytest.approx(least, abs=1e-3)


def test_listed_for_the_cells_that_run_attention():
    entry = next(m for m in manifest.load()["per_layer"]
                 if m["name"] == "attention_bwd_roofline")
    assert entry["workloads"] == ["gpt2m_train_seq1024",
                                  "qwen3next_train_seq8192"]
    assert entry["layer"] == "kernels" and entry["unit"] == "%" \
        and entry["moves"] == "train_items_per_s_per_chip"


def test_recorded_gpt2_steps_read_the_scans_share(monkeypatch):
    from mxnet_tpu.observability import perf

    with gzip.open(os.path.join(
            FIXTURES, "gpt2m_train_seq1024.op_names.json.gz"), "rt",
            encoding="utf-8") as f:
        recorded = json.load(f)
    monkeypatch.setattr(perf, "ledger",
                        lambda: {"sharded_step@abc":
                                 {"label": "sharded_step"}})
    monkeypatch.setattr(perf, "op_names", lambda key: recorded["names"])
    run = _Run("gpt2m_train_seq1024", xplane.reduce(xplane.read(
        xplane.open_trace(os.path.join(
            FIXTURES, "gpt2m_train_seq1024.2steps.textproto.gz")))))
    took = recorded["chip_run_printed"]["attention_bwd_ms_per_step"]
    share = READER.read(run)
    assert share == pytest.approx(100 * 5.2325 / took, rel=0.02)
    assert 7.0 < share < 8.0        # the scan: 7.4 % of its roofline
    assert any("attention backward: least time 5.23" in line
               for line in run.lines)


def test_nothing_to_read_is_none_and_an_older_program_is_zero(monkeypatch):
    from benchmarks import attribution

    run = _Run("qwen3next_train_seq8192")
    run.facts["attribution"] = None         # no device trace
    assert READER.read(run) is None
    run.facts["attribution"] = attribution.Attribution(3)  # before names
    assert READER.read(run) == 0.0
