"""``linear_attention_bwd_roofline``: the least time by shapes for the
cell that runs the gated delta rule, the share read off a hand-made
trace of two steps (``test_benchmark_scopes``'s: 0.021 ms a step of
backward ops under the ``linear_attention`` scope, the recomputed
forward among them), and what a program without the scope gives."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import device, manifest  # noqa: E402
from tests.benchmarks import test_benchmark_scopes as recorded  # noqa: E402

CELL = "qwen3next_train_seq8192"
READER = manifest.module("layer_metrics", "linear_attention_bwd_roofline")
FORWARD = manifest.module("layer_metrics", "linear_attention_fwd_roofline")


def _cell():
    found = manifest.Cell(manifest.load(), CELL)
    return found.config, found.traffic, device.peaks("TPU v5 lite")


def test_least_time_by_shapes():
    """8192 tokens, 16 / 32 heads of 128, bf16, three of four layers:
    0.403 GFLOP x 12 a token and value head is 0.0164 ms a layer at 197
    TFLOP/s; 24 704 values a token moved is 404.8 MB, 0.494 ms a layer
    at 819 GB/s, so memory-bound, 1.483 ms a step."""
    config, traffic, peaks = _cell()
    got, bound = READER.least_ms(config, traffic, peaks)
    assert bound == "memory"
    moved = 8192 * (2 * 2048 + 3 * 4096 + 64 + 2 * 2048 + 4096 + 64) * 2
    assert got == pytest.approx(3 * moved / peaks["hbm_bytes_per_s"] * 1e3)
    assert got == pytest.approx(1.483, abs=2e-3)
    # twice the forward's products, and as it happens twice its bytes
    fwd, fwd_bound = FORWARD.least_ms(config, traffic, peaks)
    assert fwd_bound == "memory" and got == pytest.approx(2 * fwd)


def test_compute_bound_where_the_heads_are_wide_enough():
    config, traffic, peaks = _cell()
    wide = dict(config, linear_key_head_dim=2048, linear_value_head_dim=2048)
    got, bound = READER.least_ms(wide, traffic, peaks)
    flops = 12 * 8192 * 32 * 2048 * 2048
    assert bound == "compute"
    assert got == pytest.approx(3 * flops / peaks["bf16_flops_per_s"] * 1e3)


def test_listed_for_the_cell_that_runs_linear_attention():
    """Wherever it stands in ``per_layer``: later PRs append entries."""
    found = [m for m in manifest.load()["per_layer"]
             if m["name"] == "linear_attention_bwd_roofline"]
    assert found == [{"name": "linear_attention_bwd_roofline", "unit": "%",
                      "better": "higher", "source": "device_trace",
                      "layer": "kernels",
                      "moves": "train_items_per_s_per_chip",
                      "workloads": [CELL]}]


def test_share_of_the_recorded_steps(monkeypatch):
    from mxnet_tpu.observability import perf

    monkeypatch.setattr(perf, "ledger",
                        lambda: {"sharded_step@abc":
                                 {"label": "sharded_step"}})
    monkeypatch.setattr(perf, "op_names", lambda key: recorded.NAMES)
    run = recorded._Run(recorded._summary())
    run.peaks = lambda: device.peaks("TPU v5 lite")
    took = recorded.EXPECT["linear_attention", "backward"]
    share = READER.read(run)
    assert share == pytest.approx(100 * 1.4826 / took, rel=1e-3)
    assert any("linear attention backward: least time 1.48" in line
               and "memory-bound" in line for line in run.lines)


def test_nothing_to_read_is_none():
    """No device trace, or a program with no op under the scope (a
    model without these layers): the line leaves the metric out."""
    run = recorded._Run(None)
    run.peaks = lambda: device.peaks("TPU v5 lite")
    run.facts["scopes"] = None
    assert READER.read(run) is None
    run.facts["scopes"] = {("moe", "forward"): 1.0}
    assert READER.read(run) is None
    # forward ops only: a share of a roofline is never 0
    run.facts["scopes"] = {("linear_attention", "forward"): 1.0}
    assert READER.read(run) is None
