"""The four ``window_attention_*`` readers and what they share
(``benchmarks/window_attention.py``): on a trace written with
``harness/xplane_text.py`` and a hand-made map of two steps' names (a
window layer and a full one, forward, recomputed and backward), with
known answers; the least time by shapes at Trinity-Mini's cell (1.22 /
3.05 ms a layer); what a program without the scope gives; where the
entries stand; and that the cell's configuration keeps the published
widths and its check catches weights at three bits of mantissa at the
tiny CPU preset."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import window_attention  # noqa: E402
from benchmarks.harness import device, manifest  # noqa: E402
from tests.benchmarks import test_benchmark_scopes as recorded  # noqa: E402

CELL = "trinitymini_train_seq8192_balanced"
METRICS = ("window_attention_fwd_ms_per_step",
           "window_attention_bwd_ms_per_step",
           "window_attention_fwd_roofline", "window_attention_bwd_roofline")
FULL_METRICS = ("full_attention_fwd_roofline", "full_attention_bwd_roofline")
MOE = ("moe_fwd_ms_per_step", "moe_bwd_ms_per_step",
       "moe_experts_fwd_roofline", "moe_busiest_expert_tokens")
J = "jit(sharded_step)/"
FWD = J + "jvp(net0)/net0_blocks/"
BWD = J + "transpose(jvp(net0))/net0_blocks/"
WIN = "net0_blocks_b1/checkpoint/{}net0_blocks_b1_attn/attention/" \
    "window_attention/"
FULL = "net0_blocks_b4/checkpoint/{}net0_blocks_b4_attn/attention/"
REMAT = "rematted_computation/"


def _entry(op_name, kernel="", called=()):
    return {"op_name": op_name, "kernel": kernel, "called": list(called)}


NAMES = {
    # a projection lies outside both scopes
    "fusion.1": _entry(FWD + "net0_blocks_b1/checkpoint/net0_blocks_b1_attn/"
                       "net0_blocks_b1_attn_q/dot_general"),
    "custom-call.2": _entry(FWD + WIN.format("")
                            + "flash_attention_fwd/pallas_call",
                            "flash_attention_fwd"),
    # no name of its own: its root's, the last inside it
    "fusion.3": _entry("", called=[FWD + WIN.format("") + "reshape"]),
    "custom-call.4": _entry(FWD + FULL.format("")
                            + "flash_attention_fwd/pallas_call",
                            "flash_attention_fwd"),
    # what a Remat half recomputes runs in the backward pass
    "custom-call.5": _entry(BWD + WIN.format(REMAT)
                            + "flash_attention_fwd/pallas_call",
                            "flash_attention_fwd"),
    "custom-call.6": _entry(BWD + WIN.format("")
                            + "flash_attention_bwd/pallas_call",
                            "flash_attention_bwd"),
    "fusion.7": _entry(BWD + WIN.format("") + "reduce_sum"),
    "custom-call.8": _entry(BWD + FULL.format("")
                            + "flash_attention_bwd/pallas_call",
                            "flash_attention_bwd"),
    "fusion.9": _entry(J + "optimizer/mul"),
}
# (instruction, start us, duration us) of one 80 us step
STEP = (("fusion.1", 0, 10), ("custom-call.2", 10, 4), ("fusion.3", 14, 1),
        ("custom-call.4", 15, 9), ("custom-call.5", 24, 4),
        ("custom-call.6", 28, 8), ("fusion.7", 36, 2),
        ("custom-call.8", 38, 20), ("fusion.9", 58, 5))
EXPECT = {"forward": 0.005, "backward": 0.014}


def _summary(steps=2, module="jit_sharded_step(7)"):
    from jax.profiler import ProfileData

    from benchmarks.harness import xplane, xplane_text

    dev = {"ops": [], "async": [], "modules": []}
    for i in range(steps):
        t0 = 1e3 + i * 100e3
        dev["modules"].append((module, t0, 80e3, {}))
        dev["ops"] += [(name, t0 + s * 1e3, d * 1e3,
                        {"op": name.split(".")[0],
                         "kind": "kLoop" if name.startswith("fusion") else "",
                         "target": "tpu_custom_call"
                         if name.startswith("custom") else ""})
                       for name, s, d in STEP]
    trace = {"devices": {0: dev}, "host": []}
    return xplane.reduce(xplane.read(ProfileData.from_text_proto(
        xplane_text.to_text_proto(trace))))


class _Run(recorded._Run):
    def __init__(self, summary):
        super().__init__(summary)
        found = manifest.Cell(manifest.load(), CELL)
        self.cell, self.config, self.traffic = found, found.config, \
            found.traffic


def _read(metric, run):
    return manifest.module("layer_metrics", metric).read(run)


@pytest.fixture
def program_map(monkeypatch):
    from mxnet_tpu.observability import perf, trace

    def install(names, built=()):
        monkeypatch.setattr(perf, "ledger", lambda: {
            "sharded_step@abc": {"label": "sharded_step"}})
        monkeypatch.setattr(perf, "op_names", lambda key: names)
        monkeypatch.setattr(
            trace, "spans", lambda trace_id=None, name=None:
            [{"name": "kernel.build", "attrs": attrs} for attrs in built]
            if name == "kernel.build" else [])
    install(NAMES)
    return install


def test_the_join_on_two_steps_names_with_known_answers(program_map):
    program_map(NAMES, built=[
        {"kernel": "flash_attention_fwd", "bh": 32, "t": 8192, "d": 128,
         "block_q": 512, "block_k": 512, "window": 2048,
         "tiles_visited": 70, "tiles_causal": 136},
        {"kernel": "flash_attention_fwd", "bh": 32, "t": 8192, "d": 128,
         "block_q": 512, "block_k": 512, "window": None,
         "tiles_visited": 136, "tiles_causal": 136}])
    run = _Run(_summary())
    got = window_attention.of_run(run)
    assert got == pytest.approx(EXPECT)
    assert window_attention.of_run(run) is got          # once a run
    said = "\n".join(run.lines)
    # the full layer's time beside: attention_* less window_attention_*
    assert "attention forward: 0.005 ms a step under window_attention, " \
        "0.009 in the layers that see the whole sequence" in said
    assert "attention backward: 0.014 ms a step under window_attention, " \
        "0.020 in the layers that see the whole sequence" in said
    assert "kernel flash_attention_fwd (bh 32, T 8192, D 128, tile 512 x " \
        "512, window 2048): 70 tiles visited of 136 in the causal half" \
        in said
    assert "window None): 136 tiles visited of 136" in said


def test_every_reader_on_the_trace_with_known_answers(program_map):
    run = _Run(_summary())
    assert _read(METRICS[0], run) == pytest.approx(0.005)
    assert _read(METRICS[1], run) == pytest.approx(0.014)
    assert _read(METRICS[2], run) == pytest.approx(100 * 4.8840 / 0.005,
                                                   rel=1e-4)
    assert _read(METRICS[3], run) == pytest.approx(100 * 12.2099 / 0.014,
                                                   rel=1e-4)
    said = "\n".join(run.lines)
    assert "window attention forward: least time 4.8840 ms a step " \
        "(compute-bound), took 0.005 ms" in said
    assert "window attention backward: least time 12.2099 ms" in said
    # the layer that sees the whole sequence: ``attention`` less the
    # scope, 9 and 20 us, against T (T + 1) / 2 pairs
    assert _read(FULL_METRICS[0], run) == pytest.approx(
        100 * 2.7910 / 0.009, rel=1e-4)
    assert _read(FULL_METRICS[1], run) == pytest.approx(
        100 * 6.9774 / 0.020, rel=1e-4)
    said = "\n".join(run.lines)
    assert "full attention forward: least time 2.7910 ms a step " \
        "(compute-bound), took 0.009 ms" in said
    assert "full attention backward: least time 6.9774 ms" in said


def test_least_time_by_shapes_at_the_cells_shape():
    """1 x 8192 tokens, 32 heads of 128, window 2048, four window layers:
    2048 x 8192 - 2048 x 2047 / 2 = 14.68 M pairs (the causal 33.56 M:
    44 %), x 4 x 32 x 128 = 240.5 GFLOP forward = 1.221 ms a layer at 197
    TFLOP/s, 2.5 times that backward; q, k, v, o are 4 x 67 MB = 0.33 ms
    at 819 GB/s: compute-bound both ways."""
    cell = manifest.Cell(manifest.load(), CELL)
    peaks = device.peaks("TPU v5 lite")
    assert window_attention.window_layers(cell.config) == (4, 2048)
    pairs = window_attention.seen_pairs(8192, 2048)
    assert pairs == 2048 * 8192 - 2048 * 2047 / 2 == 14681088
    assert pairs / (8192 * 8193 / 2) == pytest.approx(0.4375, abs=1e-3)
    assert window_attention.seen_pairs(8192, 8192) == 8192 * 8193 / 2
    assert window_attention.seen_pairs(100, 8192) == 100 * 101 / 2
    fwd, bound = manifest.module("layer_metrics", METRICS[2]).least_ms(
        cell.config, cell.traffic, peaks)
    assert bound == "compute"
    assert fwd == pytest.approx(4 * 4 * 32 * 128 * pairs
                                / peaks["bf16_flops_per_s"] * 1e3)
    assert fwd / 4 == pytest.approx(1.221, abs=1e-3)
    bwd, bound = manifest.module("layer_metrics", METRICS[3]).least_ms(
        cell.config, cell.traffic, peaks)
    assert bound == "compute" and bwd == pytest.approx(2.5 * fwd)
    assert bwd / 4 == pytest.approx(3.052, abs=1e-3)
    # memory-bound where the window is a few keys wide
    thin = dict(cell.config, sliding_window=16)
    got, bound = manifest.module("layer_metrics", METRICS[2]).least_ms(
        thin, cell.traffic, peaks)
    assert bound == "memory"
    assert got == pytest.approx(4 * 4 * 8192 * 4096 * 2
                                / peaks["hbm_bytes_per_s"] * 1e3)
    # a model without window layers has none to count
    assert window_attention.window_layers({"num_layers": 4}) == (0, None)
    # the one layer that sees the whole sequence: the causal half's pairs
    full, bound = manifest.module("layer_metrics", FULL_METRICS[0]).least_ms(
        cell.config, cell.traffic, peaks)
    assert bound == "compute"
    assert full == pytest.approx(4 * 32 * 128 * (8192 * 8193 / 2)
                                 / peaks["bf16_flops_per_s"] * 1e3)
    assert full == pytest.approx(2.791, abs=1e-3)
    assert manifest.module("layer_metrics", FULL_METRICS[1]).least_ms(
        cell.config, cell.traffic, peaks)[0] == pytest.approx(2.5 * full)


def test_a_program_without_the_scope_leaves_the_metrics_out(program_map):
    bare = {k: _entry(v["op_name"].replace("window_attention/", ""),
                      v["kernel"], [c.replace("window_attention/", "")
                                    for c in v["called"]])
            for k, v in NAMES.items()}
    program_map(bare)
    run = _Run(_summary())
    for metric in METRICS + FULL_METRICS:
        assert _read(metric, run) is None, metric
    # and so does a run with no device trace (a rehearsal)
    assert window_attention.of_run(_Run(None)) is None
    # forward ops only: the backward's time reads 0, not nothing; its
    # share of a roofline is never 0
    run = _Run(None)
    run.facts[window_attention.SCOPE] = {"forward": 1.0}
    assert _read(METRICS[1], run) == 0.0 and _read(METRICS[3], run) is None


def test_where_the_cells_entries_stand():
    """All of them in BENCHMARK.json, which is what the driver reads: the
    configuration, the cell, its name in the lists of the metrics whose
    readers serve it unchanged, the four ``window_attention_*`` metrics
    and its place in the four ``moe_*`` lists (until PR 37 the last two
    waited under ``benchmarks/pending/``)."""
    admitted = manifest.load()
    listed = {m["name"]: m for m in admitted["per_layer"]}
    for name in ("device_idle_share", "peak_hbm_gb", "step_device_ms",
                 "step_mfu", "trainer_host_ms_per_step",
                 "forward_ms_per_step", "backward_ms_per_step",
                 "optimizer_ms_per_step", "step_attributed_share",
                 "attention_fwd_ms_per_step", "attention_bwd_ms_per_step",
                 "setup_import_s", "setup_build_s", "setup_compile_s"):
        assert listed[name]["workloads"][-1] == CELL, name
    rate = next(m for m in admitted["end_to_end"]
                if m["name"] == "train_items_per_s_per_chip")
    assert rate["workloads"][-1] == CELL
    # readers that would miscount it (five full layers, kernels alone):
    # ``full_attention_*_roofline`` read its one full layer instead
    for name in ("attention_fwd_roofline", "attention_bwd_roofline",
                 "custom_call_ms_per_step"):
        assert CELL not in listed[name]["workloads"]
    for name in METRICS + FULL_METRICS:
        assert listed[name] == {
            "name": name, "unit": "%" if name.endswith("roofline") else "ms",
            "better": "higher" if name.endswith("roofline") else "lower",
            "source": "device_trace", "layer": "kernels",
            "moves": "train_items_per_s_per_chip", "workloads": [CELL]}
    for name in MOE:
        assert listed[name]["workloads"] == ["qwen3next_train_seq8192",
                                             CELL]
    cell = manifest.Cell(admitted, CELL)
    assert set(METRICS) | set(FULL_METRICS) | set(MOE) \
        <= {m["name"] for m in cell.per_layer}
    # no list names a cell the manifest lacks (the retired one's name)
    cells = {w["name"] for w in admitted["workloads"]}
    for m in admitted["end_to_end"] + admitted["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
    assert not os.path.exists(os.path.join(
        ROOT, "benchmarks", "pending", "trinitymini_train_seq8192.json"))


def test_the_configuration_keeps_the_published_widths():
    cell = manifest.Cell(manifest.load(), CELL)
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "trinity-mini")
    assert entry["reduced"] == cell.config["reduced"] == [
        "num_layers", "num_dense_layers", "num_experts", "vocab_size",
        "layer_types"]
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
    for key in ("num_experts", "vocab_size", "num_dense_layers",
                "num_hidden_layers"):
        assert cell.config["published"][key] == published[key], key
    assert cell.config["num_experts"] * \
        cell.config["deployment"]["chips_per_layer"] == \
        published["num_experts"]
    assert cell.config["vocab_size"] * 8 == published["vocab_size"]
    # one leading dense layer (published layer 1), then layers 4-7
    built = [published["layer_types"][i] for i in (1, 4, 5, 6, 7)]
    assert cell.config["layer_types"] == built
    assert cell.config["num_layers"] == len(built)
    assert cell.traffic["batch"] == 1 and cell.traffic["seq_len"] == 8192
    assert cell.traffic["routing"] == "balanced"
    assert "router" not in cell.traffic


def test_the_training_check_catches_a_window_layer_that_sees_every_key():
    """``degrade.py --program-key sliding_window=<T>``: the program
    built with the window as wide as the sequence, the reference with
    the file's, and the check says so by the median logit."""
    from benchmarks import degrade

    cell = manifest.Cell(manifest.load(), CELL).rehearse()
    model = manifest.module("models", cell.config["model"])
    sizes = model.reference_sizes
    assert degrade.faulted_check(cell, 3, {"sliding_window": 1024},
                                 allow_cpu=True)
    assert model.reference_sizes is sizes       # put back


def test_the_training_check_catches_weights_at_three_bits_of_mantissa():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "rehearse.py"),
         "--workload", CELL, "--seed", "3", "--degrade"],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "the degraded program is caught" in proc.stdout
