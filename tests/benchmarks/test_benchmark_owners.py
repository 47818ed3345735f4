"""The readers of the program's owners (``benchmarks/owners.py``):
``step_owned_share`` and ``unnamed_ms_per_step`` on a trace written with
``harness/xplane_text.py`` and a hand-made map with owners, with known
answers; the same map without owners (a program from before them);
the recorded GPT-2 steps, whose map has none; and a run with no step
to read."""
import gzip
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import owners  # noqa: E402
from benchmarks.harness import manifest, xplane, xplane_text  # noqa: E402
from tests.benchmarks.test_benchmark_attribution import (  # noqa: E402
    BWD, FIXTURES, FWD, J, _Run)

MOE = FWD + "net0_moe/moe_experts/"
CAST = J + "convert_element_type"
# the program's map, with the fields PR 40 adds
NAMES = {
    "fusion.1": {"op_name": FWD + "net0_dense0/dot_general", "kernel": "",
                 "called": [], "owner": "", "via": ""},
    "copy.2": {"op_name": "", "kernel": "", "called": [],
               "owner": BWD + "net0_conv0/conv_general_dilated",
               "via": "user"},
    "broadcast.3": {"op_name": "", "kernel": "", "called": [],
                    "owner": MOE + "scatter-add", "via": "user"},
    # a compiler kernel with a bare op_name
    "ragged-dot-none.4": {"op_name": "ragged-dot-none",
                          "kernel": "ragged-dot-none.4", "called": [],
                          "owner": MOE + "mul", "via": "user"},
    # owned, by a cast outside value_and_grad
    "copy-done.5": {"op_name": "", "kernel": "", "called": [],
                    "owner": CAST, "via": "operand"},
    # named, outside the three phases
    "fusion.6": {"op_name": CAST, "kernel": "", "called": [CAST],
                 "owner": "", "via": ""},
    "copy.7": {"op_name": "", "kernel": "", "called": [], "owner": "",
               "via": ""},
    "fusion.8": {"op_name": J + "optimizer/add", "kernel": "",
                 "called": [J + "optimizer/add"], "owner": "", "via": ""},
    "fusion.9": {"op_name": BWD + "net0_dense0/dot_general", "kernel": "",
                 "called": [], "owner": "", "via": ""},
}
# (instruction, start us, duration us) of one 100 us step
STEP = (("fusion.1", 0, 30), ("copy.2", 30, 10), ("broadcast.3", 40, 5),
        ("ragged-dot-none.4", 45, 8), ("copy-done.5", 53, 4),
        ("fusion.6", 57, 3), ("copy.7", 60, 1), ("fusion.8", 61, 9),
        ("fusion.9", 70, 30))
UNNAMED_MS = (10 + 5 + 8 + 4 + 1) / 1e3


def _summary(steps=3, module="jit_sharded_step(7)"):
    from jax.profiler import ProfileData

    dev = {"ops": [], "async": [], "modules": []}
    for i in range(steps):
        t0 = 1e3 + i * 200e3
        dev["modules"].append((module, t0, 100e3, {}))
        for name, start, dur in STEP:
            op = xplane.base_name(name)
            op = "custom-call" if op.startswith("ragged") else op
            dev["ops"].append((name, t0 + start * 1e3, dur * 1e3, {
                "op": op, "kind": "kLoop" if op == "fusion" else "",
                "target": "tpu_custom_call" if op == "custom-call"
                else ""}))
    trace = {"devices": {0: dev}, "host": []}
    return xplane.reduce(xplane.read(ProfileData.from_text_proto(
        xplane_text.to_text_proto(trace))))


@pytest.fixture
def program_map(monkeypatch):
    from mxnet_tpu.observability import perf

    def install(names):
        monkeypatch.setattr(perf, "ledger", lambda: {
            "sharded_step@abc": {"label": "sharded_step"}})
        monkeypatch.setattr(perf, "op_names", lambda key: names)
    install(NAMES)
    return install


def _read(metric, run):
    return manifest.module("layer_metrics", metric).read(run)


def _without_owners(names):
    return {k: {f: v for f, v in e.items() if f not in ("owner", "via")}
            for k, e in names.items()}


def test_owners_put_the_unnamed_ops_in_their_phases(program_map):
    run = _Run(_summary())
    assert _read("step_device_ms", run) == pytest.approx(0.100)
    # named: forward 30, backward 30, optimizer 9 of 100
    assert _read("step_attributed_share", run) == pytest.approx(69.0)
    # owned besides: forward 5 + 8 (the expert layer's fill and grouped
    # product), backward 10 (a copy for a convolution's gradient)
    assert _read("step_owned_share", run) == pytest.approx(92.0)
    said = "\n".join(run.lines)
    assert "named outside the phases: 0.003 ms, 3.000 % of the step, " \
        "1 instruction(s); largest: fusion.6 0.003" in said
    assert "owned outside the phases: 0.004 ms" in said
    assert "no name and no owner: 0.001 ms, 1.000 % of the step, " \
        "1 instruction(s); largest: copy.7 0.001" in said


def test_unnamed_time_splits_by_kind_scope_and_phase(program_map):
    run = _Run(_summary())
    reader = manifest.module("layer_metrics", "unnamed_ms_per_step")
    reader.SMALL_MS = 0.0045        # every row but the two smallest
    assert reader.read(run) == pytest.approx(UNNAMED_MS)
    ops, names, n_steps, _ = owners.step(run)
    rows, tails, unknown = owners.unnamed_rows(ops, names, n_steps)
    assert unknown == 0
    assert rows == pytest.approx({
        ("copy", "net0_conv0", "backward"): 0.010,
        ("ragged-dot-none", "moe_experts", "forward"): 0.008,
        ("broadcast", "moe_experts", "forward"): 0.005,
        ("copy-done", "outside any block", "rest"): 0.004,
        ("copy", owners.NO_OWNER, "rest"): 0.001})
    assert sum(rows.values()) == pytest.approx(UNNAMED_MS)
    assert tails["ragged-dot-none", "moe_experts", "forward"] \
        == pytest.approx({"moe_experts/mul": 0.008})
    said = "\n".join(run.lines)
    assert "0.028 ms a step in instructions with no name" in said
    assert "moe_experts/scatter-add 0.005" in said
    assert "2 smaller row(s), each under 0.0045 ms: 0.005 ms" in said


@pytest.mark.parametrize("metric", ["step_owned_share",
                                    "unnamed_ms_per_step"])
def test_a_map_without_owners_reads_what_the_names_alone_give(program_map,
                                                              metric):
    """The parent of PR 40 is measured with these readers: its map has
    no owner field, the unnamed time is the same, and the owned share is
    the attributed one."""
    program_map(_without_owners(NAMES))
    run = _Run(_summary())
    got = _read(metric, run)
    if metric == "step_owned_share":
        assert got == pytest.approx(_read("step_attributed_share", run))
        assert "older than PR 40" in "\n".join(run.lines)
    else:
        assert got == pytest.approx(UNNAMED_MS)
        assert "the program's map has no owners" in "\n".join(run.lines)


@pytest.mark.parametrize("metric", ["step_owned_share",
                                    "unnamed_ms_per_step"])
def test_no_step_to_read_reads_nothing(program_map, metric):
    # no device trace; a module the ledger has no map for
    assert _read(metric, _Run(None)) is None
    assert _read(metric, _Run(_summary(module="jit_step(7)"))) is None


def test_recorded_gpt2_steps_without_owners(program_map):
    """The recorded map (PR 27's format) has no owners: the owned share
    is the attributed share to the digit, and the unnamed time is what
    the phases leave out less what is named outside them."""
    trace = os.path.join(FIXTURES,
                         "gpt2m_train_seq1024.2steps.textproto.gz")
    with gzip.open(os.path.join(
            FIXTURES, "gpt2m_train_seq1024.op_names.json.gz"), "rt",
            encoding="utf-8") as f:
        recorded = json.load(f)
    program_map(recorded["names"])
    run = _Run(xplane.reduce(xplane.read(xplane.open_trace(trace))))
    assert _read("step_owned_share", run) \
        == _read("step_attributed_share", run)
    unnamed = _read("unnamed_ms_per_step", run)
    step_ms = _read("step_device_ms", run)
    named = step_ms * _read("step_attributed_share", run) / 100
    assert 0 < unnamed < step_ms - named + 0.01
