"""The reduction from a profiler trace to busy / idle share, per-category
time and collective exposure: on traces built with a known answer, and on
the recorded window of ``resnet50_train_bs256`` kept under
``benchmarks/fixtures/``. CPU only: a trace is read with
``jax.profiler.ProfileData``, which needs no chip."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import xplane, xplane_text  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmarks", "fixtures",
                       "resnet50_train_bs256.2steps.textproto.gz")
PERF0 = 5_000_000_000_000      # perf-counter reading at trace time 0


def _through_proto(trace):
    """What ``xplane.read`` gives after a trip through the XSpace text
    proto and ``ProfileData``: the loader a recorded trace goes through."""
    from jax.profiler import ProfileData

    return xplane.read(ProfileData.from_text_proto(
        xplane_text.to_text_proto(trace)))


def _op(name, start, dur, op="fusion", kind="", target=""):
    return (name, float(start), float(dur),
            {"op": op, "kind": kind, "target": target})


def _sync(at):
    return ("bench.clock_sync", float(at), 10.0, {"t_perf_ns": PERF0 + at})


def _step(t0, collective=True, custom=True):
    """One 100 us training step starting at ``t0`` ns: a 40 us
    convolution, a 10 us Pallas kernel, a 16 us while loop holding two
    5 us fusions, an all-reduce in flight for 30 us (1 us start op, 5 us
    done op) of which 20 us run under a reduction fusion and 4 us under
    nothing at all, and 4 us of idle at the end: 92 us busy."""
    ops = [_op("fusion.1", t0, 40e3, kind="kOutput")]
    if custom:
        ops.append(_op("jvp__.7", t0 + 40e3, 10e3, op="custom-call",
                       target="tpu_custom_call"))
    ops += [_op("while.2", t0 + 50e3, 16e3, op="while"),
            _op("fusion.3", t0 + 51e3, 5e3, kind="kLoop"),
            _op("fusion.4", t0 + 58e3, 5e3, kind="kLoop")]
    if collective:
        ops += [_op("all-reduce-start.5", t0 + 66e3, 1e3,
                    op="all-reduce-start"),
                _op("all-reduce-done.5", t0 + 91e3, 5e3,
                    op="all-reduce-done")]
    ops.append(_op("multiply_reduce_fusion.6", t0 + 67e3, 20e3,
                   kind="kLoop"))
    return ("jit_step(9)", t0, 96e3, {}), ops


def _trace(steps=3, chips=1, **kw):
    trace = {"devices": {}, "host": [_sync(0), _sync(steps * 100e3)]}
    for n in range(chips):
        dev = trace["devices"][n] = {"ops": [], "async": [], "modules": []}
        for i in range(steps):
            module, ops = _step(1e3 + i * 100e3, **kw)
            dev["modules"].append(module)
            dev["ops"].extend(ops)
    trace["host"].append(("bench.sync", 90e3, 20e3, {}))
    return _through_proto(trace)


def _reduce(trace, steps=3, spans=()):
    return xplane.reduce(trace, (PERF0, PERF0 + steps * 100e3 + 1e3), spans)


# ------------------------------------------------------- interval arithmetic

def test_union_subtract_clip():
    merged = xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert xplane.total(merged) == 6
    assert xplane.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert xplane.subtract([(0, 10)], merged) == [(3, 5), (8, 10)]
    assert xplane.subtract(merged, [(0, 10)]) == []
    assert xplane.subtract([(0, 4), (6, 9)], [(1, 2), (3, 7)]) == \
        [(0, 1), (2, 3), (7, 9)]


def test_self_time_takes_children_out_of_their_parent():
    events = [("while", 0.0, 100.0, {}), ("a", 10.0, 20.0, {}),
              ("b", 40.0, 30.0, {}), ("b.inner", 45.0, 5.0, {}),
              ("after", 100.0, 7.0, {})]
    got = {name: self_ns for name, _, self_ns, _ in xplane.self_times(events)}
    assert got == {"while": 50.0, "a": 20.0, "b": 25.0, "b.inner": 5.0,
                   "after": 7.0}


# HLO instruction texts as this installation's profiler names its events
# (shapes shortened), and the category each falls in
HLO = [
    ("%fusion.1 = (f32[64]{0:T(128)S(1)}, bf16[256,112,112,64]{0,3,2,1:T(8,128)"
     "(2,1)}) fusion(bf16[64,4,4,12]{0,2,3,1} %convert.9, bf16[256,12,112,112]"
     " %bitcast.81), kind=kOutput, calls=%fused_computation.16",
     "fusion.1", "fusion", "matmul or convolution fusion"),
    ("%multiply_reduce_fusion.52 = (bf16[512]{0:T(512)(128)(2,1)S(1)}, "
     "bf16[256,7,7,512]{3,0,2,1}) fusion(bf16[256,7,7,512] %gte.3131), "
     "kind=kOutput, calls=%fused_computation.938",
     "multiply_reduce_fusion.52", "fusion", "reduction fusion"),
    ("%add_add_fusion.2 = bf16[256,56,56,256]{3,0,2,1:T(8,128)(2,1)} "
     "fusion(bf16[256,56,56,256] %gte.3083), kind=kLoop, calls=%fc.3",
     "add_add_fusion.2", "fusion", "elementwise fusion"),
    ("%copy-start.147 = (f32[64,4,4,12]{0,2,3,1:T(4,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(f32[64,4,4,12]{0,2,3,1:T(4,128)} %params.1)",
     "copy-start.147", "copy-start", "copy"),
    ("%slice-done.357 = f32[256,1,1,256]{3,2,1,0:T(1,128)S(1)} "
     "async-done(((f32[1024,1,1,256]), s32[]{:S(2)}) %slice-start.357)",
     "slice-done.357", "async-done", "copy"),
    ("%bitcast_dynamic-update-slice_fusion.23 = f32[24,1281,16,16,64] "
     "fusion(f32[24,1281,16,16,64] %p.4), kind=kLoop, calls=%fc.9",
     "bitcast_dynamic-update-slice_fusion.23", "fusion", "copy"),
    ('%custom-call.94 = f32[1024,1,1,256]{3,2,1,0} custom-call(f32[256,1,1,256]'
     ' %slice-done.357), custom_call_target="ConcatBitcast"',
     "custom-call.94", "custom-call", "other"),
    ('%jvp__.24 = (bf16[128,1024,64]{2,1,0}, f32[128,1024,1]{2,1,0}) '
     'custom-call(s32[1]{0} %c.1, bf16[128,1024,64] %q), '
     'custom_call_target="tpu_custom_call", operand_layout_constraints={}',
     "jvp__.24", "custom-call", "custom call"),
    ("%all-reduce.7 = (f32[64]{0}, f32[64]{0}) all-reduce(f32[64]{0} %a, "
     "f32[64]{0} %b), channel_id=3, replica_groups={{0,1,2,3}}",
     "all-reduce.7", "all-reduce", "collective"),
    ("%all-gather-start.3 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8] "
     "%x), dimensions={0}", "all-gather-start.3", "all-gather-start",
     "collective"),
    ("%while.24 = (s32[]{:T(128)}, f32[8,16,1024,64]{2,3,1,0}) "
     "while((s32[], f32[8,16,1024,64]) %tuple.5), condition=%c, body=%b",
     "while.24", "while", "other"),
    ("%fusion.1480.remat4 = f32[3,4]{1,0} fusion(f32[3,4] %p), kind=kLoop, "
     "calls=%fc.1", "fusion.1480.remat4", "fusion", "elementwise fusion"),
    ("%convert_element_type.1117 = bf16[256,3,224,224]{0,3,2,1:T(8,128)(2,1)"
     "S(1)} convert(f32[256,3,224,224]{0,3,2,1:T(8,128)} %x.1)",
     "convert_element_type.1117", "convert", "elementwise fusion"),
    ("%select_and_scatter.9 = bf16[256,112,112,64] select-and-scatter("
     "bf16[256,112,112,64] %a, bf16[256,56,56,64] %b, bf16[] %c), "
     "window={size=1x3x3x1}", "select_and_scatter.9", "select-and-scatter",
     "other"),
]


@pytest.mark.parametrize("text,short,op,category", HLO,
                         ids=[row[1] for row in HLO])
def test_hlo_text_is_parsed_and_categorized(text, short, op, category):
    got_short, meta = xplane.parse_op(text)
    assert (got_short, meta["op"]) == (short, op)
    assert xplane.categorize(got_short, meta) == category
    assert category in xplane.CATEGORIES
    # the short form a fixture stores parses to the same fields
    assert xplane.parse_op(xplane_text.op_text(got_short, meta)) == \
        (got_short, meta)


def test_async_collectives_pair_start_with_done():
    ops = [_op("all-reduce-start.1", 0, 1, op="all-reduce-start"),
           _op("all-gather-start.4", 2, 1, op="all-gather-start"),
           _op("fusion.2", 3, 10, kind="kLoop"),
           _op("all-reduce-done.1", 20, 2, op="all-reduce-done"),
           _op("all-gather-done.9", 30, 1, op="all-gather-done"),  # suffix
           _op("all-reduce.6", 40, 5, op="all-reduce")]    # synchronous
    assert xplane._collective_spans(ops) == [(0.0, 31.0), (40.0, 45.0)]
    # the profiler's async line gives the span without any pairing
    on_async_line = [_op("all-reduce-start.8", 50, 9, op="all-reduce-start")]
    assert xplane._collective_spans(ops, on_async_line)[-1] == (50.0, 59.0)


# ------------------------------------------------------------ the reduction

def test_known_trace_gives_known_shares():
    summary = _reduce(_trace())
    dev = summary["devices"][0]
    assert summary["window_s"] == pytest.approx(301e-6)
    assert dev["busy_s"] == pytest.approx(3 * 92e-6)
    cats = dev["category_s"]
    assert cats["matmul or convolution fusion"] == pytest.approx(3 * 40e-6)
    assert cats["custom call"] == pytest.approx(3 * 10e-6)
    assert cats["elementwise fusion"] == pytest.approx(3 * 10e-6)
    assert cats["reduction fusion"] == pytest.approx(3 * 20e-6)
    assert cats["other"] == pytest.approx(3 * 6e-6)       # while, self time
    assert cats["collective"] == pytest.approx(3 * 6e-6)  # start + done ops
    assert sum(cats.values()) == pytest.approx(dev["busy_s"])
    assert dev["collective_s"] == pytest.approx(3 * 30e-6)
    assert dev["collective_exposed_s"] == pytest.approx(3 * 10e-6)
    assert summary["clock"].drift_ns == pytest.approx(0.0, abs=1.0)


def test_per_step_numbers_use_complete_steps_only():
    dev = _reduce(_trace())["devices"][0]
    assert len(xplane.step_runs(dev)) == 3
    conv = xplane.per_step(
        dev, lambda n, c: c == "matmul or convolution fusion")
    assert conv == pytest.approx(40e-6)
    assert xplane.per_step(dev, lambda n, c: False) == 0.0
    # a window that cuts the first and the last step leaves one whole step
    cut = xplane.reduce(_trace(), (PERF0 + 50e3, PERF0 + 250e3))
    assert len(xplane.step_runs(cut["devices"][0])) == 1
    assert cut["window_s"] == pytest.approx(200e-6)


def test_reduction_is_the_same_every_time():
    trace = _trace(steps=4, chips=2)
    first = _reduce(trace, 4)
    for _ in range(3):
        again = _reduce(trace, 4)
        for n in (0, 1):
            for key in ("busy_s", "collective_s", "collective_exposed_s",
                        "category_s", "ops", "idle_by"):
                assert again["devices"][n][key] == first["devices"][n][key]
    assert json.dumps(xplane.breakdown(first)) == \
        json.dumps(xplane.breakdown(_reduce(trace, 4)))


def test_a_trace_without_collectives_and_one_without_custom_calls():
    lonely = _reduce(_trace(collective=False))["devices"][0]
    assert lonely["collective_s"] == 0.0
    assert lonely["collective_exposed_s"] == 0.0
    assert lonely["category_s"]["collective"] == 0.0
    assert lonely["busy_s"] == pytest.approx(3 * 86e-6)
    plain = _reduce(_trace(custom=False))["devices"][0]
    assert plain["category_s"]["custom call"] == 0.0
    assert xplane.per_step(plain, lambda n, c: c == "custom call") == 0.0
    assert plain["busy_s"] == pytest.approx(3 * 82e-6)


def test_idle_gaps_are_labelled_by_what_the_host_was_doing():
    # a program span on the perf counter, moved onto the trace's clock
    spans = [{"name": "train.sharded_step", "t0_ns": PERF0 + 200e3,
              "dur_ns": 100e3},
             {"name": "decode.token", "t0_ns": PERF0 + 240e3, "dur_ns": 20e3}]
    trace = _trace()
    trace["devices"][0]["ops"] = [
        ev for ev in trace["devices"][0]["ops"] if ev[1] < 201e3]
    summary = _reduce(trace, spans=spans)
    labels = {label: secs
              for label, secs, _, _ in summary["devices"][0]["idle_by"]}
    assert labels[xplane.BETWEEN_OPS] == pytest.approx(1e-6 + 3 * 4e-6)
    assert labels["train.sharded_step"] == pytest.approx(104e-6)
    out = xplane.breakdown(summary)
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    assert out["idle_gaps"][0][0].startswith("train.sharded_step")
    assert out["device_ops"][0][0].startswith(
        "fusion.1 [matmul or convolution fusion]")


def test_a_trace_without_device_planes_reduces_to_nothing():
    assert xplane.reduce({"devices": {}, "host": [_sync(0)]},
                         (PERF0, PERF0 + 1)) is None


def test_a_traced_window_needs_its_clock_sync():
    trace = _trace()
    trace["host"] = [ev for ev in trace["host"] if ev[0] != xplane.SYNC]
    with pytest.raises(ValueError):
        _reduce(trace)


def test_cutting_a_trace_keeps_whole_steps():
    cut = xplane_text.cut(_trace(steps=5), 1, 3)
    assert len(cut["devices"][0]["modules"]) == 3
    assert len(cut["devices"][0]["ops"]) == 3 * 8
    dev = xplane.reduce(_through_proto(cut))["devices"][0]
    assert dev["busy_s"] == pytest.approx(3 * 92e-6)     # no sync pair:
    assert len(xplane.step_runs(dev)) == 3                # the whole span


# ------------------------------------- the recorded window (chip, PR 22)

@pytest.fixture(scope="module")
def recorded():
    """Two consecutive steps of ``resnet50_train_bs256`` on one TPU v5
    lite, cut from the traced run of PR 22's first chip call by
    ``xplane_text.py <pb> <out> 5 6``."""
    return xplane.read(xplane.open_trace(FIXTURE))


def test_recorded_window_holds_what_was_recorded(recorded):
    assert sorted(recorded["devices"]) == [0]
    dev = recorded["devices"][0]
    assert len(dev["ops"]) == 8956 and len(dev["async"]) == 3656
    assert [m[0] for m in dev["modules"]] == \
        ["jit_step(12917792724494447234)"] * 2


def test_recorded_window_reduces_to_the_same_numbers_every_time(recorded):
    first = xplane.reduce(recorded)
    dev = first["devices"][0]
    assert first["window_s"] == pytest.approx(0.19958925, abs=1e-9)
    assert dev["busy_s"] == pytest.approx(0.199554355, abs=1e-9)
    assert 1 - dev["busy_s"] / first["window_s"] == pytest.approx(
        1.748e-4, rel=1e-3)
    want = {"matmul or convolution fusion": 0.078456016,
            "reduction fusion": 0.056781866,
            "elementwise fusion": 0.052101207, "copy": 0.009239736,
            "custom call": 0.0, "collective": 0.0, "other": 0.00297553}
    for cat, secs in want.items():
        assert dev["category_s"][cat] == pytest.approx(secs, abs=1e-9)
    assert dev["collective_s"] == 0.0 and dev["collective_exposed_s"] == 0.0
    runs = xplane.step_runs(dev)
    assert [round((e - s) / 1e6, 3) for s, e in runs] == [99.789, 99.793]
    per_step = xplane.per_step(dev, lambda n, c: c == "reduction fusion")
    assert per_step == pytest.approx(0.028390933, abs=1e-9)
    top = xplane.breakdown(first)["device_ops"][0]
    assert top[0] == "multiply_reduce_fusion.2 [reduction fusion] x2"
    assert top[1] == pytest.approx(0.005797868, abs=1e-9)
    for _ in range(2):
        again = xplane.reduce(recorded)
        assert again["devices"][0]["category_s"] == dev["category_s"]
        assert again["devices"][0]["ops"] == dev["ops"]
        assert again["devices"][0]["idle_by"] == dev["idle_by"]
