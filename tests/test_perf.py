"""Performance observatory (ISSUE 11, docs/observability.md
"Performance attribution", PERF.md round 6).

Covers: the per-executable perf ledger (captured trainer steps AND
serving bucket executables land cost + memory + compile-ms entries,
keyed by the AOT fingerprint), the LEDGER_FIELDS closure (the RD005
runtime mirror), dump()/Prometheus surfacing, the opt-in
dependency-chained device-timing mode and its MFU/roofline derivation,
and tools/perf_gate.py (compare semantics, baseline-store validation,
the committed store's validity, the perf_regression fault hook).
Marker: perf (tier-1; the live gate run is slow-marked).
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
import mxnet_tpu.observability as obs
from mxnet_tpu import capture, serving
from mxnet_tpu.observability import metrics, perf, trace, flight
from mxnet_tpu.resilience import faults

pytestmark = pytest.mark.perf

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _perf_gate():
    spec = importlib.util.spec_from_file_location(
        "perf_gate_under_test",
        os.path.join(ROOT, "tools", "perf_gate.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _clean_perf():
    trace.set_enabled(False)
    trace.clear()
    perf.set_device_time(False)
    perf.clear()
    faults.reset()
    yield
    trace.set_enabled(False)
    trace.clear()
    perf.set_device_time(False)
    perf.clear()
    faults.reset()


def _loss(out, y):
    return ((out - y) ** 2).sum()


def _captured_step(seed=11, label="perftest_step"):
    mx.random.seed(seed)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    step = capture.capture(trainer, net=net, loss_fn=_loss, label=label)
    x = mx.nd.array(np.ones((2, 3), np.float32))
    y = mx.nd.ones((2, 4))
    return step, x, y


# ------------------------------------------------------------- the ledger

def test_captured_step_lands_ledger_entry():
    step, x, y = _captured_step()
    step(x, y, batch_size=2)
    entries = [e for e in perf.ledger().values()
               if e["label"] == "perftest_step"]
    assert len(entries) == 1
    e = entries[0]
    assert e["compile_ms"] is not None and e["compile_ms"] > 0
    assert e["compiles"] == 1
    # cost + memory analysis are available on the CPU backend
    assert e["flops"] and e["flops"] > 0
    assert e["peak_hbm_bytes"] > 0
    assert e["backend"] == "cpu"
    # the key embeds the AOT fingerprint the entry records
    key = [k for k, v in perf.ledger().items()
           if v["label"] == "perftest_step"][0]
    assert key == f"perftest_step@{e['fingerprint'][:16]}"
    assert len(e["fingerprint"]) == 32


def test_serving_bucket_lands_ledger_entry():
    mx.random.seed(5)
    net = mx.gluon.nn.Dense(4, in_units=3)
    net.initialize()
    pred = serving.Predictor.from_block(
        net, input_shapes={"data": (3,)}, batch_sizes=(2,))
    pred.predict(np.ones((1, 3), np.float32))
    entries = {k: e for k, e in perf.ledger().items()
               if e["label"] == "serving_bucket2"}
    assert len(entries) == 1
    (key, e), = entries.items()
    assert e["compile_ms"] > 0 and e["peak_hbm_bytes"] > 0 and e["flops"]
    assert key.startswith("serving_bucket2@")


def test_ledger_fields_closure():
    """Every ledger entry carries exactly perf.LEDGER_FIELDS — the
    runtime mirror of the RD005 docs gate (a field the code records but
    the declaration misses would dodge the documentation check)."""
    step, x, y = _captured_step()
    step(x, y, batch_size=2)
    for key, e in perf.ledger().items():
        assert set(e) == set(perf.LEDGER_FIELDS), key


def test_recompile_merges_into_one_entry():
    step, x, y = _captured_step()
    step(x, y, batch_size=2)
    key, e0 = next(iter(perf.ledger().items()))
    perf.note_compile(e0["label"], e0["fingerprint"], object(), 0.5)
    led = perf.ledger()
    assert len(led) == 1 and led[key]["compiles"] == 2
    # a lazily-jitted fallback without analysis methods still lands
    assert led[key]["compile_ms"] == pytest.approx(500.0)


def test_ledger_key_schema():
    assert perf.ledger_key("a_step", "ab" * 16) == "a_step@" + "ab" * 8
    assert perf.ledger_key("a_step", "") == "a_step@none"
    assert perf.ledger_key("a_step", None) == "a_step@none"
    # the aval signature folds INTO the identity; no signature = the
    # bare fingerprint (stable for fixed-shape sites)
    assert perf.combined_fingerprint("ab" * 16, None) == "ab" * 16
    a = perf.combined_fingerprint("ab" * 16, "((2, 3), 'float32')")
    b = perf.combined_fingerprint("ab" * 16, "((4, 3), 'float32')")
    assert a != b and len(a) == 32 and a != "ab" * 16


def test_one_capturedexec_two_shapes_two_ledger_entries():
    """Review fix: the AOT cache keys by (fingerprint, signature); a
    ledger keyed by fingerprint alone would merge the two programs one
    CapturedExec compiles for two batch shapes into one last-writer-wins
    entry. Each signature must own its entry."""
    import jax.numpy as jnp

    exe = capture.CapturedExec(lambda x: x * 2.0, label="two_shape",
                               fingerprint="ff" * 16, sig_argnums=(0,))
    exe(jnp.ones((2, 3)))
    exe(jnp.ones((4, 3)))
    keys = [k for k, e in perf.ledger().items()
            if e["label"] == "two_shape"]
    assert len(keys) == 2, keys
    # and with device timing on, each shape's timings land on ITS entry
    perf.set_device_time(True)
    exe(jnp.ones((2, 3)))
    exe(jnp.ones((4, 3)))
    timed = {k: e["device_calls"] for k, e in perf.ledger().items()
             if e["label"] == "two_shape"}
    assert all(n == 1 for n in timed.values()), timed


def test_update_gauges_prunes_stale_executables():
    """Review fix: a re-fingerprinted program (retrace churn) must not
    leave its old key exporting frozen gauge values forever."""
    perf.note_compile("stale_exe", "aa" * 16, object(), 0.01)
    perf.update_gauges()
    g = metrics.get("mxnet_tpu_compile_ms")
    old_key = perf.ledger_key("stale_exe", "aa" * 16)
    assert g.value(executable=old_key) is not None
    perf.clear()
    perf.note_compile("fresh_exe", "bb" * 16, object(), 0.01)
    perf.update_gauges()
    assert g.value(executable=old_key) is None, \
        "stale executable still exported"
    assert g.value(
        executable=perf.ledger_key("fresh_exe", "bb" * 16)) is not None


def test_dump_and_prometheus_surface_the_ledger():
    step, x, y = _captured_step()
    step(x, y, batch_size=2)
    d = obs.dump()
    assert d["perf"]["entries"], "dump() must expose the perf ledger"
    # XLA-CPU's device_kind has no row in the peaks table: the roofs are
    # reported as unknown, never borrowed from another device
    assert d["perf"]["peaks"] == {"flops_per_s": None,
                                  "hbm_bytes_per_s": None}
    json.dumps(d, default=str)  # JSON-able end to end
    text = metrics.render_prometheus()
    key = next(iter(perf.ledger()))
    assert f'mxnet_tpu_compile_ms{{executable="{key}"}}' in text
    assert f'mxnet_tpu_executable_peak_hbm_bytes{{executable="{key}"}}' \
        in text


# ---------------------------------------------------------- device timing

def test_device_timing_splits_and_derives_mfu(monkeypatch):
    # the MFU / roofline gauges need a roof: XLA-CPU has none in the
    # table, so this test supplies one through the env overrides
    monkeypatch.setenv("MXNET_TPU_PERF_PEAK_FLOPS", "2e11")
    monkeypatch.setenv("MXNET_TPU_PERF_PEAK_GBPS", "50")
    step, x, y = _captured_step()
    step(x, y, batch_size=2)  # compile outside the timed window
    trace.set_enabled(True)
    perf.set_device_time(True)
    step(x, y, batch_size=2)
    key, e = next(iter(perf.ledger().items()))
    assert e["device_calls"] >= 1
    assert e["device_ms"] > 0 and e["dispatch_ms"] >= 0
    assert e["mfu"] and 0 < e["mfu"] < 1
    assert e["roofline_fraction"] and e["roofline_fraction"] > 0
    spans = trace.spans(name="perf.device_execute")
    assert spans, "device-timed calls must record a retroactive span"
    attrs = spans[-1]["attrs"]
    assert attrs["executable"] == key
    assert attrs["host_dispatch_ns"] >= 0 and attrs["device_ns"] >= 0
    assert spans[-1]["dur_ns"] >= attrs["device_ns"]
    # the gauges export once derived
    text = metrics.render_prometheus()
    assert f'mxnet_tpu_mfu{{executable="{key}"}}' in text
    assert f'mxnet_tpu_device_ms{{executable="{key}"}}' in text


def test_device_timing_off_is_silent():
    before = obs.stats()["perf_device_timings"]
    step, x, y = _captured_step()
    trace.set_enabled(True)
    step(x, y, batch_size=2)
    step(x, y, batch_size=2)
    assert obs.stats()["perf_device_timings"] == before
    assert not trace.spans(name="perf.device_execute")
    e = next(iter(perf.ledger().values()))
    assert e["device_calls"] == 0 and e["mfu"] is None


def test_nominal_peaks_env_override(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_PERF_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("MXNET_TPU_PERF_PEAK_GBPS", "2000")
    flops, bw = perf.nominal_peaks("cpu")
    assert flops == 1e15 and bw == 2000e9
    monkeypatch.setenv("MXNET_TPU_PERF_PEAK_FLOPS", "not-a-number")
    flops, _ = perf.nominal_peaks("TPU v5 lite")
    assert flops == 197e12  # malformed override falls back, never raises


def test_unknown_device_kind_has_no_peaks(monkeypatch):
    monkeypatch.delenv("MXNET_TPU_PERF_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("MXNET_TPU_PERF_PEAK_GBPS", raising=False)
    assert perf.nominal_peaks("cpu") == (None, None)
    step, x, y = _captured_step()
    step(x, y, batch_size=2)
    perf.set_device_time(True)
    step(x, y, batch_size=2)
    e = next(iter(perf.ledger().values()))
    assert e["device_calls"] >= 1 and e["device_ms"] > 0
    assert e["mfu"] is None and e["roofline_fraction"] is None


# ------------------------------------------------------------- gate logic

_BASE = {
    "trainer_step@feedfacefeedface": {
        "step_ms": 1.0, "compile_ms": 50.0, "peak_hbm_bytes": 4096},
}


def test_gate_compare_clean_and_regressed():
    pg = _perf_gate()
    current = {k: dict(v) for k, v in _BASE.items()}
    regs, rebase = pg.compare(current, _BASE)
    assert not regs and not rebase
    # within tolerance: 40% slower step (tol 50%) passes
    current2 = {k: dict(v) for k, v in _BASE.items()}
    current2["trainer_step@feedfacefeedface"]["step_ms"] = 1.4
    regs, _ = pg.compare(current2, _BASE)
    assert not regs
    # beyond tolerance: peak HBM +20% (tol 10%) fails with a flight event
    mark = flight.last_seq()
    current3 = {k: dict(v) for k, v in _BASE.items()}
    current3["trainer_step@feedfacefeedface"]["peak_hbm_bytes"] = 4915.2
    regs, _ = pg.compare(current3, _BASE)
    assert len(regs) == 1 and regs[0]["metric"] == "peak_hbm_bytes"
    events = [e for e in flight.events(kind="perf", since_seq=mark)
              if e.get("event") == "regression"]
    assert len(events) == 1 and events[0]["metric"] == "peak_hbm_bytes"


def test_gate_first_measure_can_suppress_flight_events():
    """Review fix: the gate's first (possibly noisy) measure passes
    record_flight=False, so a regression the one-shot re-measure then
    clears never plants phantom perf:regression events in the recorder."""
    pg = _perf_gate()
    mark = flight.last_seq()
    current = {k: dict(v) for k, v in _BASE.items()}
    current["trainer_step@feedfacefeedface"]["peak_hbm_bytes"] = 9999.0
    regs, _ = pg.compare(current, _BASE, record_flight=False)
    assert regs, "the regression itself must still be detected"
    assert not [e for e in flight.events(kind="perf", since_seq=mark)
                if e.get("event") == "regression"]


def test_gate_rebaselines_changed_fingerprints():
    pg = _perf_gate()
    current = {"trainer_step@0123456789abcdef": dict(
        _BASE["trainer_step@feedfacefeedface"])}
    regs, rebase = pg.compare(current, _BASE)
    assert not regs
    assert rebase == ["trainer_step@0123456789abcdef"]


def test_gate_perf_regression_fault_hook():
    pg = _perf_gate()
    current = {k: dict(v) for k, v in _BASE.items()}
    with faults.inject("perf_regression") as f:
        regs, _ = pg.compare(current, _BASE)
    assert f.fired == 1 and len(regs) == len(pg.GATED_METRICS)
    # disarmed, the identical measurements pass — and the fault did not
    # mutate the caller's dict in place
    regs2, _ = pg.compare(current, _BASE)
    assert not regs2


def test_validate_baseline_catches_drift():
    pg = _perf_gate()
    good = {"schema_version": pg.BASELINE_SCHEMA_VERSION,
            "key_schema": pg.KEY_SCHEMA_VERSION,
            "backends": {"cpu": {"entries": dict(_BASE)}}}
    assert pg.validate_baseline(good) == []
    bad_schema = dict(good, schema_version=999)
    assert any("schema_version" in p
               for p in pg.validate_baseline(bad_schema))
    bad_keys = dict(good, key_schema=999)
    assert any("key_schema" in p for p in pg.validate_baseline(bad_keys))
    stale_key = {**good, "backends": {"cpu": {"entries": {
        "no-fingerprint-separator": {"step_ms": 1.0}}}}}
    assert any("stale key format" in p
               for p in pg.validate_baseline(stale_key))
    unknown_metric = {**good, "backends": {"cpu": {"entries": {
        "a@ff00ff00": {"step_ms": 1.0, "zombie_metric": 2.0}}}}}
    assert any("unknown metric" in p
               for p in pg.validate_baseline(unknown_metric))
    negative = {**good, "backends": {"cpu": {"entries": {
        "a@ff00ff00": {"step_ms": -1.0}}}}}
    assert any("non-negative" in p for p in pg.validate_baseline(negative))
    assert any("no per-backend" in p
               for p in pg.validate_baseline(
                   {"schema_version": 1, "key_schema": 1}))


def test_committed_baseline_store_is_valid():
    """The checked-in tools/perf_baseline.json must always satisfy its
    own schema — a fingerprint-schema change lands here as a failure,
    never as a silently orphaned store."""
    pg = _perf_gate()
    data, problems = pg.load_baseline(
        os.path.join(ROOT, "tools", "perf_baseline.json"))
    assert problems == [], problems
    assert "cpu" in data["backends"]
    entries = data["backends"]["cpu"]["entries"]
    assert any(k.startswith("trainer_step@") for k in entries)
    assert any(k.startswith("serving_bucket") for k in entries)
    for rec in entries.values():
        assert set(rec) <= set(pg.GATED_METRICS)


def test_update_baseline_merges_backends(tmp_path):
    pg = _perf_gate()
    path = str(tmp_path / "b.json")
    pg.update_baseline(path, "tpu", {"k@ff00ff00": {"step_ms": 2.0}})
    pg.update_baseline(path, "cpu", dict(_BASE))
    data, problems = pg.load_baseline(path)
    assert problems == []
    assert set(data["backends"]) == {"cpu", "tpu"}
    # re-updating one backend leaves the other untouched
    pg.update_baseline(path, "cpu", dict(_BASE))
    data, _ = pg.load_baseline(path)
    assert data["backends"]["tpu"]["entries"] == {
        "k@ff00ff00": {"step_ms": 2.0}}


def test_load_baseline_missing_and_corrupt(tmp_path):
    pg = _perf_gate()
    _, problems = pg.load_baseline(str(tmp_path / "absent.json"))
    assert problems and "does not exist" in problems[0]
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    _, problems = pg.load_baseline(str(bad))
    assert problems and "cannot read" in problems[0]


# ------------------------------------------------------------- slow gates

@pytest.mark.slow
def test_perf_gate_runs_clean_end_to_end():
    """Acceptance: the gate passes clean on the unmodified repo (same
    subprocess form an operator/CI runs)."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "perf_gate.py"),
         "--interpret"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT)
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert out["metric"] == "perf_gate_regressions" and out["value"] == 0
    assert out["extra"]["checked"], "gate must actually check keys"
