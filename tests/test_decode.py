"""Generative decode serving: paged KV cache + continuous batching.

Covers the ISSUE 18 acceptance surface: greedy decode through the paged
prefill/step path matches the uncaptured full-context forward's argmax
TOKEN FOR TOKEN (fp32; the int8 KV pool tracks it at this scale), the
page pool accounts exactly (backpressure when empty, zero pages held
after every exit path), the executable set is FROZEN after warmup —
sequence membership churn never retraces — and the DecodeBatcher /
StreamRouter layers keep those invariants under concurrency, mid-stream
cancellation, preemption, replica death (fault-injected) and KV pool
exhaustion. The RolloutManager's decode gates (token parity + TTFT
ceiling) and the decode SLO gauges ride the same tiny model.
"""
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import capture, serving
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon.model_zoo.transformer import transformer_lm
from mxnet_tpu.observability import metrics
from mxnet_tpu.resilience import faults
from mxnet_tpu.serving.batcher import DecodeBatcher

VOCAB, MAX_LEN = 40, 48


@pytest.fixture(scope="module")
def net():
    mx.random.seed(7)
    block = transformer_lm(vocab=VOCAB, units=24, num_heads=2,
                           num_layers=1, max_len=MAX_LEN)
    block.initialize()
    block(mx.nd.array(np.zeros((1, 8), np.int32), dtype="int32"))
    return block


@pytest.fixture(scope="module")
def pred(net):
    return serving.DecodePredictor(net, page_size=4, num_pages=16,
                                   max_seqs=2, prefill_buckets=(8, 16),
                                   warmup=True)


@pytest.fixture(scope="module")
def ref_decode(net):
    def run(prompt, n):
        seq, out = list(prompt), []
        for _ in range(n):
            logits = net(mx.nd.array(np.asarray([seq], np.int32),
                                     dtype="int32"))
            nxt = int(np.asarray(logits.asnumpy())[0, -1].argmax())
            out.append(nxt)
            seq.append(nxt)
        return out
    return run


@pytest.fixture(autouse=True)
def _clean_stats():
    serving.reset_stats()
    faults.reset()
    yield
    faults.reset()


# --------------------------------------------------------------- parity
@pytest.mark.parametrize("prompt", [
    [3, 17, 5, 29, 11],                       # bucket 8
    list(range(2, 26, 2)),                    # 12 tokens -> bucket 16
])
def test_greedy_parity_token_for_token(pred, ref_decode, prompt):
    got = pred.greedy_decode(list(prompt), 10)
    assert got == ref_decode(prompt, 10)
    assert pred.pool.in_use == 0


def test_greedy_parity_int8_kv(net, ref_decode):
    pred8 = serving.DecodePredictor(net, page_size=4, num_pages=16,
                                    max_seqs=2, prefill_buckets=(8,),
                                    kv_dtype="int8", warmup=True)
    assert {str(a.dtype) for a in pred8._kv[:2]} == {"int8"}
    prompt = [3, 17, 5, 29, 11]
    got = pred8.greedy_decode(prompt, 10)
    ref = ref_decode(prompt, 10)
    # the first token comes straight off the fp32 prefill activations
    assert got[0] == ref[0]
    # the int8 pool's quantization noise must not derail greedy argmax
    # at this scale (deterministic: exact agreement measured 10/10)
    assert sum(a == b for a, b in zip(got, ref)) >= 8
    assert pred8.pool.in_use == 0


def test_eos_stops_generation(pred, ref_decode):
    prompt = [3, 17, 5, 29, 11]
    ref = ref_decode(prompt, 10)
    eos = ref[3]
    got = pred.greedy_decode(prompt, 10, eos_id=eos)
    assert got == ref[:4]          # emitted up to AND including the eos
    assert pred.pool.in_use == 0


# --------------------------------------------------- pool + zero retrace
def test_pool_backpressure_and_exact_accounting(net):
    small = serving.DecodePredictor(net, page_size=4, num_pages=3,
                                    max_seqs=2, prefill_buckets=(8,),
                                    warmup=True)
    held = small.pool.alloc(2)
    assert held is not None and small.pool.in_use == 2
    with pytest.raises(MXNetError, match="backpressure"):
        small.greedy_decode([1, 2, 3], 12)   # needs 4 pages, 0 free
    assert serving.stats()["decode_backpressure"] >= 1
    small.pool.free(held)
    assert small.pool.in_use == 0
    assert small.greedy_decode([1, 2, 3], 2) is not None


def test_zero_retrace_after_warmup(pred):
    pred.greedy_decode([3, 1, 4], 6)
    keys = list(pred.compiled_keys)
    before = {k: capture.stats().get(k, 0)
              for k in ("capture_retraces", "capture_misses")}
    # churn through both buckets and the probe path: replay only
    pred.greedy_decode([3, 1, 4, 1, 5], 8)
    pred.greedy_decode(list(range(12)), 8)
    pred.predict_raw(np.zeros((1, 8), np.int32))
    assert list(pred.compiled_keys) == keys
    after = {k: capture.stats().get(k, 0)
             for k in ("capture_retraces", "capture_misses")}
    assert after == before


def test_predict_raw_probe_surface(pred):
    outs, rows = pred.predict_raw(np.zeros((2, 8), np.int32))
    assert rows == 2
    assert np.asarray(outs[0]).shape == (2, 8, VOCAB)
    # the BatchServer coercion shims (fleet probes ride these)
    feeds, rows = pred._coerce_feeds(np.zeros((1, 8), np.int32))
    assert rows == 1 and feeds["data"].dtype == np.int32
    assert pred._sig_of(feeds) == (("data", (8,), "int32"),)
    with pytest.raises(MXNetError):
        pred._coerce_feeds({"data": np.zeros((8,), np.int32)})
    assert pred.buckets == (1,)


# --------------------------------------------------- continuous batching
def test_batcher_concurrent_streams_parity(pred, ref_decode):
    bat = DecodeBatcher(pred, ttft_slo_ms=60000)
    rs = np.random.RandomState(3)
    prompts = [[int(t) for t in rs.randint(0, VOCAB, rs.randint(3, 12))]
               for _ in range(5)]
    try:
        streams = [bat.submit(p, 8) for p in prompts]
        results = [s.result(timeout=60) for s in streams]
        for p, r in zip(prompts, results):
            assert r == ref_decode(p, 8)
    finally:
        bat.close()
    assert pred.pool.in_use == 0
    st = serving.stats()
    assert st["decode_sequences"] == 5
    assert st["decode_evictions"] == 5


def test_cancellation_mid_stream_frees_pages(pred):
    bat = DecodeBatcher(pred, ttft_slo_ms=60000)
    try:
        s = bat.submit([5, 9, 2], 500)
        it = s.tokens(timeout=60)
        next(it)
        next(it)
        s.cancel()
        list(it)
        assert s.reason == "cancelled"
        deadline = time.time() + 5
        while pred.pool.in_use and time.time() < deadline:
            time.sleep(0.01)
        assert pred.pool.in_use == 0
    finally:
        bat.close()


def test_preemption_keeps_parity(net, ref_decode):
    tiny = serving.DecodePredictor(net, page_size=4, num_pages=8,
                                   max_seqs=3, prefill_buckets=(8,),
                                   warmup=True)
    bat = DecodeBatcher(tiny, ttft_slo_ms=60000)
    prompts = [[2, 7, 1, 9], [4, 4, 8, 3], [1, 6, 6, 2]]
    try:
        streams = [bat.submit(p, 16) for p in prompts]
        for p, s in zip(prompts, streams):
            assert s.result(timeout=120) == ref_decode(p, 16)
    finally:
        bat.close()
    assert tiny.pool.in_use == 0


def test_ttft_slo_miss_counter(pred):
    bat = DecodeBatcher(pred, ttft_slo_ms=0.0)   # every first token late
    try:
        bat.submit([1, 2, 3], 2).result(timeout=60)
    finally:
        bat.close()
    st = serving.stats()
    assert st["decode_ttft_misses"] >= 1
    assert st["decode_p99_ttft_us"] > 0
    assert st["decode_p99_itl_us"] > 0


# ------------------------------------------------------- injected faults
def test_replica_death_fails_streams_and_frees_pages(pred):
    bat = DecodeBatcher(pred, ttft_slo_ms=60000)
    try:
        faults.arm("decode_replica_death", at_step=0, times=1)
        s1 = bat.submit([5, 1, 3], 20)
        s2 = bat.submit([2, 8, 4], 20)
        with pytest.raises(faults.DecodeReplicaDead):
            s1.result(timeout=60)
        with pytest.raises(faults.DecodeReplicaDead):
            s2.result(timeout=60)
        assert bat.dead
        assert pred.pool.in_use == 0
    finally:
        faults.reset()
        bat.close()


def test_kv_pool_exhaustion_backpressures_then_recovers(net, ref_decode):
    tiny = serving.DecodePredictor(net, page_size=4, num_pages=8,
                                   max_seqs=2, prefill_buckets=(8,),
                                   warmup=True)
    bat = DecodeBatcher(tiny, ttft_slo_ms=60000)
    try:
        with faults.inject("kv_pool_exhaustion", at_step=0, times=3) as f:
            got = bat.submit([7, 3, 9], 5).result(timeout=60)
        assert got == ref_decode([7, 3, 9], 5)
        assert f.fired >= 1
        assert serving.stats()["decode_backpressure"] >= 1
        assert tiny.pool.in_use == 0
    finally:
        bat.close()


def test_stream_router_reroutes_on_replica_death(net, ref_decode):
    def factory():
        return serving.DecodePredictor(net, page_size=4, num_pages=16,
                                       max_seqs=2, prefill_buckets=(8,),
                                       warmup=True)

    router = serving.StreamRouter(factory, replicas=2, ttft_slo_ms=60000)
    try:
        prompt = [5, 11, 23, 2]
        with faults.inject("decode_replica_death", at_step=2, times=1):
            got = router.submit_stream(prompt, 12).result(timeout=120)
        assert got == ref_decode(prompt, 12)
        assert serving.stats()["decode_reroutes"] >= 1
        assert router.live_replicas == 1
        assert router.revive() == 1
        assert router.live_replicas == 2
        assert all(b.predictor.pool.in_use == 0 for b in router.replicas)
    finally:
        router.close()


# -------------------------------------------------- operator + SLO wires
def test_rollout_decode_gates_promote_and_ttft_rollback(net, monkeypatch):
    import itertools
    import types

    from mxnet_tpu.serving import operator

    # the canary windows read a clock the test sets (every probe takes
    # 1 ms): one TTFT sample a window, timed on a host running five other
    # workers, let a slow baseline sample pass the x100 candidate
    ticks = itertools.count()
    monkeypatch.setattr(operator, "time", types.SimpleNamespace(
        perf_counter=lambda: next(ticks) * 1e-3))

    def factory():
        return serving.DecodePredictor(net, page_size=4, num_pages=16,
                                       max_seqs=2, prefill_buckets=(8,),
                                       warmup=True)

    batch = np.zeros((1, 8), np.int32)
    with serving.Fleet(factory, replicas=1, mode="thread") as fleet:
        assert fleet.wait_healthy(timeout=30)
        # the rollback half forces x100 against an allowance of x30
        mgr = serving.RolloutManager(fleet, eval_batch=batch,
                                     canary_calls=4, max_latency_x=30.0)
        params = net.collect_params()
        good = {f"arg:{n}": params[n].data() for n in params}
        dec = mgr.rollout_weights(good)
        assert dec["action"] == "promote"
        assert dec["canary_ttft_us"] >= 0
        assert dec["baseline_ttft_us"] >= 0

        # a canary whose TTFT blows the allowance must roll back
        orig = serving.RolloutManager._measure_ttft
        calls = {"n": 0}

        def slow(self, p, prompt):
            calls["n"] += 1
            v = orig(self, p, prompt)
            return v * 100.0 if calls["n"] > 1 else v

        serving.RolloutManager._measure_ttft = slow
        try:
            dec = mgr.rollout_weights(good)
        finally:
            serving.RolloutManager._measure_ttft = orig
        assert dec["action"] == "rollback"
        assert dec["gate"] == "decode_ttft"


def test_decode_slo_gauges_derive(pred):
    metrics.reset()
    bat = DecodeBatcher(pred, ttft_slo_ms=60000)
    try:
        bat.submit([1, 2, 3], 4).result(timeout=60)
    finally:
        bat.close()
    metrics.update_decode_slo()
    assert metrics.get("mxnet_tpu_decode_ttft_p50_us").value() > 0
    assert metrics.get("mxnet_tpu_decode_ttft_p99_us").value() > 0
    assert metrics.get("mxnet_tpu_decode_itl_p99_us").value() > 0
    assert metrics.get("mxnet_tpu_decode_ttft_hit_rate").value() == 1.0
