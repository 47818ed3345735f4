"""Open-loop token serving through ``DecodeBatcher``.

The traffic file gives the arrival process and rate, the length
distributions and the geometry of the page pool; ``harness/traffic.py``
turns it and ``--seed`` into a fixed schedule before the window opens.
One thread sends each request when it is due, whether or not earlier
ones have finished. Every time is taken on the client's side: a
request's clock starts when it was DUE, not when it was sent or
admitted, so a stall is charged to every request it delays. A token's
time is stamped as the engine hands it to the request's stream (the
benchmark's own ``TokenStream``, passed in through ``submit(stream=)``;
no reader thread per stream competes with the engine for the
interpreter).

The window opens on an empty system and closes after ``--seconds``: no
new request is sent, and what is still running is cancelled (waiting for
256-token answers to end would add half a minute to every run). Only
what happened inside the window counts.

End-to-end: ``serve_itl_p50_ms`` and ``serve_itl_p90_ms``, over every
gap between consecutive tokens of one stream (the median is a decode
step; the 90th percentile is a step with a prefill in front of it).
Recorded for the per-layer readers, not judged (PERF.md says why): first
token times (due -> first token; a request that failed, or that the
window closed on unanswered, counts as infinitely late), the 99th
percentile gap, tokens delivered per second. attempted / failed:
requests; failed is an error, a refusal, a stream that ended with
another count than its budget, or one left without a first token for
``answer_limit_s``.

Correct: no failed request, every page back in the pool after the
cancel, and for a seeded sample of requests prefill and then
``check_steps`` decode steps through the paged cache give logits within
the configuration's tolerance of the float32 reference's full forward.
"""
from __future__ import annotations

import math
import threading
import time

import numpy as np


def _stream_class():
    from mxnet_tpu.serving import TokenStream

    class TimedStream(TokenStream):
        """Stamps each token as it is delivered, and the stream's end."""

        def __init__(self):
            super().__init__()
            self.stamps = []
            self.error = None
            self.ended_at = None

        def _push(self, tok):
            self.stamps.append(time.perf_counter())
            super()._push(tok)

        def _finish(self, reason):
            self.ended_at = time.perf_counter()
            super()._finish(reason)

        def _fail(self, exc):
            self.error, self.ended_at = exc, time.perf_counter()
            super()._fail(exc)

    return TimedStream


def warm_up(pred):
    """Compile the shapes this traffic uses: every prefill bucket and the
    decode step, against the scratch page (as ``DecodePredictor.warmup``
    does, less its probe forward, which no request of this loop runs)."""
    import jax

    row = np.zeros((pred.max_pages,), np.int32)
    for b in pred.prefill_buckets:
        pred.prefill(np.zeros((b,), np.int32), row)
    z = np.zeros((pred.max_seqs,), np.int32)
    pred.step(z, z, z, np.zeros((pred.max_seqs, pred.max_pages), np.int32))
    jax.block_until_ready(pred._kv)


def _send(batcher, schedule, prompts, t0, stream_cls, streams, sent, run):
    for i, due in enumerate(schedule.due_s):
        wait = t0 + due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        stream = stream_cls()
        with run.annotate("bench.submit"):
            sent[i] = time.perf_counter()
            try:
                batcher.submit(prompts[i], int(schedule.output_len[i]),
                               stream=stream)
            except Exception as e:  # a refusal is a failed request
                stream.error, stream.ended_at = e, sent[i]
        streams[i] = stream


def check_logits(job, schedule, seed, steps, sample, max_len):
    """Largest |system - reference| logit over ``sample`` requests, at
    the prompt's last position and ``steps`` decoded positions after it;
    and the largest |reference| logit, for scale."""
    pred = job.predictor
    n = len(schedule)
    picks = np.random.default_rng([int(seed), 9]).choice(
        n, min(sample, n), replace=False)
    k = len(picks)
    table = np.zeros((pred.max_seqs, pred.max_pages), np.int32)
    toks = np.zeros((pred.max_seqs,), np.int32)
    pos = np.zeros((pred.max_seqs,), np.int32)
    active = np.zeros((pred.max_seqs,), np.int32)
    full = np.zeros((k, max_len), np.int32)
    where = np.zeros((k, steps + 1), np.int32)
    got = np.zeros((k, steps + 1, job.vocab_size), np.float32)
    held = []
    try:
        for j, r in enumerate(picks):
            prompt = schedule.prompts[r][:max_len - steps - 1]
            pages = pred.pool.alloc(
                -(-(len(prompt) + steps + 1) // pred.page_size))
            if pages is None:
                raise RuntimeError("page pool refused the check's pages")
            held.append(pages)
            table[j, :len(pages)] = pages
            first, logits = pred.prefill(prompt, table[j])
            got[j, 0] = np.asarray(logits).reshape(-1)
            full[j, :len(prompt)] = prompt
            toks[j], pos[j], active[j] = first, len(prompt), 1
            where[j] = len(prompt) - 1 + np.arange(steps + 1)
        for s in range(steps):
            full[np.arange(k), pos[:k]] = toks[:k]
            nxt, logits = pred.step(toks, pos, active, table)
            got[:, s + 1] = np.asarray(logits)[:k]
            toks[:k] = nxt[:k]
            pos[:k] += 1
    finally:
        for pages in held:
            pred.pool.free(pages)
    ref = np.asarray(job.reference_logits(full, where))
    return float(np.max(np.abs(got - ref))), float(np.max(np.abs(ref)))


def serve_window(job, run, traffic, seconds):
    """Offer ``traffic`` for ``seconds`` to a fresh ``DecodeBatcher`` over
    the job's predictor, cancel what is left, and return what the client
    saw inside the window. Also what ``benchmarks/sweep.py`` calls once
    per rate."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving.batcher import DecodeBatcher

    from benchmarks.harness.traffic import make_schedule

    pred = job.predictor
    schedule = make_schedule(traffic, run.seed, seconds, job.vocab_size)
    prompts = [p.tolist() for p in schedule.prompts]
    n = len(schedule)
    stream_cls = _stream_class()
    streams, sent = [None] * n, [0.0] * n
    traced_s = float(traffic["trace_seconds"]) if run.tracer else 0.0

    serving.reset_stats()
    # the engine's own TTFT limit only feeds a counter nothing here reads
    batcher = DecodeBatcher(pred, ttft_slo_ms=60000)
    t0 = run.open_window()
    sender = threading.Thread(
        target=_send, name="bench-sender", daemon=True,
        args=(batcher, schedule, prompts, t0, stream_cls, streams, sent,
              run))
    sender.start()
    if run.tracer:
        time.sleep(max(0.0, t0 + seconds - traced_s - time.perf_counter()))
        run.tracer.start()
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = run.close_window(time.perf_counter())
    if run.tracer:
        run.tracer.stop()
    engine = {k: v for k, v in serving.stats().items()
              if k.startswith("decode_")}
    run.peak_bytes_after_window()
    sender.join(timeout=30)
    batcher.close(drain=False, timeout=60)

    limit = float(traffic["answer_limit_s"])
    seen = {"schedule": schedule, "t0": t0, "t1": t1, "engine": engine,
            "ttft_s": [], "itl_s": [], "late_s": [], "failed": 0,
            "tokens": 0, "done_requests": 0, "unanswered_at_close": 0,
            "running_at_close": 0}
    for i, s in enumerate(streams):
        budget = int(schedule.output_len[i])
        due = t0 + schedule.due_s[i]
        stamps = [t for t in (s.stamps if s is not None else []) if t <= t1]
        seen["late_s"].append(sent[i] - due)
        seen["ttft_s"].append(stamps[0] - due if stamps else math.inf)
        seen["itl_s"].extend(b - a for a, b in zip(stamps, stamps[1:]))
        seen["tokens"] += len(stamps)
        seen["unanswered_at_close"] += not stamps
        ended = s is not None and s.ended_at is not None \
            and s.ended_at <= t1
        if ended and s.error is None and s.reason == "length" \
                and len(s.stamps) == budget:
            seen["done_requests"] += 1
        elif ended or s is None or len(s.stamps) > budget \
                or (not stamps and t1 - due > limit):
            seen["failed"] += 1     # an error, a refusal, another count
            #                         than asked, or left waiting
        else:
            seen["running_at_close"] += 1
    return seen


def run(run):
    from benchmarks.harness import stats

    traffic, config = run.traffic, run.config
    job = run.model.build_server(config, traffic, run.seed, run.devices,
                                 run.reference)
    pred = job.predictor
    warm_up(pred)
    seen = serve_window(job, run, traffic, run.seconds)
    schedule, n = seen["schedule"], len(seen["schedule"])
    ttft, itl, late = seen["ttft_s"], seen["itl_s"], seen["late_s"]
    window = seen["t1"] - seen["t0"]

    def ms(values, q):
        return stats.percentile(values, q) * 1e3

    gap = 1.0 / float(traffic["arrivals"]["rate_per_s"])
    answered = [t for t in ttft if t != math.inf]
    run.log(f"{n} requests offered in {run.seconds} s "
            f"({schedule.prompt_len.sum()} prompt tokens, "
            f"{schedule.output_len.sum()} output tokens asked); "
            f"{seen['done_requests']} completed, {seen['running_at_close']} "
            f"running and {seen['unanswered_at_close']} unanswered when the "
            f"window closed; {seen['failed']} failed; {seen['tokens']} "
            f"tokens delivered")
    run.log(f"first token ms: p25 {ms(ttft, 25):.2f} p50 {ms(ttft, 50):.2f} "
            f"p75 {ms(ttft, 75):.2f} p90 {ms(ttft, 90):.2f} mean of answered "
            f"{stats.mean(answered) * 1e3:.2f}; gap between tokens ms over "
            f"{len(itl)} gaps: p50 {ms(itl, 50):.3f} p90 {ms(itl, 90):.3f} "
            f"p99 {ms(itl, 99):.3f} mean {stats.mean(itl) * 1e3:.3f}; "
            f"generator late p99 {ms(late, 99):.3f} ms; engine {seen['engine']}")
    if stats.percentile(late, 99) > float(traffic["late_share_of_gap"]) * gap:
        run.log(f"WARNING: the generator ran late: p99 {ms(late, 99):.2f} ms "
                f"is over {float(traffic['late_share_of_gap']):.0%} of the "
                f"mean gap between requests ({gap * 1e3:.1f} ms); read "
                "first-token times with care")

    notes = []
    if seen["failed"]:
        notes.append(f"{seen['failed']} request(s) failed, ended with "
                     "another count than their budget, or were left "
                     "unanswered")
    if pred.pool.in_use:
        notes.append(f"{pred.pool.in_use} page(s) are not back in the pool")
    tol = config["serve"]["logits_tolerance"]
    err, scale = check_logits(job, schedule, run.seed,
                              int(traffic["check_steps"]),
                              int(traffic["check_requests"]),
                              config["n_positions"])
    run.log(f"paged prefill + decode vs float32 reference: max |logit diff| "
            f"{err:.6f} at |logit| <= {scale:.3f} (tolerance {tol['abs']})")
    if not err <= tol["abs"]:
        notes.append(f"logits differ from the float32 reference by {err} "
                     f"(tolerance {tol['abs']})")
    if pred.pool.in_use:
        notes.append("the logits check left pages allocated")

    run.facts.update(requests=n, ttft_s=ttft, itl_s=itl, late_s=late,
                     due_s=list(schedule.due_s), engine=seen["engine"],
                     max_seqs=pred.max_seqs,
                     out_tok_per_s=seen["tokens"] / window,
                     prompt_len=[int(v) for v in schedule.prompt_len],
                     logits_err=err, logits_scale=scale)
    return {"end_to_end": {"serve_itl_p50_ms": ms(itl, 50),
                           "serve_itl_p90_ms": ms(itl, 90)},
            "attempted": n, "failed": seen["failed"],
            "correct": not notes, "notes": notes}
