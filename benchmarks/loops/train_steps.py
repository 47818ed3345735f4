"""Training steps chained on device-resident batches.

The job (``benchmarks/traffic/<job>.json``) gives the batch and the
mesh. The method is ``bench.py:_throughput``'s (device-resident batch,
steps chained by the donated state, time ends on ``block_until_ready``
of the last loss), made to the benchmark's contract: the work is drawn
from ``--seed`` (a ring of ``RING`` batches made on the device), the
window lasts ``--seconds`` (the traffic file's ``ring`` gives another
number of batches than ``RING``), and a sync about once a second of steps
bounds how far the host runs ahead. The sync waits for the step BEFORE
the newest one, so one step is always queued behind the one that runs
and the sync itself leaves no bubble on the device.

Set-up: build, one step that compiles (its loss is the one checked
against the float32 reference), ``WARMUP_STEPS`` more, whose period
sets the steps between two syncs. A traced run measures ``--seconds``
less ``TRACE_SECONDS``, then runs ``TRACE_SECONDS`` more under the
device profiler; the rate comes from the first part, the device's
timeline from the second.

End-to-end: ``train_items_per_s_per_chip``. attempted / failed: steps,
and steps whose loss is not finite.
"""
from __future__ import annotations

import time

RING = 8                # seeded batches, reused in turn, unless the
                        # traffic file gives its own ``ring``
WARMUP_STEPS = 2
SYNC_EVERY_S = 1.0      # of steps, by the warm-up steps' period
TRACE_SECONDS = 3


def _steps(job, ring, run, seconds, group, losses, marks):
    """Chain steps for ``seconds``; returns the time the last one ended.
    ``marks`` gets (steps done, time) at every sync."""
    t0 = time.perf_counter()
    n0 = len(losses)
    while True:
        with run.annotate("bench.trainer_step"):
            losses.append(job.step(*ring[len(losses) % len(ring)]))
        n = len(losses) - n0
        if n % group == 0:
            with run.annotate("bench.sync"):
                losses[-2].block_until_ready()
            now = time.perf_counter()
            marks.append((n - 1, now))
            if now - t0 >= seconds:
                break
    with run.annotate("bench.sync"):
        losses[-1].block_until_ready()
    return time.perf_counter()


def _held(run, when):
    """Where the model has expert layers: the assignments each layer's
    held experts got in the newest step (the program's own counter),
    logged and returned, so that the run shows whether the routing held
    through the window (``TrainJob.routing_check``). None elsewhere."""
    read = getattr(run.model, "expert_tokens", None)
    counts = read() if read is not None else None
    if counts:
        run.log(f"assignments to the experts held, {when}: " + "; ".join(
            f"{int(c.sum())} (per expert {int(c.min())}-{int(c.max())})"
            for c, _ in counts))
    return counts


def ring_of(traffic):
    """Batches in the ring of a job."""
    return int(traffic.get("ring", RING))


def run(run):
    import jax
    import numpy as np

    from benchmarks.harness import stats

    traffic = run.traffic
    job = run.model.build_trainer(run.config, traffic, run.seed,
                                  run.devices, run.reference)
    ring = job.make_ring(run.seed, ring_of(traffic))
    job.prepare(ring, traffic, run.log)
    first = job.step(*ring[0])
    first.block_until_ready()
    held_first = _held(run, "first step")
    t_warm = time.perf_counter()
    for i in range(WARMUP_STEPS):
        job.step(*ring[(i + 1) % len(ring)]).block_until_ready()
    period = (time.perf_counter() - t_warm) / WARMUP_STEPS
    group = max(2, round(SYNC_EVERY_S / period))
    before = job.program_counters()
    traced_s = float(TRACE_SECONDS) if run.tracer else 0.0

    losses, marks = [], []
    t0 = run.open_window()
    t1 = _steps(job, ring, run, max(1.0, run.seconds - traced_s), group,
                losses, marks)
    steps = len(losses)
    run.close_window(t1)
    trace_steps = 0
    if run.tracer:
        run.tracer.start()
        _steps(job, ring, run, traced_s, group, losses, [])
        run.tracer.stop()
        trace_steps = len(losses) - steps
    run.peak_bytes_after_window()
    held_last = _held(run, f"step {WARMUP_STEPS + 1 + len(losses)}")

    values = np.asarray(jax.device_get(losses), np.float64)
    bad = int(np.sum(~np.isfinite(values)))
    rate = steps * job.items_per_step / (t1 - t0)
    periods = [(tb - ta) / (nb - na) for (na, ta), (nb, tb)
               in zip(marks, marks[1:])] or [period]
    run.log(f"{steps} steps in {t1 - t0:.3f} s, a sync every {group}; step "
            f"period between syncs min {min(periods) * 1e3:.2f} median "
            f"{stats.median(periods) * 1e3:.2f} max "
            f"{max(periods) * 1e3:.2f} ms over {len(marks) - 1} groups "
            f"(warm-up steps {period * 1e3:.2f}); loss "
            f"{values[0]:.4f} -> {values[-1]:.4f}")

    notes = job.path_faults(before)
    checked = job.check(float(first), *ring[0], run.seed)
    run.log(checked["said"])
    notes += checked["notes"]
    routed = job.routing_check(held_first, held_last)
    notes += routed["notes"]
    if bad:
        notes.append(f"{bad} of {len(values)} losses are not finite")

    run.facts.update(steps=steps, trace_steps=trace_steps,
                     items_per_step=job.items_per_step)
    return {"end_to_end": {"train_items_per_s_per_chip":
                           rate / len(run.devices)},
            "attempted": len(values), "failed": bad,
            "correct": not notes, "notes": notes,
            "reported": routed["reported"],
            "compared": {**checked["compared"], **routed["compared"]}}
