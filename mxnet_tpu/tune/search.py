"""Measured schedule search driver.

One :func:`run_search` call owns one (kernel, shape, dtype, backend)
table entry: it builds the reference schedule, generates the legal
candidate set from :data:`~mxnet_tpu.tune.schedule.SEARCH_SPACE`, runs
every candidate through the numerics gate (reject on disagreement with
the reference output — tuning can never change results), times the
survivors with the block-on-outputs / min-of-rounds discipline
(:mod:`~mxnet_tpu.tune.measure`), persists the winner into the target
schedule table, and emits ONE ``autotune`` flight-recorder event naming
the winning schedule and its measured margin.

A key already present in the *target* table is warm: the search is
skipped entirely (the ``--demo`` second-run-does-zero-searches
contract). Kernel builders read the *merged* committed+host view
(:func:`~mxnet_tpu.tune.schedule.load_table`); the warm check is
against the file being built so an operator can always re-tune into a
fresh table.

Workloads are plain objects with ``kernel/shape_key/dtype/backend``
identity and a ``build(schedule) -> (fn, args)`` factory — the flash
and INT8 workloads below cover the shipped kernels; tests inject
synthetic ones to drive the gate logic.
"""
from __future__ import annotations

import functools
import time

from . import _STATS, measure, schedule

__all__ = ["Workload", "run_search", "flash_fwd_workload",
           "flash_bwd_workload", "delta_rule_workload", "int8_fc_workload",
           "int8_conv_workload", "int8_requant_workload"]


class Workload:
    """One tunable (kernel, shape, dtype, backend) site.

    ``build(sched)`` returns ``(fn, args)`` where ``fn(*args)`` runs the
    kernel under the candidate schedule; the first build per schedule is
    also the warmup (compile) call. ``candidates()`` returns the
    schedule dicts to sweep — the reference (declared default, legalized
    for the shape) is always timed too and wins ties."""

    def __init__(self, kernel, shape_key, dtype, backend, build,
                 candidates, label=None, reference=None):
        self.kernel = kernel
        self.shape_key = shape_key
        self.dtype = dtype
        self.backend = backend
        self.build = build
        self._candidates = list(candidates)
        self._reference = reference
        self.label = label or kernel

    def candidates(self):
        return [dict(c) for c in self._candidates]

    def reference(self):
        """The declared default schedule (legalized for the shape when
        the workload provides one) — the numerics oracle and the margin
        baseline."""
        if self._reference is not None:
            return dict(self._reference)
        return dict(schedule.DEFAULT_SCHEDULES.get(self.kernel, {}))


def _dedup(scheds):
    seen, out = set(), []
    for s in scheds:
        key = tuple(sorted(s.items()))
        if key not in seen:
            seen.add(key)
            out.append(s)
    return out


def run_search(workload, table_path, rounds=3, iters=5, force=False):
    """Search one workload; returns a result dict (``skipped=True`` when
    the target table is already warm for the key)."""
    key = schedule.entry_key(workload.kernel, workload.shape_key,
                             workload.dtype, workload.backend)
    if not force and key in schedule.load_single_table(table_path):
        return {"key": key, "label": workload.label, "skipped": True}
    _STATS["autotune_searches"] += 1

    ref_sched = workload.reference()
    fn, args = workload.build(ref_sched)
    ref_out = measure.block_on(fn(*args))  # warmup = compile
    ref_ms = measure.time_min_ms(fn, args, rounds=rounds, iters=iters)
    measure.note_timed()
    best_sched, best_ms = ref_sched, ref_ms
    rejected, timed = 0, 1
    for cand in _dedup(workload.candidates()):
        if cand == ref_sched:
            continue
        try:
            fn, args = workload.build(cand)
            out = measure.block_on(fn(*args))
        except Exception:
            rejected += 1  # unbuildable candidate = rejected candidate
            measure.note_rejected()
            continue
        ok, err = measure.outputs_match(ref_out, out)
        if not ok:
            rejected += 1
            measure.note_rejected()
            continue
        ms = measure.time_min_ms(fn, args, rounds=rounds, iters=iters)
        measure.note_timed()
        timed += 1
        if ms < best_ms:
            best_sched, best_ms = cand, ms
    margin_pct = round((ref_ms - best_ms) / ref_ms * 100.0, 2) \
        if ref_ms > 0 else 0.0
    schedule.put_entry(
        table_path, workload.kernel, workload.shape_key, workload.dtype,
        workload.backend, best_sched,
        measured_ms=round(best_ms, 4), ref_ms=round(ref_ms, 4),
        margin_pct=margin_pct, candidates=timed, rejected=rejected,
        tuned_at=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()))
    try:
        from ..observability import flight

        flight.record("autotune", kernel=workload.kernel, key=key,
                      label=workload.label,
                      winner=dict(best_sched), margin_pct=margin_pct,
                      ref_ms=round(ref_ms, 4),
                      best_ms=round(best_ms, 4),
                      candidates=timed, rejected=rejected)
    except ImportError:  # standalone use without the package
        pass
    return {"key": key, "label": workload.label, "skipped": False,
            "winner": best_sched, "margin_pct": margin_pct,
            "ref_ms": ref_ms, "best_ms": best_ms,
            "candidates": timed, "rejected": rejected}


# --------------------------------------------------------- flash workloads

def _flash_qkv(b, h, t, d, seed, dtype="float32"):
    import numpy as np

    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(b, h, t, d).astype(np.float32) * 0.3,
                        dtype)
            for _ in range(3)]


def _flash_block_pairs(t, quick=False, min_block=None,
                       grain=schedule.MIN_SUBLANE):
    # a production sweep (min_block) also leaves out the one block of a
    # long T: a tile wider than the widest candidate only fails to build
    cap = None if min_block is None else schedule.FLASH_BLOCK_CANDIDATES[0]
    legal = schedule.legal_flash_blocks(t, cap=cap, grain=grain)
    if min_block is not None:
        legal = [b for b in legal if b >= min_block] or legal[:1]
    if quick:
        small = [b for b in legal if b in (128, 64)]
        legal = small if len(small) > 1 else legal[:2]
    return [{"block_q": bq, "block_k": bk} for bq in legal for bk in legal]


def flash_fwd_workload(b=2, h=1, t=256, d=32, causal=True, interpret=False,
                       seed=11, quick=False, k_offset=0, label=None,
                       dtype="float32", min_block=None):
    """Flash-attention forward sweep at one shape and dtype (the entry
    is keyed by both: the kernel feeds the MXU the input's dtype).
    ``min_block`` leaves the narrow tiles out of a production shape's
    sweep (an 8 x 8 tile at T = 1024 is two million grid steps a call).
    ``k_offset != 0`` shapes the ring-attention per-hop case (rotated
    K/V block placed later in the global sequence — same kernel,
    hop-shaped masking). ``interpret=True`` (tests, ``autotune.py
    --demo``) times the Pallas interpreter and keys the table under
    ``interpret``."""
    q, k, v = _flash_qkv(b, h, t, d, seed, dtype)

    def build(sched):
        import jax

        from ..ops.pallas_kernels import flash_attention

        fn = jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal, interpret=interpret,
            k_offset=k_offset, block_q=sched["block_q"],
            block_k=sched["block_k"]))
        return fn, (q, k, v)

    default = schedule.DEFAULT_SCHEDULES["flash_fwd"]
    ref = {"block_q": schedule.legalize_block(t, default["block_q"]),
           "block_k": schedule.legalize_block(t, default["block_k"])}
    return Workload(
        "flash_fwd", schedule.flash_shape_key(b * h, t, d), str(dtype),
        schedule.resolve_backend(interpret), build,
        _flash_block_pairs(t, quick=quick, min_block=min_block),
        label=label or "flash_fwd", reference=ref)


def flash_bwd_workload(b=2, h=1, t=256, d=32, causal=True, interpret=False,
                       seed=11, quick=False, label=None, dtype="float32",
                       min_block=None):
    """Flash-attention backward sweep at one shape and dtype: the
    backward kernel alone under the candidate tile, on what the forward
    kernel (under its own schedule) leaves for it and the cotangent of a
    sum of squares; keyed and cut down as :func:`flash_fwd_workload`
    is."""
    q, k, v = _flash_qkv(b, h, t, d, seed, dtype)

    def build(sched):
        import jax

        from ..ops import pallas_kernels as pk

        scale = d ** -0.5
        out, lse = pk._flash_fwd(q, k, v, causal, scale, interpret, 0, 0,
                                 None, None)
        fn = jax.jit(lambda *args: pk._flash_bwd(
            *args, None, scale, causal, interpret, 0, 0,
            block_k=sched["block_k"], block_q=sched["block_q"]))
        return fn, (pk._seq_minor(q), pk._seq_minor(k), pk._seq_minor(v),
                    out, lse, 2 * out)

    default = schedule.DEFAULT_SCHEDULES["flash_bwd"]
    ref = {axis: schedule.legalize_block(t, default[axis], schedule.LANES)
           or t for axis in ("block_q", "block_k")}
    return Workload(
        "flash_bwd", schedule.flash_shape_key(b * h, t, d), str(dtype),
        schedule.resolve_backend(interpret), build,
        _flash_block_pairs(t, quick=quick, min_block=min_block,
                           grain=schedule.LANES),
        label=label or "flash_bwd", reference=ref)


def delta_rule_workload(kernel="delta_rule_fwd", b=1, t=256, hk=1, hv=2,
                        dk=128, dv=128, chunk=64, interpret=False, seed=11,
                        quick=False, label=None, dtype="float32"):
    """Gated-delta-rule sweep at one shape and dtype: chunks a grid
    step of the forward kernel alone (``delta_rule_fwd``), or of the
    backward kernel (``delta_rule_bwd``) on what the forward under its
    own schedule saves and the cotangent of a sum of squares. Inputs as
    the mixer hands them over: SiLU outputs, decays of a few tenths to a
    few units a token, write strengths in (0, 1)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(jax.nn.silu(rs.randn(b, t, h, d).astype(
        np.float32)), dtype) for h, d in ((hk, dk), (hk, dk), (hv, dv)))
    g = jnp.asarray(-rs.uniform(0.0, 4.0, (b, t, hv)), jnp.float32)
    beta = jnp.asarray(rs.uniform(0.0, 1.0, (b, t, hv)), jnp.float32)
    back = kernel == "delta_rule_bwd"

    def build(sched):
        from ..ops.delta_rule_kernels import gated_delta_rule_kernels

        def run(*args, **chunks):
            return gated_delta_rule_kernels(*args, chunk=chunk,
                                            interpret=interpret, **chunks)

        if not back:
            return jax.jit(functools.partial(
                run, chunks=sched["chunks"])), (q, k, v, g, beta)
        out, vjp = jax.vjp(functools.partial(
            run, bwd_chunks=sched["chunks"]), q, k, v, g, beta)
        return jax.jit(vjp), (2 * out,)

    n = -(-t // chunk)
    space = sorted({schedule.delta_rule_chunks(
        kernel, b * hv, t, n, dk, dv, dtype, chunks=c)
        for c in schedule.SEARCH_SPACE[kernel]["chunks"]}, reverse=True)
    if quick:
        space = space[:2]
    ref = schedule.delta_rule_chunks(
        kernel, b * hv, t, n, dk, dv, dtype,
        chunks=schedule.DEFAULT_SCHEDULES[kernel]["chunks"])
    return Workload(
        kernel, schedule.delta_rule_shape_key(b * hv, t, dk, dv),
        str(dtype), schedule.resolve_backend(interpret), build,
        [{"chunks": c} for c in space], label=label or kernel,
        reference={"chunks": ref})


def decode_attn_workload(b=4, pages=8, page_size=16, h=2, d=32, seed=9,
                         quick=False, label=None, interpret=False):
    """Paged decode attention sweep at one (batch, pages) shape — the
    block_pages width of the streaming-softmax gather loop
    (ops/decode_attention.py). Every width in [1, pages] is legal (the
    resolver snaps to the largest dividing width), so candidates are
    the declared space clipped to the table width."""
    import numpy as np

    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(b, h, d).astype(np.float32) * 0.3)
    k_pages, v_pages = [
        jnp.asarray(rs.randn(pages + 1, page_size, h, d)
                    .astype(np.float32) * 0.3) for _ in range(2)]
    table = jnp.asarray(
        rs.permutation(pages)[None].repeat(b, 0) + 1, jnp.int32)
    lengths = jnp.asarray(
        rs.randint(page_size, pages * page_size + 1, b), jnp.int32)

    def build(sched):
        import jax

        from ..ops.decode_attention import paged_decode_attention

        fn = jax.jit(lambda q, kp, vp, tbl, ln: paged_decode_attention(
            q, kp, vp, tbl, ln, block_pages=sched["block_pages"],
            interpret=interpret))
        return fn, (q, k_pages, v_pages, table, lengths)

    space = [bp for bp in
             schedule.SEARCH_SPACE["decode_attn"]["block_pages"]
             if bp <= pages]
    if quick:
        space = space[:3] or [1]
    default = schedule.DEFAULT_SCHEDULES["decode_attn"]["block_pages"]
    ref_bp = schedule.decode_attn_block_pages(
        b, pages, "float32", interpret=interpret, block_pages=default)
    return Workload(
        "decode_attn", schedule.decode_shape_key(b, pages), "float32",
        schedule.resolve_backend(interpret), build,
        [{"block_pages": bp} for bp in space],
        label=label or "decode_attn",
        reference={"block_pages": ref_bp})


# ---------------------------------------------------------- int8 workloads

def int8_fc_workload(m=8, k=64, n=32, seed=5, label=None):
    import numpy as np

    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randint(-127, 128, (m, k)).astype(np.int8))
    w = jnp.asarray(rs.randint(-127, 128, (n, k)).astype(np.int8))

    def build(sched):
        import jax

        from ..ops.quantization import _s8_matmul

        fn = jax.jit(lambda x, w: _s8_matmul(
            x, w, operand_width=sched["operand_width"]))
        return fn, (x, w)

    return Workload(
        "int8_fc", schedule.int8_fc_shape_key(m, k, n), "int8",
        schedule.resolve_backend(False), build,
        [{"operand_width": w} for w in
         schedule.SEARCH_SPACE["int8_fc"]["operand_width"]],
        label=label or "int8_fc")


def int8_conv_workload(n=2, c=8, hw=8, o=16, seed=5, label=None):
    import numpy as np

    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randint(-127, 128, (n, c, hw, hw)).astype(np.int8))
    w = jnp.asarray(rs.randint(-127, 128, (o, c, 3, 3)).astype(np.int8))

    def build(sched):
        import jax

        from ..ops.quantization import _s8_conv

        dn = jax.lax.conv_dimension_numbers(
            x.shape, w.shape, ("NCHW", "OIHW", "NCHW"))
        fn = jax.jit(lambda x, w: _s8_conv(
            x, w, (1, 1), ((1, 1), (1, 1)), (1, 1), dn, 1,
            operand_width=sched["operand_width"]))
        return fn, (x, w)

    return Workload(
        "int8_conv",
        schedule.int8_conv_shape_key(x.shape, w.shape, (1, 1)), "int8",
        schedule.resolve_backend(False), build,
        [{"operand_width": w} for w in
         schedule.SEARCH_SPACE["int8_conv"]["operand_width"]],
        label=label or "int8_conv")


def int8_requant_workload(rows=8, cols=32, seed=5, label=None):
    import numpy as np

    import jax.numpy as jnp

    rs = np.random.RandomState(seed)
    data = jnp.asarray(
        rs.randint(-2 ** 28, 2 ** 28, (rows, cols)).astype(np.int32))
    real_in = jnp.asarray(6.0, jnp.float32)
    out_min = jnp.asarray(-0.9, jnp.float32)
    out_max = jnp.asarray(0.9, jnp.float32)

    def build(sched):
        import jax

        from ..ops.quantization import _requant_epilogue

        fn = jax.jit(lambda d: _requant_epilogue(
            d, real_in, out_min, out_max, path=sched["path"]))
        return fn, (data,)

    return Workload(
        "int8_requant", schedule.int8_requant_shape_key(rows, cols),
        "int8",
        schedule.resolve_backend(False), build,
        [{"path": p} for p in
         schedule.SEARCH_SPACE["int8_requant"]["path"]],
        label=label or "int8_requant")
