"""Pallas TPU kernels for hot ops.

The custom-kernel layer the blueprint reserves for "where fusion matters"
(SURVEY.md §7): hand-placed VMEM tiling for operations whose fused form
XLA cannot synthesize. First resident: streaming flash attention,
forward and backward. In the forward K/V arrive in VMEM one
(HEADS, BLOCK_K, D) tile per grid step, running (m, l, acc)
online-softmax statistics live in VMEM scratch that persists across the
innermost grid dimension, and the O(T^2) score matrix never exists
anywhere, in either pass. Sequence length is bounded by HBM, not VMEM.

The forward's tile program (docs/autotune.md, "The flash forward's tile
program"): the tile is a schedule (candidate axes up to 1024, block_q
and block_k independent; default 512 x 512 legalized to T, so it
depends on (T, D, dtype) and not on a model), and a grid step takes as
many heads, unrolled, as fill it up to one 512 x 512 tile's worth and
fit VMEM (``tune.schedule.flash_fwd_heads``). Both matmuls take their operands in
the input's dtype and accumulate in float32 (bf16 inputs: bf16
operands, the probabilities cast to the value dtype for P.V; float32
inputs: float32 operands); the scale is applied to the float32 scores;
max, sum, exp, correction and accumulator are float32. Under ``causal``
a K block in the q block's future does no arithmetic and no DMA, and
only a tile that crosses the diagonal is masked. Inside the kernel the
row statistics are (rows, 128) with every lane holding the row's value;
the log-sum-exp leaves the kernel with the sequence along lanes,
(BH, T / BLOCK_Q, 1, BLOCK_Q), and ``flash_attention(return_lse=True)``
hands its callers (B, H, T, 1).

The backward's tile program (docs/autotune.md, "The flash backward's
tile program"): one kernel, ``flash_attention_bwd``, gives dq, dk and dv. Its
grid is (heads, q windows, K blocks, q blocks), the q blocks innermost:
dk / dv of a K block gather in float32 VMEM scratch while the q blocks
pass, dq of a whole window of the sequence gathers in float32 scratch
while the K blocks pass, so a (q block, K block) pair costs five matrix
products and one ``exp`` and nothing of size T x block ever passes
through HBM (q, k, v, dout, ``lse`` and ``D = rowsum(dout * out) -
dlse`` are read, dq, dk, dv written). Every operand has the SEQUENCE
ALONG LANES, (BH, D, T), and the tile is computed transposed, s^T =
K.Q^T (BLOCK_K, BLOCK_Q): ``lse`` and ``D`` arrive as the lane-dense
rows the forward emits and spread over sublanes, and the three products
that take the tile (dv^T = dout^T.p, dk^T = q^T.ds, dq^T = k^T.ds^T)
take it as it lies. That layout pads nothing: (T, D) operands with D
short of the 128 lanes are padded to them in HBM (twice the bytes at
D = 64), and XLA holds what the backward kernel reads from the end of
the forward pass on, so q, k and v wait transposed between the passes
(``_seq_minor``). Operands as in the forward: the input's dtype into
every product (p^T and ds^T cast to it), float32 scores, ``exp``, ``D``,
ds and accumulators. A tile wholly above the causal diagonal does no
arithmetic and no DMA (clamped index maps by the scalar-prefetched
offsets, so a ring hop's traced offsets work); a tile that crosses it
is masked after the ``exp``, to zero. The tile is the ``flash_bwd``
schedule's (block_q, block_k), or the caller's ``bwd_block_k``,
legalized to T as the forward's is but
on the lane grid (a multiple of 128, or all of T; a long sequence off
that grid runs padded to it with zeros, ``tune.schedule.
flash_bwd_length``); heads a step and the number of q windows (one
while dq of the whole sequence fits VMEM: T x D to about four million)
follow from the tile and the shape (``flash_bwd_heads``,
``flash_bwd_windows``). Every shape the forward takes, the backward
takes: there is no other backward.

Every ``pl.pallas_call`` carries a ``name=`` (``flash_attention_fwd``,
``flash_attention_bwd``): it becomes the
instruction's name and the last scope of its ``op_name`` in the
compiled program, which is how a device
trace and ``observability.perf.op_names`` find the kernel. A kernel added
here is named the same way (``tests/test_kernel_names_tpu.py`` holds
every call site in the package to it).

Kernels compile for the TPU or raise: no entry point here substitutes
another implementation or flips to interpret mode on its own. Tests
drive the same kernels in Pallas interpret mode on CPU by passing
``interpret=True``, so numerics are CI-checked everywhere.
"""
from __future__ import annotations

import functools

import numpy as _np

from ..remat import kernel_residuals

__all__ = ["flash_attention", "flash_attention_with_grad",
           "flash_attention_with_lse", "pallas_available"]

# Block sizes are SCHEDULES, not constants: they resolve per
# (kernel, shape, dtype, backend) through mxnet_tpu/tune/schedule.py —
# explicit override > measured schedule table > legalized default
# (graftlint TS004 keeps hardcoded blocks out of kernel files).
_NEG = -1e30


def _schedule():
    from ..tune import schedule

    return schedule


def pallas_available():
    """Whether compiled (non-interpret) Pallas kernels can run here: jax's
    default backend is the TPU. What ``impl='auto'`` selects on."""
    import jax

    return jax.default_backend() == "tpu"


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic widened (or narrowed) to
    ``n`` lanes: whole lane tiles repeat the vregs that are there, so the
    subtraction from a (rows, n) score tile needs no lane broadcast."""
    import jax.numpy as jnp

    lanes = x.shape[-1]
    if n % lanes == 0:
        return jnp.tile(x, (1, n // lanes))
    if n < lanes:
        return x[:, :n]
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _note_build(name, bh, t, d, bq, bk, causal, window):
    """One ``kernel.build`` span (no time in it, and nothing at all
    while span tracing is off) for each kernel built: its shape, its
    tile and, under ``causal``, how many tiles its grid visits of the
    tiles at or below the diagonal (by the schedule, T and the window,
    offsets at zero). The builders are cached, so a kernel is noted once
    however many layers call it."""
    import time

    from ..observability import trace

    if not trace.enabled():
        return
    tiles = {}
    if causal:
        visited, below = _schedule().flash_tiles(t, bq, bk, window)
        tiles = {"tiles_visited": visited, "tiles_causal": below}
    trace.record("kernel.build", time.perf_counter_ns(), 0, kernel=name,
                 bh=bh, t=t, d=d, block_q=bq, block_k=bk, window=window,
                 **tiles)


def _window_of(window, causal, t, q_offset=0, k_offset=0):
    """The window a kernel is built with: None for none, and for one
    that shuts no key out (``window >= T`` with both blocks at the
    sequence's start), which is the causal kernel."""
    if window is None:
        return None
    window = int(window)
    if window < 1:
        raise ValueError(f"flash_attention: window {window} < 1")
    if not causal:
        raise ValueError(
            "flash_attention: a window is 0 <= i - j < window, so it "
            "needs causal=True")
    return None if window >= t and _at_start(q_offset, k_offset) \
        else window


def _at_start(q_offset, k_offset):
    """Both blocks sit at the sequence's start, and not by a traced
    value: the grids of a window count their steps exactly then."""
    return all(isinstance(o, int) and o == 0 for o in (q_offset, k_offset))


def _first_k_block(q_first, k_offset, bk, window):
    """The first K block that holds a key some query at or after
    ``q_first`` sees under ``window`` (not below 0). The forward's index
    map and its kernel both count a q block's K steps from it."""
    import jax.numpy as jnp

    return jnp.maximum((q_first - (window - 1) - k_offset) // bk, 0)


def _mha_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, causal, n_kb, window=None,
                n_steps=None):
    """Grid = (BH / HB, n_q_blocks, n_k_blocks); the k dimension is
    innermost, so the VMEM scratch (m, l, acc) carries across K blocks of
    one (heads, q-block) pair and the outputs write on the last step.

    Under a ``window`` (a query sees the keys ``0 <= i - j < window``)
    the innermost dimension has ``n_steps`` steps, as many as the K
    blocks a q block's band of keys can touch, and counts them from the
    band's first block (:func:`_first_k_block`): blocks behind the
    window are not grid steps at all.

    qoff_ref/koff_ref: scalar-prefetch global position offsets — ring
    attention runs the kernel on (local Q, rotated K/V) block pairs whose
    causal relation is decided by where each block sits in the GLOBAL
    sequence, and the offsets are traced values (lax.axis_index), so they
    arrive in SMEM rather than being baked into the compiled kernel.

    q_ref, o_ref (HB, BQ, D) / k_ref, v_ref (HB, BK, D) in the caller's
    dtype: both matmuls take their operands as they arrive and
    accumulate in float32. m_ref, l_ref (HB, BQ, 128) float32, every
    lane of a row holding the row's statistic; acc_ref (HB, BQ, D)
    float32; lse_ref (HB, 1, 1, BQ) float32, the sequence along lanes.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    step = pl.program_id(2)
    qi = pl.program_id(1)
    hb, bq, d = q_ref.shape
    bk = k_ref.shape[1]

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if window is None:
        kb, n_steps = step, n_kb
    else:
        kb = _first_k_block(qoff_ref[0] + qi * bq, koff_ref[0], bk,
                            window) + step

    def _tile(masked):
        if masked:
            # qpos >= kpos, as row - col >= (first kpos) - (first qpos)
            rel = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) - \
                jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            ahead = (koff_ref[0] + kb * bk) - (qoff_ref[0] + qi * bq)
            keep = rel >= ahead
            if window is not None:
                keep &= rel < ahead + window
        # unrolled on purpose: what several heads a step gain is the
        # compiler interleaving their code (rolled into a fori_loop,
        # eight heads of 128 x 128 ran 2.5 x slower; PERF.md, PR 28)
        for h in range(hb):
            s = jax.lax.dot_general(
                q_ref[h], k_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(keep, s, _NEG)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, bk))
            corr = jnp.exp(m_prev - m_new)
            m_ref[h] = m_new
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            v_blk = v_ref[h]
            acc_ref[h] = acc_ref[h] * _lanes(corr, d) + jax.lax.dot_general(
                p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        # K blocks strictly in this q block's future are all-masked: no
        # arithmetic here, and no DMA either (the index maps of
        # _build_flash name the last live block again). Only a tile that
        # crosses the diagonal pays for the mask.
        q_first = qoff_ref[0] + qi * bq
        k_first = koff_ref[0] + kb * bk
        live = k_first <= q_first + bq - 1
        crosses = k_first + bk - 1 > q_first
        if window is not None:
            # a step past the last K block, or (a hop far in the past) a
            # block wholly behind the window, is dead as one in the
            # future is; a tile the window's edge runs through is masked
            live &= (kb < n_kb) & (k_first + bk - 1 > q_first - window)
            crosses |= k_first < q_first + bq - window
        pl.when(live & crosses)(lambda: _tile(True))
        pl.when(live & jnp.logical_not(crosses))(lambda: _tile(False))
    else:
        _tile(False)

    @pl.when(step == n_steps - 1)
    def _finish():
        for h in range(hb):
            l_fin = jnp.maximum(l_ref[h], 1e-20)
            o_ref[h] = (acc_ref[h] / _lanes(l_fin, d)).astype(o_ref.dtype)
            # row log-sum-exp, already held in scratch — emit it so the
            # custom_vjp backward doesn't need a recomputation sweep;
            # transposed, so that the sequence runs along lanes
            lse = m_ref[h] + jnp.log(l_fin)
            lse_ref[h, 0] = _row_of(lse)


def _row_of(x):
    """(rows, 128) lane-replicated statistic -> (1, rows): the rows laid
    along lanes. A one-hot row contracted against the statistic's lanes
    (the same A.B^T form as Q.K^T, so it lowers wherever the kernel
    does, aligned to the lane tile or not); at HIGHEST precision a
    float32 times 1.0 summed with zeros is the float32 itself."""
    import jax
    import jax.numpy as jnp

    pick = (jax.lax.broadcasted_iota(
        jnp.int32, (_schedule().MIN_SUBLANE, x.shape[-1]), 1) == 0
    ).astype(jnp.float32)
    rows = jax.lax.dot_general(
        pick, x, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    return rows[:1]


@functools.lru_cache(maxsize=32)
def _build_flash(bh, t, d, dtype_str, scale, causal, interpret, bq, bk, hb,
                 window=None, at_start=False):
    """One pallas_call per (shape, dtype, config, SCHEDULE): bq/bk/hb are
    part of the cache key, so a schedule-table change re-builds instead
    of serving the old tiling."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sched = _schedule()
    n_kb = n_steps = t // bk
    kernel = functools.partial(_mha_kernel, scale=scale, causal=causal,
                               n_kb=n_kb)
    if window is not None:
        n_steps = sched.flash_window_steps(t, bq, bk, window, at_start)
        kernel = functools.partial(kernel, window=window, n_steps=n_steps)
    _note_build("flash_attention_fwd", bh, t, d, bq, bk, causal, window)

    def q_map(b, i, kb, *_):
        return (b, i, 0)

    def kv_map(b, i, kb, qoff_ref, koff_ref):
        if causal:
            # a step in the q block's future names the last live block
            # again: the block is resident, so no DMA is issued for it.
            # A hop wholly in the future has no live block; it names
            # block 0 throughout and computes nothing.
            last = (qoff_ref[0] + (i + 1) * bq - 1 - koff_ref[0]) // bk
            if window is not None:
                kb += _first_k_block(qoff_ref[0] + i * bq, koff_ref[0], bk,
                                     window)
            kb = jnp.minimum(kb, jnp.clip(last, 0, n_kb - 1))
        return (b, kb, 0)

    lanes = sched.LANES
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # q_offset, k_offset (SMEM)
        grid=(bh // hb, t // bq, n_steps),
        in_specs=[
            pl.BlockSpec((hb, bq, d), q_map),
            pl.BlockSpec((hb, bk, d), kv_map),
            pl.BlockSpec((hb, bk, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((hb, bq, d), q_map),
            pl.BlockSpec((hb, 1, 1, bq), lambda b, i, kb, *_: (b, i, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, bq, lanes), jnp.float32),   # running max m
            pltpu.VMEM((hb, bq, lanes), jnp.float32),   # running sum l
            pltpu.VMEM((hb, bq, d), jnp.float32),       # output accumulator
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), jnp.dtype(dtype_str)),
            jax.ShapeDtypeStruct((bh, t // bq, 1, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=sched.flash_fwd_vmem_limit(
                hb, bq, bk, d, jnp.dtype(dtype_str).itemsize)),
        interpret=interpret,
        name="flash_attention_fwd",
    )


def _flash_fwd(q, k, v, causal, scale, interpret, q_offset, k_offset,
               block_q, block_k, window=None):
    """The forward kernel on jax arrays: (out (B, H, T, D), the row
    log-sum-exp as the kernel lays it, (BH, T / BLOCK_Q, 1, BLOCK_Q)
    float32: row-major the flat sequence, which is how the backward
    kernel takes it back)."""
    import jax.numpy as jnp

    b, h, t, d = q.shape
    if k.shape != q.shape or v.shape != q.shape:
        raise ValueError(
            f"flash_attention: unsupported shape — q {q.shape} vs k "
            f"{k.shape} / v {v.shape} (self-attention only)")
    sched = _schedule()
    window = _window_of(window, causal, t, q_offset, k_offset)
    bq, bk = sched.flash_fwd_blocks(
        b * h, t, d, str(q.dtype), interpret=bool(interpret),
        block_q=block_q, block_k=block_k, window=window)
    hb = sched.flash_fwd_heads(b * h, bq, bk, d, q.dtype.itemsize)
    fn = _build_flash(b * h, t, d, str(q.dtype), float(scale), bool(causal),
                      bool(interpret), bq, bk, hb, window,
                      _at_start(q_offset, k_offset))
    out, lse = fn(jnp.asarray(q_offset, jnp.int32).reshape(1),
                  jnp.asarray(k_offset, jnp.int32).reshape(1),
                  q.reshape(b * h, t, d), k.reshape(b * h, t, d),
                  v.reshape(b * h, t, d))
    return out.reshape(b, h, t, d), lse


def flash_attention(q, k, v, causal=False, scale=None, interpret=False,
                    return_lse=False, q_offset=0, k_offset=0,
                    block_q=None, block_k=None, window=None):
    """Fused attention forward: q/k/v (B, H, T, D) -> (B, H, T, D)
    (plus the per-row log-sum-exp when return_lse=True).

    ``window`` (with ``causal``): a query sees the keys
    ``0 <= i - j < window``, itself counted. K blocks wholly behind the
    window are no grid steps (no arithmetic, no DMA), the tile its edge
    runs through is masked; ``window >= T`` is the causal kernel.

    q_offset/k_offset (int or traced scalar) place the Q and K/V blocks in
    a larger global sequence for causal masking — the ring-attention hop
    case, where K/V blocks rotate past stationary local queries.

    Block sizes resolve through the schedule registry
    (mxnet_tpu/tune/schedule.py, docs/autotune.md): explicit
    block_q/block_k override (must divide T — the search driver's path),
    else the measured schedule table, else the legalized default.
    Requirements: a legal block exists (T itself, or a multiple-of-8
    divisor of T up to the scheduled block), D <= 256, self-attention
    shapes. Raises ValueError otherwise.

    Accepts NDArrays or jax arrays, computed where they live: inputs on a
    CPU device need ``interpret=True`` (a program compiled for a CPU
    device cannot lower the kernel, and says so).
    """
    if hasattr(q, "_data"):
        from ..ndarray.ndarray import NDArray

        ctx = getattr(q, "_ctx", None)
        out = flash_attention(q._data, k._data, v._data, causal=causal,
                              scale=scale,
                              interpret=interpret, return_lse=return_lse,
                              q_offset=q_offset, k_offset=k_offset,
                              block_q=block_q, block_k=block_k,
                              window=window)
        if return_lse:
            return NDArray(out[0], ctx), NDArray(out[1], ctx)
        return NDArray(out, ctx)
    s = scale if scale is not None else 1.0 / _np.sqrt(q.shape[-1])
    out, lse = _flash_fwd(q, k, v, causal, s, interpret, q_offset, k_offset,
                          block_q, block_k, window)
    if return_lse:
        return out, lse.reshape(q.shape[:3] + (1,))
    return out


# ---------------------------------------------------------------------------
# the backward kernel: probabilities recomputed tile by tile from the
# forward's saved log-sum-exp; the score matrix exists in neither
# direction, and nothing of size T x block passes through HBM
# ---------------------------------------------------------------------------

def _first_q_block(k_first, q_offset, bq, lo):
    """The first q block, not before block ``lo``, that holds a query
    which sees a key at or after ``k_first``. Under a window the
    backward's index maps and its kernel both count a K block's q steps
    from it."""
    import jax.numpy as jnp

    return jnp.maximum((k_first - q_offset) // bq, lo)


def _mha_bwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, dd_ref, dq_ref, dk_ref, dv_ref,
                    dq_acc, dk_acc, dv_acc, *, scale, causal, n_kb, n_qw,
                    window=None, n_steps=None):
    """Grid = (BH / HB, q windows, n_k_blocks, q blocks a window), the q
    blocks innermost: dk / dv of one K block gather over them in VMEM
    scratch and leave on the last; dq of the whole window gathers in
    scratch over the K blocks and leaves on the last of those. One tile
    pair is five matrix products and one ``exp``.

    Under an attention ``window`` (``0 <= i - j < window``; not the q
    windows above, which cut the sequence for VMEM) the innermost
    dimension has ``n_steps`` steps, as many as the q blocks whose
    queries can see a K block's keys, counted from the first of them
    (:func:`_first_q_block`): q blocks past the window are not grid
    steps at all. dq is then zeroed whole on a q window's first step and
    written whole on its last, since no K block visits every q block.

    Every operand has the SEQUENCE ALONG LANES, (D, block), and the tile
    is computed transposed, s^T = K.Q^T (BK, BQ): ``lse`` and
    ``D = rowsum(dout * out) - dlse`` arrive as lane-dense rows (1, BQ),
    the layout the forward emits, and spread over sublanes; the two
    products that make the tile contract the short (D, BK) blocks of K
    and V over their rows, and dv^T = dout^T.p, dk^T = q^T.ds and
    dq^T = k^T.ds^T take the (BK, BQ) tile as it lies.

    q_ref, do_ref (HB, D, BQ) / k_ref, v_ref (HB, D, BK) in the caller's
    dtype: every product takes its operands so (p^T and ds^T cast to
    it) and accumulates in float32. lse_ref, dd_ref (HB, 1, 1, BQ)
    float32. dq_ref / dq_acc (HB, window's q blocks, D, BQ); dk_ref,
    dv_ref (1, HB, D, BK) of one window's part; dk_acc, dv_acc
    (HB, D, BK) float32. Offsets as in the forward.
    """
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    w, kb, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    hb, d, bq = q_ref.shape
    bk = k_ref.shape[2]

    @pl.when(step == 0)
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if window is None:
        qi, n_steps = step, n_qw

        @pl.when(kb == 0)
        def _init_dq():
            for h in range(hb):
                dq_acc[h, qi] = jnp.zeros((d, bq), jnp.float32)
    else:
        # the q block of this step, counted inside the q window
        qi = _first_q_block(koff_ref[0] + kb * bk, qoff_ref[0], bq,
                            w * n_qw) - w * n_qw + step

        @pl.when((kb == 0) & (step == 0))
        def _init_dq():
            dq_acc[...] = jnp.zeros_like(dq_acc)

    q_first = qoff_ref[0] + (w * n_qw + qi) * bq
    k_first = koff_ref[0] + kb * bk

    def _tile(masked):
        if masked:
            # qpos >= kpos, as col - row >= (first kpos) - (first qpos)
            rel = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) - \
                jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            keep = rel >= k_first - q_first
            if window is not None:
                keep &= rel < k_first - q_first + window
        rows = (((0,), (0,)), ((), ()))     # (D, BK) x (D, BQ) -> (BK, BQ)
        lanes = (((1,), (1,)), ((), ()))    # (D, BQ) x (BK, BQ) -> (D, BK)
        # unrolled, as the forward's heads are (PERF.md, PR 28)
        for h in range(hb):
            k_blk, v_blk, q_blk, do_blk = k_ref[h], v_ref[h], q_ref[h], \
                do_ref[h]
            s_t = jax.lax.dot_general(
                k_blk, q_blk, rows,
                preferred_element_type=jnp.float32) * scale
            p_t = jnp.exp(s_t - lse_ref[h, 0])
            if masked:
                # after the exp: a masked entry is 0 whatever the row's
                # lse is (a row that sees no key at all has lse ~ -1e30)
                p_t = jnp.where(keep, p_t, 0.0)
            dp_t = jax.lax.dot_general(
                v_blk, do_blk, rows, preferred_element_type=jnp.float32)
            ds_t = (p_t * (dp_t - dd_ref[h, 0])).astype(q_blk.dtype)
            dv_acc[h] += jax.lax.dot_general(
                do_blk, p_t.astype(do_blk.dtype), lanes,
                preferred_element_type=jnp.float32)
            dk_acc[h] += jax.lax.dot_general(
                q_blk, ds_t, lanes, preferred_element_type=jnp.float32)
            dq_acc[h, qi] += jax.lax.dot_general(
                k_blk, ds_t, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        # as in the forward: a tile wholly above the diagonal does no
        # arithmetic and no DMA (the index maps of _build_flash_bwd name
        # a resident block again); only one that crosses it is masked
        live = k_first <= q_first + bq - 1
        crosses = k_first + bk - 1 > q_first
        if window is not None:
            # a step past the q window's last block, or a q block wholly
            # past the attention window, is dead as one before the K
            # block is; a tile the window's edge runs through is masked
            live &= (qi < n_qw) & (k_first + bk - 1 > q_first - window)
            crosses |= k_first < q_first + bq - window
        pl.when(live & crosses)(lambda: _tile(True))
        pl.when(live & jnp.logical_not(crosses))(lambda: _tile(False))
    else:
        _tile(False)

    @pl.when(step == n_steps - 1)
    def _finish_dkv():
        for h in range(hb):
            dk_ref[0, h] = (dk_acc[h] * scale).astype(dk_ref.dtype)
            dv_ref[0, h] = dv_acc[h].astype(dv_ref.dtype)

    if window is None:
        @pl.when(kb == n_kb - 1)
        def _finish_dq():
            for h in range(hb):
                dq_ref[h, qi] = (dq_acc[h, qi] * scale).astype(dq_ref.dtype)
    else:
        @pl.when((kb == n_kb - 1) & (step == n_steps - 1))
        def _finish_dq():
            for h in range(hb):
                for g in range(n_qw):
                    dq_ref[h, g] = (dq_acc[h, g] * scale).astype(
                        dq_ref.dtype)


@functools.lru_cache(maxsize=32)
def _build_flash_bwd(bh, t, d, dtype_str, scale, causal, interpret, bq, bk,
                     hb, n_win, window=None, at_start=False):
    """The backward's pallas_call for one (shape, dtype, config,
    SCHEDULE), on (BH, D, T) operands. ``n_win`` q windows share the K
    blocks: each window's dk / dv part comes out on its own (float32
    when there are several, for the caller to add up); dq comes out a q
    block at a time, (BH, T / BLOCK_Q, D, BLOCK_Q)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sched = _schedule()
    n_kb, n_qb = t // bk, t // bq
    n_qw = n_steps = n_qb // n_win
    dtype = jnp.dtype(dtype_str)
    part = dtype if n_win == 1 else jnp.dtype(jnp.float32)
    kernel = functools.partial(_mha_bwd_kernel, scale=scale, causal=causal,
                               n_kb=n_kb, n_qw=n_qw)
    if window is not None:
        n_steps = min(n_qw, sched.flash_window_steps(
            t, bq, bk, window, at_start, backward=True))
        kernel = functools.partial(kernel, window=window, n_steps=n_steps)
    _note_build("flash_attention_bwd", bh, t, d, bq, bk, causal, window)

    def q_block(w, kb, qi, qoff_ref, koff_ref):
        if window is not None:
            # the K block's live q blocks in turn, then the last of them
            # again (resident: a dead step issues no DMA)
            k_first = koff_ref[0] + kb * bk
            last = (k_first + bk - 1 + window - 1 - qoff_ref[0]) // bq
            g = jnp.minimum(_first_q_block(k_first, qoff_ref[0], bq,
                                           w * n_qw) + qi, last)
            return jnp.clip(g, w * n_qw, (w + 1) * n_qw - 1)
        g = w * n_qw + qi
        if causal:
            # a q block in the K block's past names the first live one
            # (or the window's last, where none is): resident when the
            # sweep reaches it, so a dead step issues no DMA
            first = (koff_ref[0] + kb * bk - qoff_ref[0]) // bq
            g = jnp.minimum(jnp.maximum(g, first), (w + 1) * n_qw - 1)
        return g

    def q_map(b, w, kb, qi, qoff_ref, koff_ref):
        return (b, 0, q_block(w, kb, qi, qoff_ref, koff_ref))

    def row_map(b, w, kb, qi, qoff_ref, koff_ref):
        return (b, q_block(w, kb, qi, qoff_ref, koff_ref), 0, 0)

    def kv_map(b, w, kb, qi, qoff_ref, koff_ref):
        if causal:
            # K blocks in the whole window's future: the last live one
            last = (qoff_ref[0] + (w + 1) * n_qw * bq - 1
                    - koff_ref[0]) // bk
            kb = jnp.minimum(kb, jnp.clip(last, 0, n_kb - 1))
            if window is not None:
                # and K blocks wholly behind the q window's first query
                kb = jnp.maximum(kb, jnp.minimum(_first_k_block(
                    qoff_ref[0] + w * n_qw * bq, koff_ref[0], bk, window),
                    n_kb - 1))
        return (b, 0, kb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # q_offset, k_offset (SMEM)
        grid=(bh // hb, n_win, n_kb, n_steps),
        in_specs=[
            pl.BlockSpec((hb, d, bq), q_map),           # q^T
            pl.BlockSpec((hb, d, bk), kv_map),          # k^T
            pl.BlockSpec((hb, d, bk), kv_map),          # v^T
            pl.BlockSpec((hb, d, bq), q_map),           # dout^T
            pl.BlockSpec((hb, 1, 1, bq), row_map),      # lse
            pl.BlockSpec((hb, 1, 1, bq), row_map),      # D
        ],
        out_specs=[
            pl.BlockSpec((hb, n_qw, d, bq), lambda b, w, *_: (b, w, 0, 0)),
            pl.BlockSpec((1, hb, d, bk), lambda b, w, kb, *_: (w, b, 0, kb)),
            pl.BlockSpec((1, hb, d, bk), lambda b, w, kb, *_: (w, b, 0, kb)),
        ],
        scratch_shapes=[
            pltpu.VMEM((hb, n_qw, d, bq), jnp.float32),     # dq^T
            pltpu.VMEM((hb, d, bk), jnp.float32),           # dk^T
            pltpu.VMEM((hb, d, bk), jnp.float32),           # dv^T
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_qb, d, bq), dtype),
            jax.ShapeDtypeStruct((n_win, bh, d, t), part),
            jax.ShapeDtypeStruct((n_win, bh, d, t), part),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=sched.flash_bwd_vmem_limit(
                hb, bq, bk, n_qw * bq, d, dtype.itemsize)),
        interpret=interpret,
        name="flash_attention_bwd",
    )


def _seq_minor(x):
    """(B, H, T, D) -> (B, H, D, T): the layout the backward kernel takes
    and the one q, k and v wait in between the passes. With the sequence
    along lanes nothing is padded; (T, D) operands with D short of the
    128 lanes are, to twice the bytes at D = 64, and XLA would hold the
    backward's padded copies from the end of the forward on (50 MB a
    layer at (8, 16, 1024, 64))."""
    import jax.numpy as jnp

    return jnp.swapaxes(x, -1, -2)


def _flash_bwd(qt, kt, vt, out, lse, dout, dlse, scale, causal, interpret,
               q_offset, k_offset, block_k=None, block_q=None, window=None):
    """dq, dk, dv of one flash-attention call through the backward
    kernel. ``qt``, ``kt``, ``vt`` are (B, H, D, T) (:func:`_seq_minor`);
    ``lse`` is the forward's, in any shape that is row-major the
    (B, H, T) sequence; ``dlse`` (or None) is its cotangent, non-zero
    when the caller merges results by log-sum-exp as ring attention
    does: d lse / d s = p, so it only moves D. ``block_k`` / ``block_q``
    override the schedule's tile (``bwd_block_k=``; the search driver
    gives both) and are legalized as the schedule's are."""
    import jax.numpy as jnp

    b, h, t, d = out.shape
    bh = b * h
    sched = _schedule()
    # a long sequence off the lane grid runs padded with zeros
    tp = sched.flash_bwd_length(t)
    window = _window_of(window, causal, t, q_offset, k_offset)
    bq, bk = sched.flash_bwd_block(bh, tp, d, str(out.dtype),
                                   interpret=bool(interpret),
                                   block_k=block_k, block_q=block_q,
                                   window=window)
    itemsize = out.dtype.itemsize
    n_win = sched.flash_bwd_windows(tp, bq, bk, d, itemsize)
    hb = sched.flash_bwd_heads(bh, bq, bk, tp // n_win, d, itemsize)
    fn = _build_flash_bwd(bh, tp, d, str(out.dtype), float(scale),
                          bool(causal), bool(interpret), bq, bk, hb, n_win,
                          window, _at_start(q_offset, k_offset))
    dd = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    if dlse is not None:
        dd = dd - dlse.astype(jnp.float32).reshape(dd.shape)

    def lanes(x, *lead):
        x = x.reshape(bh, *lead, t)
        if tp != t:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, tp - t)])
        return x

    rows = (bh, tp // bq, 1, bq)
    dq, dk, dv = fn(jnp.asarray(q_offset, jnp.int32).reshape(1),
                    jnp.asarray(k_offset, jnp.int32).reshape(1),
                    lanes(qt, d), lanes(kt, d), lanes(vt, d),
                    lanes(_seq_minor(dout), d),
                    lanes(lse).reshape(rows), lanes(dd).reshape(rows))
    if n_win == 1:
        dk, dv = dk[0], dv[0]
    else:
        dk, dv = dk.sum(0).astype(out.dtype), dv.sum(0).astype(out.dtype)
    dq = jnp.swapaxes(dq, -1, -2).reshape(bh, tp, d)[:, :t]
    return (dq.reshape(out.shape),
            _seq_minor(dk[..., :t]).reshape(out.shape),
            _seq_minor(dv[..., :t]).reshape(out.shape))


def flash_attention_with_lse(q, k, v, causal=False, scale=None,
                             interpret=False, q_offset=0, k_offset=0,
                             block_q=None, block_k=None, bwd_block_k=None,
                             window=None):
    """Differentiable (out, lse) pair — the ring-attention building block:
    per-hop results merge by log-sum-exp, so the lse output needs a
    gradient path too (its cotangent enters the backward kernel through
    D = rowsum(dout * out) - dlse). Offsets may be traced scalars
    (lax.axis_index inside shard_map); custom_vjp cannot close over
    tracers, so they ride along as float primals with zero cotangents.
    Both passes are Pallas kernels (``flash_attention_fwd``,
    ``flash_attention_bwd``) whose tiles resolve through the schedule
    registry (docs/autotune.md): block_q / block_k override the
    forward's, bwd_block_k the width of the backward's K block
    (legalized: any width the scan before it took still works).
    ``window`` as :func:`flash_attention`'s, placed by the offsets."""
    import jax
    import jax.numpy as jnp

    s = scale if scale is not None else 1.0 / _np.sqrt(q.shape[-1])

    def fwd(q, k, v, qo, ko):
        return _flash_fwd(q, k, v, causal, s, interpret,
                          qo.astype(jnp.int32), ko.astype(jnp.int32),
                          block_q, block_k, window)

    @jax.custom_vjp
    def f(q, k, v, qo, ko):
        out, lse = fwd(q, k, v, qo, ko)
        return out, lse.reshape(q.shape[:3] + (1,))

    def f_fwd(q, k, v, qo, ko):
        out, lse = kernel_residuals(*fwd(q, k, v, qo, ko))
        return ((out, lse.reshape(q.shape[:3] + (1,))),
                (_seq_minor(q), _seq_minor(k), _seq_minor(v), out, lse,
                 qo, ko))

    def f_bwd(res, cot):
        q, k, v, out, lse, qo, ko = res
        dout, dlse = cot
        dq, dk, dv = _flash_bwd(
            q, k, v, out, lse, dout, dlse, s, causal, interpret,
            qo.astype(jnp.int32), ko.astype(jnp.int32),
            block_k=bwd_block_k, window=window)
        return dq, dk, dv, jnp.zeros_like(qo), jnp.zeros_like(ko)

    f.defvjp(f_fwd, f_bwd)
    return f(q, k, v, jnp.asarray(q_offset, jnp.float32),
             jnp.asarray(k_offset, jnp.float32))


def flash_attention_with_grad(q, k, v, causal=False, scale=None,
                              interpret=False, block_q=None, block_k=None,
                              bwd_block_k=None, window=None):
    """Differentiable flash attention: the forward kernel paired by
    jax.custom_vjp with the backward kernel (``flash_attention_bwd``:
    probabilities recomputed tile by tile from the forward's saved
    log-sum-exp, dq / dk / dv gathered in VMEM; operands in the input's
    dtype, float32 accumulation, dead causal tiles skipped). Same
    shape / placement / schedule rules as flash_attention, NDArrays
    included; bwd_block_k overrides the width of the backward's K
    block (any width: it is legalized onto the kernel's lane grid).
    ``window`` as :func:`flash_attention`'s: both kernels leave the
    tiles wholly outside it out of their grids."""
    import jax

    if hasattr(q, "_data"):
        from ..ndarray.ndarray import NDArray

        ctx = getattr(q, "_ctx", None)
        return NDArray(flash_attention_with_grad(
            q._data, k._data, v._data, causal=causal, scale=scale,
            interpret=interpret,
            block_q=block_q, block_k=block_k,
            bwd_block_k=bwd_block_k, window=window), ctx)

    s = scale if scale is not None else 1.0 / _np.sqrt(q.shape[-1])

    def fwd(q, k, v):
        return _flash_fwd(q, k, v, causal, s, interpret, 0, 0,
                          block_q, block_k, window)

    @jax.custom_vjp
    def f(q, k, v):
        return fwd(q, k, v)[0]

    def f_fwd(q, k, v):
        out, lse = kernel_residuals(*fwd(q, k, v))
        return out, (_seq_minor(q), _seq_minor(k), _seq_minor(v), out, lse)

    def f_bwd(res, dout):
        q, k, v, out, lse = res
        return _flash_bwd(q, k, v, out, lse, dout, None, s, causal,
                          interpret, 0, 0, block_k=bwd_block_k,
                          window=window)

    f.defvjp(f_fwd, f_bwd)
    return f(q, k, v)
