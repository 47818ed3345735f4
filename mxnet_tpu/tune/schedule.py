"""Kernel schedule registry: search space, block legalization, table.

THE one home for Pallas block constants and kernel schedule choices
(graftlint TS004 flags hardcoded block sizes anywhere else): the flash-
attention forward/backward block sizes, the ring-attention per-hop
blocks (the hop kernels ARE the flash forward and backward, keyed at
the hop's local shape), and the INT8 conv/FC/requantize arrangement
choices all resolve here at trace time, in this order:

1. an explicit override from the caller (how the search driver times a
   candidate without touching the table),
2. the persistent schedule table — the committed
   ``tools/schedule_table.json`` merged under the per-host
   ``MXNET_TPU_SCHEDULE_TABLE`` override, keyed
   ``kernel|backend|dtype|shape`` — when ``MXNET_TPU_AUTOTUNE`` is on,
3. the declared default schedule,

followed by *legalization* (shared by forward and backward): a block
must divide the sequence length and sit on the TPU sublane grid
(multiple of 8), with the single-block case (block == T) always legal —
exactly the envelope the hand-written kernels supported, now centralized
so a tuned or defaulted block can never silently drop a tail.

The table's content digest (:func:`fingerprint_token`) folds into the
AOT compile-cache key (``capture.AOTCache.key``): tuned programs
warm-load fleet-wide from the compile cache, and a schedule change can
never false-hit an artifact compiled under another schedule.

This module is importable standalone (``tools/validate_baselines.py``
loads it by file path to audit the table schema without jax or the
package import).
"""
from __future__ import annotations

import hashlib
import json
import os
import threading

try:
    from . import _STATS
except ImportError:  # standalone (file-path) import: local counters
    _STATS = {"autotune_table_hits": 0, "autotune_table_misses": 0}

SCHEMA_VERSION = 1

# TPU sublane granularity: a non-final block must sit on this grid or
# Mosaic rejects the tile (docs/autotune.md "Legalization").
MIN_SUBLANE = 8

# The declared candidate axes per kernel — what the search driver sweeps
# and what validate_table() accepts. Block axes are legal-subset-filtered
# per shape at candidate-generation time.
FLASH_BLOCK_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8)
# Heads of the flattened batch*head axis a flash-forward grid step may
# take together, and the score elements (heads x block_q x block_k) a
# step is filled up to: one 512 x 512 tile's worth. Measured on a v5e at
# (128, 1024, 64) bf16 (PERF.md, PR 28): a grid step costs about a third
# of a microsecond whatever it holds, and the compiler interleaves the
# unrolled heads' code, so eight heads of 128 x 128 run at 0.29 of one
# and four of 256 x 256 at 0.61. Past one 512 x 512 tile the gain is
# 10 % of the kernel and the unrolled code costs the step 6 s of compile
# time (four heads of 512 x 512: 59.8 s against 53.3 s), so no further.
FLASH_HEAD_CANDIDATES = (8, 4, 2, 1)
FLASH_STEP_SCORES = 512 * 512
# The TPU's lane width: the flash forward keeps its row statistics
# replicated over one lane tile and lays the emitted lse along lanes.
LANES = 128
# VMEM a flash-forward step may plan for under the compiler's scoped
# default (16 MiB on a v5e), and the most the kernel ever asks for
# (a v5e core has 128 MiB).
FLASH_VMEM_BUDGET = 12 * 2 ** 20
FLASH_VMEM_CEILING = 48 * 2 ** 20
DECODE_PAGE_BLOCK_CANDIDATES = (16, 8, 4, 2, 1)
# Chunks of the gated delta rule one grid step of its kernels takes
# (ops/delta_rule_kernels.py): the chunks of a step are unrolled, so the
# compiler runs the part of a later chunk that does not read the state
# (its products and its triangular inverse) beside the earlier chunk's
# state update; more of them is more code, not more work.
DELTA_RULE_CHUNK_CANDIDATES = (16, 8, 4, 2, 1)
# The short causal convolution + SiLU kernels (ops/conv_silu_kernels.py):
# rows of the sequence and channels one grid step takes, the rows of
# history (forward) or of what follows (backward) a step reads beside its
# tile (one sublane tile of a 16-bit operand), and the most taps: a pass
# inside a tile hands the next one float32 sublane tile, 8 rows.
CONV_SILU_ROW_CANDIDATES = (2048, 1024, 512, 256, 128, 64, 32, 16)
CONV_SILU_COL_CANDIDATES = (2048, 1024, 512, 256, 128)
CONV_SILU_HALO = 16
CONV_SILU_MAX_TAPS = 8
# The expert layers' grouped products (ops/grouped_matmul_kernels.py):
# the sorted rows, the contraction and the output's columns one grid
# step takes. Rows legalize to a divisor of the block's rows on the grid
# of a 16-bit sublane tile; either width to whole lane tiles, as few as
# the tile asked for allows (4096: every width the cells have in one),
# the last one ragged where the width is off the lane grid.
GROUPED_MM_ROW_CANDIDATES = (1024, 512, 256, 128)
GROUPED_MM_COL_CANDIDATES = (4096, 2048, 1024, 512, 256, 128)
SEARCH_SPACE = {
    # Pallas streaming flash-attention forward (ops/pallas_kernels.py);
    # also the ring-attention per-hop kernel, keyed at the hop's local
    # (bh, t, d) shape (parallel/ring_attention.py)
    "flash_fwd": {"block_q": FLASH_BLOCK_CANDIDATES,
                  "block_k": FLASH_BLOCK_CANDIDATES},
    # Pallas flash-attention backward (one kernel for dq, dk and dv)
    "flash_bwd": {"block_q": FLASH_BLOCK_CANDIDATES,
                  "block_k": FLASH_BLOCK_CANDIDATES},
    # INT8 GEMM / conv operand arrangement: feed the MXU int8 operands
    # directly, or widen to int32 first (exact same integer results;
    # which one the backend runs faster is a measured fact)
    "int8_fc": {"operand_width": ("int8", "int32")},
    "int8_conv": {"operand_width": ("int8", "int32")},
    # requantize epilogue arrangement for calibrated boundaries: the
    # reference two-multiply form, or one fused combined scale (may
    # differ in the last ULP — the numerics gate decides per shape)
    "int8_requant": {"path": ("via_fp32", "fused_scale")},
    # paged decode attention (ops/decode_attention.py): how many KV
    # pages the streaming-softmax loop gathers per block, keyed per
    # (decode batch, pages-per-sequence) shape — the one-token-per-
    # sequence serving hot path (serving/decode.py)
    "decode_attn": {"block_pages": DECODE_PAGE_BLOCK_CANDIDATES},
    # the gated delta rule's forward and backward kernels
    # (ops/delta_rule_kernels.py): chunks a grid step, keyed per
    # (value heads, T, Dk, Dv) shape
    "delta_rule_fwd": {"chunks": DELTA_RULE_CHUNK_CANDIDATES},
    "delta_rule_bwd": {"chunks": DELTA_RULE_CHUNK_CANDIDATES},
    # the short causal convolution + SiLU and its backward
    # (ops/conv_silu_kernels.py): the (rows, channels) tile of a grid
    # step, keyed per (batch, T, channels, taps) shape
    "conv_silu_fwd": {"rows": CONV_SILU_ROW_CANDIDATES,
                      "cols": CONV_SILU_COL_CANDIDATES},
    "conv_silu_bwd": {"rows": CONV_SILU_ROW_CANDIDATES,
                      "cols": CONV_SILU_COL_CANDIDATES},
    # the grouped products (ops/grouped_matmul_kernels.py): the (rows,
    # contraction, columns) tile of ``grouped_matmul`` (the forward, and
    # the input gradient on the transposed weights, keyed apart) and of
    # ``grouped_matmul_t`` (the weight gradient), keyed per (rows,
    # contraction, columns, groups) shape
    "grouped_mm": {"tm": GROUPED_MM_ROW_CANDIDATES,
                   "tk": GROUPED_MM_COL_CANDIDATES,
                   "tn": GROUPED_MM_COL_CANDIDATES},
    "grouped_mm_t": {"tm": GROUPED_MM_ROW_CANDIDATES,
                     "tk": GROUPED_MM_COL_CANDIDATES,
                     "tn": GROUPED_MM_COL_CANDIDATES},
}

# What a kernel runs when the table has no entry (block sizes are
# legalized down to the shape). The flash forward's and backward's, the
# gated delta rule's, the short convolution's and the grouped products'
# are chip-measured (PERF.md, PRs 28, 31, 33, 39 and 45); the others are
# the hand-written pre-autotune constants.
DEFAULT_SCHEDULES = {
    "flash_fwd": {"block_q": 512, "block_k": 512},
    "flash_bwd": {"block_q": 512, "block_k": 512},
    "int8_fc": {"operand_width": "int8"},
    "int8_conv": {"operand_width": "int8"},
    "int8_requant": {"path": "via_fp32"},
    "decode_attn": {"block_pages": 8},
    "delta_rule_fwd": {"chunks": 8},
    "delta_rule_bwd": {"chunks": 4},
    "conv_silu_fwd": {"rows": 512, "cols": 1024},
    "conv_silu_bwd": {"rows": 1024, "cols": 512},
    # 256 rows with both widths whole read within 1.2 x of the best tile
    # in all 18 kernels of the expert cells (PR 45); a width past 2048
    # is tiled, so no default outgrows VMEM
    "grouped_mm": {"tm": 256, "tk": 2048, "tn": 2048},
    "grouped_mm_t": {"tm": 256, "tk": 2048, "tn": 2048},
}

_LOCK = threading.Lock()
_TABLE_CACHE: dict = {"stamp": None, "table": None}


class ScheduleError(ValueError):
    """No legal schedule for the requested shape."""


# ----------------------------------------------------------- legalization

def legalize_block(t, want, grain=MIN_SUBLANE):
    """The largest legal block ``<= want`` for sequence length ``t``:
    either ``t`` itself (a single block covering the whole sequence:
    legal on the sublane grid, and off it up to one lane tile, the
    envelope the kernels have always had), or a multiple of ``grain``
    that divides ``t``: :data:`MIN_SUBLANE` where the sequence lies
    along sublanes (the forward), :data:`LANES` where it lies along
    lanes (the backward). Returns None when no legal block exists —
    callers raise :class:`ScheduleError` (``impl="auto"`` asks
    :func:`flash_shape_supported` first)."""
    t = int(t)
    want = int(want)
    if t <= 0 or want <= 0:
        return None
    if want >= t and (t % MIN_SUBLANE == 0 or t <= LANES):
        return t
    b = (min(want, t) // grain) * grain
    while b >= grain:
        if t % b == 0:
            return b
        b -= grain
    return None


def legal_flash_blocks(t, cap=None, grain=MIN_SUBLANE):
    """The legal subset of :data:`FLASH_BLOCK_CANDIDATES` for length
    ``t`` (plus the single-block ``t`` itself), largest first — the
    candidate axis the search driver sweeps: multiples of ``grain``
    (:data:`LANES` for the backward, whose sequence lies along lanes)."""
    t = int(t)
    out = []
    for b in FLASH_BLOCK_CANDIDATES:
        if cap is not None and b > cap:
            continue
        if b == t or (b < t and t % b == 0 and b % grain == 0):
            out.append(b)
    if t not in out and (cap is None or t <= cap):
        out.insert(0, t)
    return out


def flash_shape_supported(t, d):
    """Whether the Pallas flash kernel has ANY legal schedule for a
    (T, D) shape — the shared gate ``parallel.ring_attention._pick_impl``
    and the kernel entrypoints both consult."""
    default = DEFAULT_SCHEDULES["flash_fwd"]["block_q"]
    return int(d) <= 256 and legalize_block(t, default) is not None


# ------------------------------------------------------------------ table

def default_table_path():
    """The committed schedule table: ``tools/schedule_table.json`` next
    to the package (absent in installed trees — empty table)."""
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(root, "tools", "schedule_table.json")


def host_table_path():
    """Per-host override table (``MXNET_TPU_SCHEDULE_TABLE``), or None."""
    p = os.environ.get("MXNET_TPU_SCHEDULE_TABLE", "").strip()
    return p or None


def autotune_enabled():
    """``MXNET_TPU_AUTOTUNE=0`` is the kill switch: kernel builders run
    the declared default schedules and ignore the table entirely."""
    return os.environ.get("MXNET_TPU_AUTOTUNE", "1").strip().lower() \
        not in ("0", "false", "off")


def _stamp():
    """Cache stamp over the table sources: paths + mtime/size, so an
    edited or re-pointed table is picked up without a process restart."""
    parts = []
    for p in (default_table_path(), host_table_path()):
        if not p:
            parts.append(("", 0, 0))
            continue
        try:
            st = os.stat(p)
            parts.append((p, st.st_mtime_ns, st.st_size))
        except OSError:
            parts.append((p, 0, -1))
    return tuple(parts)


def load_single_table(path):
    """One table file -> its ``entries`` dict ({} on absent/unreadable/
    wrong schema — a corrupt table must degrade to defaults, never
    crash a kernel build)."""
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(data, dict) or \
            data.get("schema_version") != SCHEMA_VERSION:
        return {}
    entries = data.get("entries")
    return entries if isinstance(entries, dict) else {}


def load_table(refresh=False):
    """The merged entries view kernels read: committed table with the
    per-host override's entries layered on top. Cached on file stamps."""
    stamp = _stamp()
    with _LOCK:
        if not refresh and _TABLE_CACHE["stamp"] == stamp:
            return _TABLE_CACHE["table"]
    merged = dict(load_single_table(default_table_path()))
    host = host_table_path()
    if host:
        merged.update(load_single_table(host))
    with _LOCK:
        _TABLE_CACHE["stamp"] = stamp
        _TABLE_CACHE["table"] = merged
    return merged


def table_digest():
    """Stable 16-hex content digest of the merged entries ('' when the
    merged table is empty)."""
    entries = load_table()
    if not entries:
        return ""
    blob = json.dumps(entries, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def fingerprint_token():
    """What the AOT cache key folds in: the merged-table digest, or ''
    when autotuning is disabled OR the table is empty — both of which
    compile the identical default-schedule programs, so they must share
    cache identity."""
    if not autotune_enabled():
        return ""
    return table_digest()


def entry_key(kernel, shape_key, dtype, backend):
    return f"{kernel}|{backend}|{dtype}|{shape_key}"


def resolve_backend(interpret=False):
    """The table's backend axis: 'interpret' for Pallas interpret mode
    (CPU emulation — its measured costs must never steer a chip), else
    the live jax backend."""
    if interpret:
        return "interpret"
    import jax

    return jax.default_backend()


def lookup(kernel, shape_key, dtype, backend):
    """Raw table lookup -> the entry's schedule dict or None. Counts
    hits/misses (``autotune_table_hits``/``autotune_table_misses``)."""
    entry = load_table().get(entry_key(kernel, shape_key, dtype, backend))
    sched = entry.get("schedule") if isinstance(entry, dict) else None
    if isinstance(sched, dict) and sched:
        _STATS["autotune_table_hits"] += 1
        return dict(sched)
    _STATS["autotune_table_misses"] += 1
    return None


def kernel_schedule(kernel, shape_key, dtype, backend):
    """The schedule a kernel builder runs: declared defaults, overlaid
    with the table entry when autotuning is enabled."""
    sched = dict(DEFAULT_SCHEDULES.get(kernel, {}))
    if autotune_enabled():
        hit = lookup(kernel, shape_key, dtype, backend)
        if hit:
            sched.update(hit)
    return sched


# ------------------------------------------------------------- shape keys
# ONE owner for every kernel's table shape key: the kernel builders and
# the search workloads both derive keys here, so a tuned entry can never
# go dead because the two sides formatted the same shape differently.

def flash_shape_key(bh, t, d, window=None):
    """Flash attention table key; a kernel built with a window is keyed
    apart (``-w<window>``), one without reads as it always did."""
    key = f"bh{int(bh)}-t{int(t)}-d{int(d)}"
    return key if window is None else f"{key}-w{int(window)}"


def int8_fc_shape_key(m, k, n):
    return f"m{int(m)}-k{int(k)}-n{int(n)}"


def int8_conv_shape_key(data_shape, weight_shape, stride):
    return ("d" + "x".join(str(int(s)) for s in data_shape)
            + "-w" + "x".join(str(int(s)) for s in weight_shape)
            + "-s" + "x".join(str(int(s)) for s in stride))


def int8_requant_shape_key(rows, cols):
    return f"r{int(rows)}-c{int(cols)}"


def delta_rule_shape_key(bh, t, dk, dv):
    """Gated delta rule table key: value heads over the batch, tokens,
    key and value head sizes."""
    return f"bh{int(bh)}-t{int(t)}-dk{int(dk)}-dv{int(dv)}"


def conv_silu_shape_key(b, t, channels, taps):
    """Short-convolution table key: batch, tokens, the channels of the
    part a call convolves, taps."""
    return f"b{int(b)}-t{int(t)}-c{int(channels)}-k{int(taps)}"


def grouped_mm_shape_key(m, k, n, groups, transpose_rhs=False):
    """Grouped-product table key: the block's rows, the contraction,
    the output's columns, the groups; ``-rt`` where the kernel reads the
    weights transposed (the input gradient)."""
    key = f"m{int(m)}-k{int(k)}-n{int(n)}-g{int(groups)}"
    return key + "-rt" if transpose_rhs else key


def decode_shape_key(batch, pages):
    """Paged decode attention table key: the fixed decode-batch width
    and the per-sequence page-table width (kv capacity in pages)."""
    return f"b{int(batch)}-p{int(pages)}"


# ----------------------------------------------- flash-kernel resolution

def _pad(n, to):
    return -(-int(n) // to) * to


def flash_fwd_vmem_bytes(hb, bq, bk, d, itemsize):
    """What one grid step of the flash forward holds in VMEM, from its
    shapes: the q/o and k/v blocks (double-buffered, D padded to the lane
    tile), the float32 statistics and accumulator, the lse row, and the
    (bq, bk) float32 score tile with the copies the softmax makes of it
    (masked scores, probabilities, probabilities in the operand dtype)."""
    dl = _pad(d, LANES)
    blocks = 2 * hb * (2 * bq + 2 * bk) * dl * itemsize
    stats = hb * bq * (2 * LANES + dl) * 4
    lse = 2 * hb * MIN_SUBLANE * _pad(bq, LANES) * 4
    scores = 4 * _pad(bq, MIN_SUBLANE) * _pad(bk, LANES) * 4
    return blocks + stats + lse + scores


def _vmem_limit(need):
    """A flash kernel's ``vmem_limit_bytes`` from its step's estimate:
    None (the compiler's own scoped default) while the step fits it,
    else the estimate with half again for what the compiler adds, up to
    :data:`FLASH_VMEM_CEILING`."""
    if need <= FLASH_VMEM_BUDGET:
        return None
    return min(need + need // 2, FLASH_VMEM_CEILING)


def _heads(bh, bq, bk, vmem_bytes):
    """Heads (rows of the flattened batch*head axis) one grid step takes:
    as many as bring the step's score tiles up to
    :data:`FLASH_STEP_SCORES` and still fit the VMEM budget
    (``vmem_bytes(hb)``), from :data:`FLASH_HEAD_CANDIDATES`, dividing
    ``bh``. A function of the tile, not a schedule axis of its own: small
    tiles (short sequences, many heads) get the grid step's fixed cost
    spread over several heads, large ones run one head a step."""
    for hb in FLASH_HEAD_CANDIDATES:
        if int(bh) % hb == 0 and hb * bq * bk <= FLASH_STEP_SCORES and \
                vmem_bytes(hb) <= FLASH_VMEM_BUDGET:
            return hb
    return 1


def flash_fwd_vmem_limit(hb, bq, bk, d, itemsize):
    """The forward's ``vmem_limit_bytes`` (:func:`_vmem_limit`)."""
    return _vmem_limit(flash_fwd_vmem_bytes(hb, bq, bk, d, itemsize))


def flash_fwd_heads(bh, bq, bk, d, itemsize):
    """Heads one grid step of the forward takes (:func:`_heads`)."""
    return _heads(bh, bq, bk, lambda hb: flash_fwd_vmem_bytes(
        hb, bq, bk, d, itemsize))


def flash_fwd_blocks(bh, t, d, dtype, interpret=False, block_q=None,
                     block_k=None, window=None):
    """Resolved + legalized (block_q, block_k) for the flash forward.
    Explicit overrides must already be legal (the search driver's
    contract); table/default blocks are legalized down. How many heads
    share a grid step follows from the tile (:func:`flash_fwd_heads`).
    Raises :class:`ScheduleError` when the shape has no legal
    schedule."""
    t = int(t)
    if int(d) > 256:
        raise ScheduleError(f"flash schedule: unsupported D={d} (> 256)")
    if block_q is not None or block_k is not None:
        bq = int(block_q) if block_q is not None else None
        bk = int(block_k) if block_k is not None else None
        for name, b in (("block_q", bq), ("block_k", bk)):
            if b is None:
                continue
            if b <= 0 or t % b != 0:
                raise ScheduleError(
                    f"flash schedule: explicit {name}={b} does not "
                    f"divide T={t}")
            # hold overrides to the SAME legality bar the resolver
            # applies everywhere else: off-grid tiles fail here with a
            # ScheduleError, not deep inside Mosaic on the chip
            if b != t and b % MIN_SUBLANE != 0:
                raise ScheduleError(
                    f"flash schedule: explicit {name}={b} is off the "
                    f"sublane grid (multiple of {MIN_SUBLANE}, or T "
                    "itself)")
    else:
        bq = bk = None
    if bq is None or bk is None:
        sched = kernel_schedule("flash_fwd",
                                flash_shape_key(bh, t, d, window),
                                str(dtype), resolve_backend(interpret))
        if bq is None:
            bq = legalize_block(t, sched["block_q"])
        if bk is None:
            bk = legalize_block(t, sched["block_k"])
    if bq is None or bk is None:
        raise ScheduleError(
            f"flash schedule: no legal block for T={t} (needs T itself "
            f"or a multiple-of-{MIN_SUBLANE} divisor)")
    return bq, bk


def _live_tiles(t, bq, bk, window=None):
    """(T / bq, T / bk) booleans: the (q block, K block) tiles that hold
    a (query, key) pair with ``0 <= i - j`` (``< window``), both blocks
    at the sequence's start."""
    import numpy as np

    q_first = np.arange(int(t) // bq)[:, None] * bq
    k_first = np.arange(int(t) // bk)[None, :] * bk
    live = k_first <= q_first + bq - 1
    if window is not None:
        live &= k_first + bk - 1 > q_first - window
    return live


def flash_tiles(t, bq, bk, window=None):
    """(tiles a causal flash kernel computes under ``window``, tiles at
    or below the diagonal) at a (block_q, block_k) tile: the same for
    the forward and the backward, which visit the same pairs."""
    return (int(_live_tiles(t, bq, bk, window).sum()),
            int(_live_tiles(t, bq, bk).sum()))


def flash_window_steps(t, bq, bk, window, at_start=False, backward=False):
    """Steps of a flash kernel's innermost grid dimension under a
    window: the K blocks one q block's queries see (forward), the q
    blocks that see one K block's keys (``backward``). Counted exactly
    where both blocks are known to sit at the sequence's start; else
    the most a band of ``window + block - 1`` positions can touch
    wherever it lies (a ring hop's traced offsets)."""
    if at_start:
        return int(_live_tiles(t, bq, bk, window).sum(
            0 if backward else 1).max())
    outer, inner = (bk, bq) if backward else (bq, bk)
    return min(int(t) // inner, (int(window) + outer - 3) // inner + 2)


def flash_bwd_length(t):
    """The sequence length the flash backward runs at. Its operands have
    the sequence along lanes, so a tile is a multiple of :data:`LANES`
    or the whole sequence: ``t`` itself where a lane tile divides it or
    it is short enough for one tile (the default's 512), else ``t``
    rounded up to the lane tile, which the caller pads to with zeros (a
    padded key or query gives nothing to any gradient that is kept)."""
    t = int(t)
    if t % LANES == 0 or t <= DEFAULT_SCHEDULES["flash_bwd"]["block_q"]:
        return t
    return _pad(t, LANES)


def flash_bwd_block(bh, t, d, dtype, interpret=False, block_k=None,
                    block_q=None, window=None):
    """The flash backward's tile, (block_q, block_k), at a length
    :func:`flash_bwd_length` gives, so every shape the forward runs on
    has one. Each axis is the caller's override (``bwd_block_k=``, the
    search driver's candidates), else the table's, else the default's,
    and every one of them is legalized onto the lane grid: the largest
    multiple of :data:`LANES` that divides ``t`` and is no wider than
    asked (a width under one lane tile runs at one), or ``t`` itself.
    The scan this kernel replaced took any K width and padded the tail;
    a width given for it still works. Heads a grid step and q windows
    follow from the tile (:func:`flash_bwd_heads`,
    :func:`flash_bwd_windows`)."""
    t = int(t)
    want = {"block_q": block_q, "block_k": block_k}
    if block_q is None or block_k is None:
        sched = kernel_schedule("flash_bwd",
                                flash_shape_key(bh, t, d, window),
                                str(dtype), resolve_backend(interpret))
        want = {axis: sched[axis] if b is None else b
                for axis, b in want.items()}
    return tuple(
        legalize_block(t, max(int(want[axis]), LANES), LANES) or t
        for axis in ("block_q", "block_k"))


def flash_bwd_vmem_bytes(hb, bq, bk, rows, d, itemsize):
    """What one grid step of the flash backward holds in VMEM, from its
    shapes (the sequence along lanes, D along sublanes): the q/dout and
    k/v blocks and the dk/dv blocks going out (double-buffered), the lse
    and D rows, the float32 dk/dv accumulators, dq of a whole window of
    ``rows`` q rows (float32 accumulator and the double-buffered block
    going out), and the (bk, bq) float32 score tile with the copies the
    tile program makes of it (probabilities, dp, ds, and the two in the
    operand dtype)."""
    ds = _pad(d, 2 * MIN_SUBLANE)
    bq, bk, rows = _pad(bq, LANES), _pad(bk, LANES), _pad(rows, LANES)
    blocks = 2 * hb * (2 * bq + 4 * bk) * ds * itemsize
    rows_in = 2 * 2 * hb * MIN_SUBLANE * bq * 4
    dkv = 2 * hb * bk * ds * 4
    dq = hb * rows * ds * (4 + 2 * itemsize)
    scores = 6 * bk * bq * 4
    return blocks + rows_in + dkv + dq + scores


def flash_bwd_vmem_limit(hb, bq, bk, rows, d, itemsize):
    """The backward's ``vmem_limit_bytes`` (:func:`_vmem_limit`)."""
    return _vmem_limit(flash_bwd_vmem_bytes(hb, bq, bk, rows, d, itemsize))


def flash_bwd_windows(t, bq, bk, d, itemsize):
    """Into how many q windows the backward cuts the sequence: the
    kernel holds dq of a whole window in VMEM while the K blocks pass,
    so the fewest windows (a divisor of T / bq) whose step, with half
    again for the compiler, stays under :data:`FLASH_VMEM_CEILING`. One
    up to T x D of about four million (8192 x 256, 32 768 x 128 in
    bf16); each further window reads K and V once more."""
    n_qb = int(t) // int(bq)
    for n_win in range(1, n_qb + 1):
        if n_qb % n_win == 0 and 3 * flash_bwd_vmem_bytes(
                1, bq, bk, int(t) // n_win, d, itemsize) \
                <= 2 * FLASH_VMEM_CEILING:
            return n_win
    return n_qb


def flash_bwd_heads(bh, bq, bk, rows, d, itemsize):
    """Heads one grid step of the backward takes (:func:`_heads`), with
    a window of ``rows`` q rows each."""
    return _heads(bh, bq, bk, lambda hb: flash_bwd_vmem_bytes(
        hb, bq, bk, rows, d, itemsize))


# ------------------------------------------ gated-delta-rule resolution

def delta_rule_shape_supported(dk, dv, chunk):
    """Whether the gated delta rule's kernels take the shape: a head is
    a column block of the (B, T, heads x size) projections, so both
    head sizes lie on the lane grid, and a chunk's rows on the sublane
    grid of a 16-bit operand (two :data:`MIN_SUBLANE`). What
    ``ops.linear_attention.gated_delta_rule`` asks before it takes the
    kernels; everything else runs its ``jax.numpy`` form."""
    return int(dk) > 0 and int(dv) > 0 and int(dk) % LANES == 0 \
        and int(dv) % LANES == 0 and int(chunk) > 0 \
        and int(chunk) % (2 * MIN_SUBLANE) == 0


def delta_rule_chunks(kernel, bh, t, n_chunks, dk, dv, dtype,
                      interpret=False, chunks=None):
    """Chunks one grid step of ``kernel`` (``delta_rule_fwd`` /
    ``delta_rule_bwd``) takes: the caller's ``chunks`` (the search
    driver's candidates), else the table's, else the default's,
    legalized to the largest divisor of the sequence's ``n_chunks`` that
    is no larger (a step holds whole chunks and the grid whole steps;
    one chunk a step is always legal)."""
    if chunks is None:
        chunks = kernel_schedule(
            kernel, delta_rule_shape_key(bh, t, dk, dv), str(dtype),
            resolve_backend(interpret))["chunks"]
    nb = max(1, min(int(chunks), int(n_chunks)))
    while int(n_chunks) % nb:
        nb -= 1
    return nb


def delta_rule_heads(rep, chunk):
    """Value heads of one key head that share a chunk's tiles in the
    gated-delta-rule kernels: as many of the ``rep`` heads the key head
    serves (a divisor of it) as fill the MXU's :data:`LANES` rows with
    their chunks, block diagonal by head. Two at chunks of 64: every
    product of the chunk program, the triangular inverse's ten first,
    then serves both heads."""
    heads = max(1, min(int(rep), LANES // int(chunk)))
    while int(rep) % heads:
        heads -= 1
    return heads


def delta_rule_vmem_bytes(kernel, nb, chunk, rep, dk, dv, itemsize):
    """What one grid step of a gated-delta-rule kernel holds in VMEM,
    from its shapes: the q / k and v / o row blocks of ``nb`` chunks
    (the backward's dout and dq / dk / dv beside them), the entering
    state of every chunk and value head and the triangular inverse of
    every chunk and group of heads where the forward saves them or the
    backward reads them (all double-buffered), the float32 state
    scratch, and the float32 copies the chunk programs make of a
    chunk's rows and its (heads x chunk) square tiles, all ``nb``
    chunks' live at once since they are unrolled."""
    rows = nb * chunk
    wide = delta_rule_heads(rep, chunk) * chunk
    tiles = rep * chunk * _pad(wide, LANES)     # a tile a group of heads
    kv = rows * (2 * dk + 2 * rep * dv) * itemsize
    saved = nb * (rep * dk * dv + tiles) * 4
    state = rep * dk * dv * 4
    work = rows * (2 * dk + rep * (2 * dk + 3 * dv)) * 4 + nb * 8 * tiles * 4
    if kernel == "delta_rule_bwd":
        kv = 2 * kv + rows * rep * dv * itemsize
        work *= 2
    return 2 * (kv + saved) + state + work


def delta_rule_vmem_limit(kernel, nb, chunk, rep, dk, dv, itemsize):
    """A gated-delta-rule kernel's ``vmem_limit_bytes``
    (:func:`_vmem_limit`)."""
    return _vmem_limit(delta_rule_vmem_bytes(kernel, nb, chunk, rep, dk,
                                             dv, itemsize))


# -------------------------------------------- short-convolution resolution

def conv_silu_shape_supported(parts, taps, width=None):
    """Whether the short-convolution kernels take the shape: each of the
    ``parts`` the channels are handed on in is a column range of the
    input read in place and an array of its own going out, so every
    part's width (and with it every part's first column) lies on the
    lane grid; at most :data:`CONV_SILU_MAX_TAPS` taps; the input is at
    least as wide as the parts together. What
    ``ops.linear_attention.causal_conv_silu`` asks before it takes the
    kernels; everything else runs its ``jax.numpy`` form."""
    parts = tuple(int(p) for p in parts)
    return bool(parts) and all(p > 0 and p % LANES == 0 for p in parts) \
        and 1 <= int(taps) <= CONV_SILU_MAX_TAPS \
        and (width is None or int(width) >= sum(parts))


def conv_silu_tile(kernel, b, t, channels, offset, taps, dtype,
                   interpret=False, rows=None, cols=None):
    """The (rows, channels) tile of one grid step of ``kernel``
    (``conv_silu_fwd`` / ``conv_silu_bwd``) over a part of ``channels``
    channels that starts at column ``offset`` of its input: the caller's
    (the search driver's candidates), else the table's, else the
    default's. Rows are legalized to a multiple of
    :data:`CONV_SILU_HALO` no longer than the padded sequence (the last
    tile may hang over the end: the kernels mask it), channels to the
    largest multiple of the lane tile that is no larger and divides both
    the part's width and its offset (a block index counts whole
    tiles)."""
    if rows is None or cols is None:
        sched = kernel_schedule(
            kernel, conv_silu_shape_key(b, t, channels, taps), str(dtype),
            resolve_backend(interpret))
        rows = sched["rows"] if rows is None else rows
        cols = sched["cols"] if cols is None else cols
    halo = CONV_SILU_HALO
    rows = max(halo, min(int(rows), _pad(t, halo)) // halo * halo)
    cols = max(LANES, min(int(cols), int(channels)) // LANES * LANES)
    while int(channels) % cols or int(offset) % cols:
        cols -= LANES
    return rows, cols


def conv_silu_vmem_bytes(kernel, rows, cols, taps, itemsize):
    """What one grid step of a short-convolution kernel holds in VMEM:
    the input tile with its halo and the output tile, double-buffered,
    and the weight; the backward's dy tile and second halos beside
    them, the float32 copy of the input tile between its halos that the
    taps are read from, and the weight gradient's accumulator."""
    halo = CONV_SILU_HALO
    need = 2 * (2 * rows + halo) * cols * itemsize + 2 * taps * cols * 4
    if kernel == "conv_silu_bwd":
        need += 2 * (rows + 2 * halo) * cols * itemsize \
            + (rows + 2 * halo) * cols * 4 \
            + (2 + MIN_SUBLANE) * taps * cols * 4
    return need


def conv_silu_vmem_limit(kernel, rows, cols, taps, itemsize):
    """A short-convolution kernel's ``vmem_limit_bytes``
    (:func:`_vmem_limit`)."""
    return _vmem_limit(conv_silu_vmem_bytes(kernel, rows, cols, taps,
                                            itemsize))


# ------------------------------------------- grouped-product resolution

def grouped_mm_shape_supported(m):
    """Whether the grouped-product kernels take a block of ``m`` sorted
    rows: a row tile divides the rows on the sublane grid of a 16-bit
    operand (two :data:`MIN_SUBLANE`); any contraction and any columns
    (a ragged last tile is masked). What ``ops.moe`` asks before it
    takes the kernels; everything else runs ``jax.lax.ragged_dot``."""
    return int(m) > 0 and int(m) % (2 * MIN_SUBLANE) == 0


def _lane_tiles(width, want):
    """A tile of ``width`` columns in whole lane tiles: as few tiles as
    ``want`` allows, each as narrow as covers the width in that many
    (the last one ragged where the width is off the lane grid: 1856 in
    one tile of 1920, two of 1024 or four of 512)."""
    width = int(width)
    cap = max(LANES, int(want) // LANES * LANES)
    tiles = -(-width // cap)
    return _pad(-(-width // tiles), LANES)


def grouped_mm_tiles(kernel, m, k, n, groups, dtype, transpose_rhs=False,
                     interpret=False, tm=None, tk=None, tn=None):
    """The (rows, contraction, columns) tile of ``kernel``
    (``grouped_mm``: lhs (m, k) x rhs[g] (k, n), ``transpose_rhs`` where
    rhs[g] is read as (n, k); ``grouped_mm_t``: the (k, n) product of a
    group's rows of lhs (m, k) and rhs (m, n)): the caller's (a sweep's
    candidates), else the table's, else the default's. Rows legalize to
    the largest multiple of the 16-bit sublane tile that is no larger
    and divides ``m``; the widths by :func:`_lane_tiles` (never a block
    off the lane grid: a tile that runs past its array's edge is masked
    in the kernels where it is contracted, and cut where it is
    written)."""
    if tm is None or tk is None or tn is None:
        sched = kernel_schedule(
            kernel, grouped_mm_shape_key(m, k, n, groups, transpose_rhs),
            str(dtype), resolve_backend(interpret))
        tm = sched["tm"] if tm is None else tm
        tk = sched["tk"] if tk is None else tk
        tn = sched["tn"] if tn is None else tn
    grain = 2 * MIN_SUBLANE
    rows = max(grain, min(int(tm), int(m)) // grain * grain)
    while int(m) % rows:
        rows -= grain
    return rows, _lane_tiles(k, tk), _lane_tiles(n, tn)


def grouped_mm_row_tiles(m, tm, groups):
    """The most row tiles a grouped product's grid visits: every tile
    once, and once more for each group that starts inside a tile (the
    weight gradient's empty groups, visited to write their zeros,
    included)."""
    return int(m) // int(tm) + int(groups) - 1


def grouped_mm_vmem_bytes(kernel, tm, tk, tn, itemsize):
    """What one grid step of a grouped-product kernel holds in VMEM: its
    two operand tiles and its output tile, double-buffered, the float32
    accumulator and product, and the float32 copies the masks make of
    the operand tiles (the weight gradient's: both, and the rows'
    transpose)."""
    if kernel == "grouped_mm_t":
        tiles = 2 * tm * (tk + tn) * itemsize + 2 * tk * tn * itemsize
        work = 2 * tk * tn * 4 + tm * (2 * tk + tn) * 4
    else:
        tiles = 2 * (tm * tk + tk * tn) * itemsize + 2 * tm * tn * itemsize
        work = 2 * tm * tn * 4 + (tm * tk + tk * tn) * 4
    return tiles + work


def grouped_mm_vmem_limit(kernel, tm, tk, tn, itemsize):
    """A grouped-product kernel's ``vmem_limit_bytes``
    (:func:`_vmem_limit`)."""
    return _vmem_limit(grouped_mm_vmem_bytes(kernel, tm, tk, tn, itemsize))


def decode_attn_block_pages(batch, pages, dtype, interpret=False,
                            block_pages=None):
    """Resolved + legalized ``block_pages`` for the paged decode
    attention loop: the largest divisor of the page-table width at or
    under the scheduled value, so the streaming-softmax scan covers the
    table exactly. Any width in [1, pages] is legal (page-granular
    masking handles ragged sequence lengths), so unlike the flash
    resolver this never raises."""
    pages = max(1, int(pages))
    if block_pages is None:
        sched = kernel_schedule(
            "decode_attn", decode_shape_key(batch, pages), str(dtype),
            resolve_backend(interpret))
        block_pages = sched["block_pages"]
    bp = max(1, min(int(block_pages), pages))
    while pages % bp != 0:
        bp -= 1
    return bp


# -------------------------------------------------------------- persistence

def put_entry(path, kernel, shape_key, dtype, backend, sched, **meta):
    """Write/merge one tuned entry into the table at ``path``
    (atomic tmp + rename; schema-versioned). Returns the entry key."""
    entries = load_single_table(path)
    key = entry_key(kernel, shape_key, dtype, backend)
    rec = {"schedule": dict(sched)}
    rec.update({k: v for k, v in sorted(meta.items()) if v is not None})
    entries[key] = rec
    data = {"schema_version": SCHEMA_VERSION,
            "entries": {k: entries[k] for k in sorted(entries)}}
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    with _LOCK:  # force a reload on next read even within one mtime tick
        _TABLE_CACHE["stamp"] = None
    return key


def validate_table(data):
    """Structural validation of a schedule-table store; returns problem
    strings (empty = valid). Checked: schema version, the
    ``kernel|backend|dtype|shape`` key format, known kernels, known
    axes, and values drawn from the declared candidate space (block
    axes accept any sane positive int — legalization may have landed
    between named candidates)."""
    problems = []
    if not isinstance(data, dict):
        return ["schedule table is not a JSON object"]
    if data.get("schema_version") != SCHEMA_VERSION:
        problems.append(
            f"schema_version {data.get('schema_version')!r} != supported "
            f"{SCHEMA_VERSION}")
    entries = data.get("entries")
    if not isinstance(entries, dict):
        problems.append("no 'entries' object")
        return problems
    for key, rec in sorted(entries.items()):
        parts = key.split("|")
        if len(parts) != 4 or not all(parts):
            problems.append(
                f"{key!r} is not a kernel|backend|dtype|shape key")
            continue
        kernel = parts[0]
        axes = SEARCH_SPACE.get(kernel)
        if axes is None:
            problems.append(f"{key}: unknown kernel {kernel!r} "
                            f"(known: {sorted(SEARCH_SPACE)})")
            continue
        sched = rec.get("schedule") if isinstance(rec, dict) else None
        if not isinstance(sched, dict) or not sched:
            problems.append(f"{key}: entry has no 'schedule' dict")
            continue
        for axis, val in sorted(sched.items()):
            cands = axes.get(axis)
            if cands is None:
                problems.append(
                    f"{key}: unknown schedule axis {axis!r} "
                    f"(declared: {sorted(axes)})")
            elif isinstance(cands[0], int):
                if not isinstance(val, int) or isinstance(val, bool) \
                        or not 1 <= val <= 65536:
                    problems.append(
                        f"{key}.{axis} is not a positive block size: "
                        f"{val!r}")
            elif val not in cands:
                problems.append(
                    f"{key}.{axis} value {val!r} not in the declared "
                    f"candidate set {list(cands)}")
    return problems
