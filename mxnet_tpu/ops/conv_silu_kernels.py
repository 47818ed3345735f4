"""The short causal convolution with its SiLU as a pair of Pallas TPU
kernels.

``ops.linear_attention.causal_conv_silu`` states the mathematics
(``silu(causal_conv1d(x[..., :C], w))`` handed on in column parts) and
keeps its ``jax.numpy`` form for every place these kernels do not run;
this file is the same computation as one pass over its input each way,
``causal_conv_silu_fwd`` and ``causal_conv_silu_bwd`` under one
``jax.custom_vjp`` (:func:`causal_conv_silu_kernels`).

A call convolves one part: a column range of the input ``x`` (B, T, W),
read in place through the index map's column offset, to an array of its
own (B, T, part). A grid step is a tile of rows and channels
(``tune.schedule.conv_silu_tile``), and beside its tile it reads one
block of ``CONV_SILU_HALO`` rows: the rows before the tile going
forward, zeros before the sequence; going back the rows after it of both
the input and the output's gradient, because the input's gradient at a
token sums the taps of the ``K - 1`` tokens after it. Tile and halo are
laid in a float32 scratch one after the other and each tap is a load of
the tile's rows shifted by its distance, so nothing is padded, shifted
or sliced in HBM. The K taps, the SiLU and its derivative are float32;
each result is rounded once, to the input's dtype.

The backward recomputes the pre-activation of its tile and of the halo
after it, writes ``d pre = dy silu'(pre)`` to scratch, and from it the
input's gradient (the taps transposed) and the weight's (K, channels),
summed over the rows in a float32 accumulator that lives across the row
tiles of a column (the row axis is the grid's last and sequential). The
parts' input gradients are written into ONE (B, T, W) array, each call
its own columns (``input_output_aliases`` hands the array from call to
call), and the gradient of the columns past the parts, which the
convolution does not touch, is laid into the rest of it.

A sequence that is not a multiple of the row tile hangs over the end of
its last tile: going forward what is read there only reaches rows that
are not written; going back those rows are masked.

A per-channel bias, where the call has one (Mamba-2's short
convolution), is added to the taps' sum before the SiLU: it travels as
one more row of the weight block, read like a tap whose input is a row
of ones, so its gradient is the sum of ``d pre`` over the rows, kept in
the weight gradient's accumulator beside the taps'. A call without one
builds the kernels as they are without the row.

None of the forward's results is named ``remat.KERNEL_RESIDUAL``: it is
one pass over its input, cheaper to run again than to keep.

Kernels compile for the TPU or raise; ``interpret=True`` runs them in
Pallas interpret mode on the CPU (the tests' parity runs).
"""
from __future__ import annotations

import functools

__all__ = ["causal_conv_silu_kernels"]

_SUB = 16      # rows of one pass of a kernel's loops: a 16-bit sublane tile
_TILE = 8      # rows of a float32 sublane tile: what a pass hands the next
_WIDE = 512    # lanes of one pass, however wide the tile


def _schedule():
    from ..tune import schedule

    return schedule


def _note_build(name, b, t, channels, taps, rows, cols):
    """One ``kernel.build`` span for each kernel built (as
    ``pallas_kernels._note_build``: nothing while span tracing is off,
    and once however many layers call the cached builder)."""
    import time

    from ..observability import trace

    if trace.enabled():
        trace.record("kernel.build", time.perf_counter_ns(), 0, kernel=name,
                     b=b, t=t, channels=channels, taps=taps, rows=rows,
                     cols=cols)


def _taps_of(w_ref, taps, lanes):
    """The weight's rows (taps, cols) float32 at the pass's ``lanes``,
    each spread over the rows of a pass."""
    import jax.numpy as jnp

    return [jnp.broadcast_to(w_ref[j:j + 1, lanes], (_SUB, lanes.size))
            for j in range(taps)]


def _passes(cols):
    """The column ranges of a tile's passes: ``_WIDE`` lanes each, so
    that a pass's values stay in registers whatever the tile's width."""
    from jax.experimental import pallas as pl

    wide = min(_WIDE, cols)
    return [pl.ds(c0, wide) for c0 in range(0, cols, wide)]


def _sigmoid(x):
    import jax.numpy as jnp

    return 1.0 / (1.0 + jnp.exp(-x))


def _rows_at(first, n):
    """Global row numbers (n, 1) of ``n`` rows from ``first``."""
    import jax
    import jax.numpy as jnp

    return first + jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)


def _behind(before, rows, s):
    """``rows`` (a pass, float32) each replaced by the row ``s`` above
    it, the first of them from ``before``, the 8 rows above the pass:
    the sublane tiles stay whole and one rotation moves the rows (a ref
    read at a row off the tile grid is not a load Mosaic takes)."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    if s == 0:
        return rows
    return pltpu.roll(jnp.concatenate([before, rows], axis=0), s, 0)[_TILE:]


def _ahead(rows, after, s):
    """:func:`_behind` the other way: each row replaced by the row ``s``
    below it, the last of them from ``after``, the 8 rows below."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    if s == 0:
        return rows
    both = jnp.concatenate([rows, after], axis=0)
    return pltpu.roll(both, both.shape[0] - s, 0)[:_SUB]


def _conv(inputs, w):
    """The taps summed: ``inputs[j]`` the pass's rows moved to tap j."""
    pre = inputs[0] * w[0]
    for x, w_j in zip(inputs[1:], w[1:]):
        pre = pre + x * w_j
    return pre


def _fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, taps, rows, cols,
                biased=False):
    """Grid (B, column tiles, row tiles): x_ref, o_ref (1, rows, cols);
    prev_ref (1, halo, cols), the rows before the tile; w_ref (taps,
    cols) float32, and the bias as one more row where ``biased``. A pass
    hands the next its last 8 rows."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    for lanes in _passes(cols):
        w = _taps_of(w_ref, taps + biased, lanes)

        def one_pass(r, before, lanes=lanes, w=w):
            r0 = pl.multiple_of(r * _SUB, _SUB)
            x = x_ref[0, pl.ds(r0, _SUB), lanes].astype(f32)
            pre = _conv([_behind(before, x, taps - 1 - j)
                         for j in range(taps)], w[:taps])
            if biased:
                pre = pre + w[taps]
            o_ref[0, pl.ds(r0, _SUB), lanes] = (pre * _sigmoid(pre)).astype(
                o_ref.dtype)
            return x[_SUB - _TILE:]

        jax.lax.fori_loop(
            0, rows // _SUB, one_pass,
            jnp.where(pl.program_id(2) == 0, 0.0,
                      prev_ref[0, -_TILE:, lanes].astype(f32)))


def _bwd_kernel(*refs, taps, rows, cols, t, aliased, biased=False):
    """Grid as the forward's, the row axis sequential: x_ref, dy_ref,
    dx_ref (1, rows, cols); prev_ref, next_ref, dnext_ref (1, halo,
    cols): the input's rows before and after the tile, dy's after it;
    w_ref (taps, cols) float32; dw_ref (1, taps, cols) float32, written
    at the column's last row tile; xbuf (halo + rows + halo, cols)
    float32 scratch, the input [before | tile | after]; acc (taps, 8,
    cols) float32 scratch. ``biased``: w_ref, dw_ref and acc have one
    row more, the bias's. The passes run from the tile's last to its
    first, each handing the one above it the first 8 rows of its
    ``d pre``. ``aliased``: the array dx is written into comes first
    among the operands, untouched."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    x_ref, prev_ref, next_ref, dy_ref, dnext_ref, w_ref, dx_ref, dw_ref, \
        xbuf, acc = refs[1:] if aliased else refs
    f32 = jnp.float32
    halo = _schedule().CONV_SILU_HALO
    i, last = pl.program_id(2), pl.num_programs(2) - 1
    first_row = i * rows
    ragged = t % rows != 0      # the last tile hangs over the sequence's end
    n = rows // _SUB

    @pl.when(i == 0)
    def _first():
        acc[...] = jnp.zeros_like(acc)

    def columns(lanes):
        w = _taps_of(w_ref, taps + biased, lanes)
        # the input in float32; zeros past the sequence's end (what is
        # read there may be anything)
        xbuf[0:halo, lanes] = jnp.where(i == 0, 0.0,
                                        prev_ref[0, :, lanes].astype(f32))

        def fill(r, carry):
            r0 = pl.multiple_of(r * _SUB, _SUB)
            x = x_ref[0, pl.ds(r0, _SUB), lanes].astype(f32)
            if ragged:
                x = jnp.where(_rows_at(first_row + r0, _SUB) < t, x, 0.0)
            xbuf[pl.ds(halo + r0, _SUB), lanes] = x
            return carry

        jax.lax.fori_loop(0, n, fill, 0)
        xbuf[halo + rows:, lanes] = jnp.where(
            _rows_at(first_row + rows, halo) < t,
            next_ref[0, :, lanes].astype(f32), 0.0)

        def through_silu(r0, dy, masked):
            """The pass at ``r0``: its rows moved to each tap, d pre."""
            at = pl.multiple_of(halo + r0, _SUB)
            before = xbuf[pl.ds(at - _TILE, _TILE), lanes]
            x = xbuf[pl.ds(at, _SUB), lanes]
            inputs = [_behind(before, x, taps - 1 - j) for j in range(taps)]
            pre = _conv(inputs, w[:taps])
            if biased:
                pre = pre + w[taps]
            sig = _sigmoid(pre)
            d = dy.astype(f32) * (sig * (1.0 + pre * (1.0 - sig)))
            if masked:
                d = jnp.where(_rows_at(first_row + r0, _SUB) < t, d, 0.0)
            return inputs, d

        def back(k, carry):
            after, sums = carry
            r0 = pl.multiple_of((n - 1 - k) * _SUB, _SUB)
            inputs, d = through_silu(
                r0, dy_ref[0, pl.ds(r0, _SUB), lanes], ragged)
            dx = _conv([_ahead(d, after, taps - 1 - j) for j in range(taps)],
                       w[:taps])
            dx_ref[0, pl.ds(r0, _SUB), lanes] = dx.astype(dx_ref.dtype)
            # the bias's input is a row of ones: its term is d itself
            sums = tuple(
                s + sum((d if x is None else d * x)[m:m + _TILE]
                        for m in range(0, _SUB, _TILE))
                for s, x in zip(sums, inputs + [None] * biased))
            return d[:_TILE], sums

        _, below = through_silu(rows, dnext_ref[0, :_SUB, lanes], True)
        _, sums = jax.lax.fori_loop(
            0, n, back,
            (below[:_TILE], tuple(jnp.zeros((_TILE, lanes.size), f32)
                                  for _ in range(taps + biased))))
        for j, s in enumerate(sums):
            acc[j, :, lanes] += s

    for lanes in _passes(cols):
        columns(lanes)

    @pl.when(i == last)
    def _last():
        dw_ref[0] = jnp.sum(acc[...], axis=1)


@functools.lru_cache(maxsize=64)
def _build(kind, b, t, width, offset, channels, taps, rows, cols, dtype_str,
           aliased, interpret, biased=False):
    """The ``pallas_call`` of one kernel (``kind``: 'fwd', 'bwd') over
    the ``channels`` columns from ``offset`` of a (B, T, ``width``)
    input, at one dtype and tile. The backward writes its columns of a
    (B, T, ``width``) gradient: a new array, or (``aliased``) its first
    operand. ``biased``: the weight block carries the bias as one more
    row, and the backward's weight gradient one more row, the bias's."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sched = _schedule()
    halo = sched.CONV_SILU_HALO
    dtype = jnp.dtype(dtype_str)
    f32 = jnp.float32
    per, first = rows // halo, offset // cols
    last_halo = -(-t // halo) - 1
    back = kind == "bwd"
    name = "conv_silu_" + kind
    _note_build("causal_" + name, b, t, channels, taps, rows, cols)

    def tile(at):
        return pl.BlockSpec((1, rows, cols), lambda n, c, i: (n, i, at + c))

    def before(at):
        return pl.BlockSpec(
            (1, halo, cols),
            lambda n, c, i: (n, jnp.maximum(i * per - 1, 0), at + c))

    def after(at):
        return pl.BlockSpec(
            (1, halo, cols),
            lambda n, c, i: (n, jnp.minimum((i + 1) * per, last_halo),
                             at + c))

    rows_w = taps + biased      # the weight block's rows
    w_spec = pl.BlockSpec((rows_w, cols), lambda n, c, i: (0, c))
    part = jax.ShapeDtypeStruct((b, t, channels), dtype)
    params = dict(
        grid=(b, channels // cols, -(-t // rows)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=sched.conv_silu_vmem_limit(
                name, rows, cols, rows_w, dtype.itemsize)),
        interpret=interpret)
    if not back:
        return pl.pallas_call(
            functools.partial(_fwd_kernel, taps=taps, rows=rows, cols=cols,
                              biased=biased),
            in_specs=[tile(first), before(first), w_spec],
            out_specs=tile(0), out_shape=part, name="causal_" + name,
            **params)
    in_specs = [tile(first), before(first), after(first), tile(0), after(0),
                w_spec]
    if aliased:
        in_specs.insert(0, pl.BlockSpec(memory_space=pl.ANY))
        params["input_output_aliases"] = {0: 0}
    return pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, rows=rows, cols=cols, t=t,
                          aliased=aliased, biased=biased),
        in_specs=in_specs,
        out_specs=[tile(first),
                   pl.BlockSpec((1, rows_w, cols), lambda n, c, i: (n, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((b, t, width), dtype),
                   jax.ShapeDtypeStruct((b, rows_w, channels), f32)],
        scratch_shapes=[pltpu.VMEM((rows + 2 * halo, cols), f32),
                        pltpu.VMEM((rows_w, _TILE, cols), f32)],
        name="causal_" + name, **params)


def causal_conv_silu_kernels(x, weight, parts, interpret=False, rows=None,
                             cols=None, bwd_rows=None, bwd_cols=None,
                             bias=None):
    """``causal_conv_silu`` through the kernels: same arguments, same
    results (a tuple: the parts, then the columns of ``x`` past them),
    differentiable in ``x``, ``weight`` and ``bias`` (C,) where there is
    one. ``rows`` / ``cols`` and ``bwd_rows`` / ``bwd_cols`` override
    the schedules' tiles (the tuning search's candidates). Raises
    ``ScheduleError`` for a shape the kernels do not take
    (``tune.schedule.conv_silu_shape_supported``)."""
    import jax
    import jax.numpy as jnp

    sched = _schedule()
    parts = tuple(int(p) for p in parts)
    b, t, width = x.shape
    channels, taps = weight.shape
    if channels != sum(parts) or not sched.conv_silu_shape_supported(
            parts, taps, width):
        raise sched.ScheduleError(
            f"causal conv + SiLU kernels: unsupported shape, parts={parts} "
            f"of {width} columns, weight {weight.shape} (every part on the "
            f"{sched.LANES}-lane grid, 1 to {sched.CONV_SILU_MAX_TAPS} taps)")
    dtype = x.dtype
    interpret = bool(interpret)
    biased = bias is not None
    offsets = [sum(parts[:n]) for n in range(len(parts))]

    def calls(kind, want_rows, want_cols):
        """[(a part's call, its first column, its width)], the
        backward's calls after the first handed the first's array."""
        out = []
        for n, (at, size) in enumerate(zip(offsets, parts)):
            tile = sched.conv_silu_tile(
                "conv_silu_" + kind, b, t, size, at, taps, str(dtype),
                interpret=interpret, rows=want_rows, cols=want_cols)
            out.append((_build(kind, b, t, width, at, size, taps, *tile,
                               str(dtype), kind == "bwd" and n > 0,
                               interpret, biased), at, size))
        return out

    def by_tap(w, at, size):
        return w[at:at + size].astype(jnp.float32).T

    def forward(x, w):
        return tuple(call(x, x, by_tap(w, at, size)) for call, at, size
                     in calls("fwd", rows, cols)) \
            + (x[..., channels:],)

    def f_fwd(x, w):
        return forward(x, w), (x, w)

    def f_bwd(res, douts):
        x, w = res
        dx, dws = None, []
        for (call, at, size), dy in zip(
                calls("bwd", bwd_rows, bwd_cols), douts):
            held = () if dx is None else (dx,)
            dy = dy.astype(dtype)
            dx, dw = call(*held, x, x, x, dy, dy, by_tap(w, at, size))
            dws.append(jnp.sum(dw, axis=0).T)
        if width > channels:
            dx = jax.lax.dynamic_update_slice(
                dx, douts[-1].astype(dtype), (0, 0, channels))
        return dx, jnp.concatenate(dws, axis=0).astype(w.dtype)

    f = jax.custom_vjp(forward)
    f.defvjp(f_fwd, f_bwd)
    if not biased:
        return f(x, weight)
    # the bias as the weight's last column, a tap the kernels read as one
    # over a row of ones; jax splits the gradient back along the column
    return f(x, jnp.concatenate(
        [weight, jnp.asarray(bias).astype(weight.dtype)[:, None]], axis=1))
