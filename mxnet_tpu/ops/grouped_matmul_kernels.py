"""The expert layers' grouped matrix products as a pair of Pallas TPU
kernels.

``ops.moe`` multiplies a block of sorted rows by the matrix of the
expert each row was routed to: rows ``[offsets[g], offsets[g + 1])`` by
``rhs[g]``, with the group sizes known only on the device and rows past
their sum belonging to no group. ``jax.lax.ragged_dot`` states that and
stays what runs wherever these kernels do not; this file is the same
products with tiles the repo chooses (``tune.schedule.grouped_mm_tiles``).

``grouped_matmul`` (kernel ``grouped_matmul``): lhs (m, k) x rhs (G, k,
n) -> (m, n), each row by its group's matrix; ``transpose_rhs`` reads
rhs as (G, n, k) and contracts over its last axis, so the input
gradient takes the weights as they lie. ``grouped_matmul_t`` (kernel
``grouped_matmul_t``): lhs (m, k), rhs (m, n) -> (G, k, n), each
group's rows of lhs transposed times its rows of rhs: the weight
gradient. :func:`grouped_matmul_kernels` is the product under one
``jax.custom_vjp``, its residuals its inputs.

A grid step is a tile of rows, of the contraction and of the output's
columns. Metadata from the group sizes (:func:`_metadata`, after
megablox's ``make_group_metadata``) lists the row tiles the grid visits
in order, each with the group it is visited for: only tiles that hold
rows of a group, and a tile that two groups share once for each of them,
each visit storing its own group's rows (a mask on the rows). The grid's
row axis is as long as that list, so rows past the groups' sum are
never visited. Every width is tiled in whole lane tiles: a ragged last
tile of the contraction is masked in the kernel (a select on both
operands: what lies past the edge is never summed), one of the output's
columns is cut where it is written; so widths off the lane grid need no
padding in HBM. A built kernel, its metadata with it, is traced once a
shape and tile however many call sites a step has: each site binds the
traced operations again, under its own names.

``grouped_matmul`` keeps its output tile in VMEM while consecutive
visits of a row tile store their groups' rows into it, and leaves rows
that no group owns as the buffer held them: not defined. Its callers
select them away (``ops.moe._block_of_rows``, going in and going out).
``grouped_matmul_t`` sums a group's visits in a float32 accumulator and
writes each group's matrix once, after its last visit; a group with no
rows is visited once to write its zeros.

Operands enter in the compute dtype (the two inputs' common type); the
products accumulate in float32 and each result is rounded once, to that
dtype: what ``ragged_dot`` gives. Nothing is named
``remat.KERNEL_RESIDUAL``.

Kernels compile for the TPU or raise; ``interpret=True`` runs them in
Pallas interpret mode on the CPU (the tests' parity runs).
"""
from __future__ import annotations

import functools

__all__ = ["grouped_matmul", "grouped_matmul_t", "grouped_matmul_kernels"]


def _schedule():
    from ..tune import schedule

    return schedule


def _note_build(name, m, k, n, groups, tm, tk, tn, transpose_rhs):
    """One ``kernel.build`` span for each kernel built (as
    ``pallas_kernels._note_build``: nothing while span tracing is off,
    and once however many layers call the cached builder): its shape,
    its tile and the most row tiles its grid can visit."""
    import time

    from ..observability import trace

    if trace.enabled():
        trace.record("kernel.build", time.perf_counter_ns(), 0, kernel=name,
                     m=m, k=k, n=n, groups=groups, tm=tm, tk=tk, tn=tn,
                     transpose_rhs=transpose_rhs,
                     row_tiles=_schedule().grouped_mm_row_tiles(m, tm,
                                                                groups))


def _metadata(sizes, m, tm, visit_empty):
    """From the group sizes (G,) int32 of a block of ``m`` rows in tiles
    of ``tm``: (offsets (G + 1,), the group of each grid step, the row
    tile of each grid step), both lists as long as the most steps
    (``grouped_mm_row_tiles``), and the steps to run. A group visits
    every tile its rows touch, in order; ``visit_empty``: a group with
    no rows visits one tile (the weight gradient's, to write zeros)."""
    import jax.numpy as jnp

    groups = sizes.shape[0]
    tiles = m // tm
    step = jnp.arange(_schedule().grouped_mm_row_tiles(m, tm, groups),
                      dtype=jnp.int32)[:, None]

    def owner(counts, n):
        # the index each step falls in when entry i takes counts[i] steps
        # in turn; the steps past them all keep the last index
        return jnp.minimum(jnp.sum(step >= jnp.cumsum(counts)[None, :],
                                   axis=1, dtype=jnp.int32), n - 1)

    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    empty = sizes == 0
    visits = (ends + tm - 1) // tm - starts // tm
    visits = jnp.where(empty, int(visit_empty), visits)
    # a tile is visited once by the group its first row belongs to, and
    # once more by each group that starts inside it (and, where empty
    # groups are visited, by each of those that lies at it); a start at
    # the block's end falls past the last tile
    again = ~empty & (starts % tm != 0)
    if visit_empty:
        again = again | empty
    per_tile = 1 + jnp.sum(
        again[None, :] & (starts[None, :] // tm
                          == jnp.arange(tiles, dtype=jnp.int32)[:, None]),
        axis=1, dtype=jnp.int32)
    return (offsets, owner(visits, groups), owner(per_tile, tiles)), \
        jnp.sum(visits)


def _rows_of_group(offsets_ref, group_of_ref, tile_of_ref, step, tm, cols):
    """(tm, cols) bool: the rows of this step's tile that belong to its
    group."""
    import jax
    import jax.numpy as jnp

    g = group_of_ref[step]
    row = tile_of_ref[step] * tm + jax.lax.broadcasted_iota(
        jnp.int32, (tm, cols), 0)
    return (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])


def _below(x, limit, axis):
    """``x`` with what lies at or past ``limit`` along ``axis`` zeroed by
    a select (the part of a ragged last tile past the array's edge,
    whatever was read there)."""
    import jax
    import jax.numpy as jnp

    at = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    return jnp.where(at < limit, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _gmm_kernel(offsets_ref, group_of_ref, tile_of_ref, lhs_ref, rhs_ref,
                out_ref, acc_ref, *, tm, tk, tn, k, transpose_rhs):
    """Grid (column tiles, visits, contraction tiles), the contraction
    innermost: lhs_ref (tm, tk), rhs_ref (tk, tn) or, ``transpose_rhs``,
    (tn, tk); out_ref (tm, tn), stored where the contraction ends, the
    group's rows alone; acc_ref (tm, tn) float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    step, k_i = pl.program_id(1), pl.program_id(2)
    last = pl.num_programs(2) - 1
    dims = (((1,), (1,)) if transpose_rhs else ((1,), (0,)), ((), ()))

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(lhs, rhs):
        acc_ref[...] += jax.lax.dot_general(
            lhs, rhs, dims, preferred_element_type=jnp.float32)

    if k % tk:
        @pl.when(k_i < last)
        def _whole():
            accumulate(lhs_ref[...], rhs_ref[...])

        @pl.when(k_i == last)
        def _ragged():
            limit = k - k_i * tk
            accumulate(_below(lhs_ref[...], limit, 1),
                       _below(rhs_ref[...], limit, 1 if transpose_rhs else 0))
    else:
        accumulate(lhs_ref[...], rhs_ref[...])

    @pl.when(k_i == last)
    def _store():
        mine = _rows_of_group(offsets_ref, group_of_ref, tile_of_ref, step,
                              tm, tn)
        out_ref[...] = jnp.where(mine, acc_ref[...],
                                 out_ref[...].astype(jnp.float32)).astype(
                                     out_ref.dtype)


def _tgmm_kernel(offsets_ref, group_of_ref, tile_of_ref, lhs_ref, rhs_ref,
                 out_ref, acc_ref, *, tm, tk, tn):
    """Grid (column tiles, contraction-side tiles, visits), the visits
    innermost: lhs_ref (tm, tk), rhs_ref (tm, tn), their rows outside
    the step's group selected to zero; out_ref (tk, tn) of the group's
    matrix, written after the group's last visit; acc_ref (tk, tn)
    float32."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    step, last = pl.program_id(2), pl.num_programs(2) - 1
    g = group_of_ref[step]
    first_visit = (step == 0) | (group_of_ref[jnp.maximum(step - 1, 0)] != g)
    last_visit = (step == last) | (group_of_ref[jnp.minimum(step + 1, last)]
                                   != g)

    @pl.when(first_visit)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets_ref[g + 1] > offsets_ref[g])
    def _accumulate():
        f32 = jnp.float32
        lhs = jnp.where(_rows_of_group(offsets_ref, group_of_ref,
                                       tile_of_ref, step, tm, tk),
                        lhs_ref[...].astype(f32), 0.0)
        rhs = jnp.where(_rows_of_group(offsets_ref, group_of_ref,
                                       tile_of_ref, step, tm, tn),
                        rhs_ref[...].astype(f32), 0.0)
        acc_ref[...] += jax.lax.dot(
            lhs.T.astype(lhs_ref.dtype), rhs.astype(rhs_ref.dtype),
            preferred_element_type=f32)

    @pl.when(last_visit)
    def _store():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.lru_cache(maxsize=64)
def _build(kind, m, k, n, groups, dtype_str, tm, tk, tn, transpose_rhs,
           interpret):
    """The ``pallas_call`` of one kernel (``kind``: 'gmm', 'tgmm') at one
    shape, dtype and tile, with its metadata from the group sizes: a
    function of (group sizes, lhs, rhs)."""
    import jax
    import jax.extend
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    sched = _schedule()
    dtype = jnp.dtype(dtype_str)
    tiles_k, tiles_n = -(-k // tk), -(-n // tn)
    name = "grouped_matmul" if kind == "gmm" else "grouped_matmul_t"
    kernel_key = "grouped_mm" if kind == "gmm" else "grouped_mm_t"
    _note_build(name, m, k, n, groups, tm, tk, tn, transpose_rhs)
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=sched.grouped_mm_vmem_limit(
            kernel_key, tm, tk, tn, dtype.itemsize))

    if kind == "gmm":
        def lhs_map(n_i, step, k_i, offsets, group_of, tile_of):
            return tile_of[step], k_i

        def rhs_map(n_i, step, k_i, offsets, group_of, tile_of):
            if transpose_rhs:
                return group_of[step], n_i, k_i
            return group_of[step], k_i, n_i

        def out_map(n_i, step, k_i, offsets, group_of, tile_of):
            return tile_of[step], n_i

        rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
        body = functools.partial(_gmm_kernel, tm=tm, tk=tk, tn=tn, k=k,
                                 transpose_rhs=transpose_rhs)
        in_specs = [pl.BlockSpec((tm, tk), lhs_map),
                    pl.BlockSpec(rhs_block, rhs_map)]
        out_spec = pl.BlockSpec((tm, tn), out_map)
        out_shape = jax.ShapeDtypeStruct((m, n), dtype)
        acc = pltpu.VMEM((tm, tn), jnp.float32)
    else:
        def lhs_map(n_i, k_i, step, offsets, group_of, tile_of):
            return tile_of[step], k_i

        def rhs_map(n_i, k_i, step, offsets, group_of, tile_of):
            return tile_of[step], n_i

        def out_map(n_i, k_i, step, offsets, group_of, tile_of):
            return group_of[step], k_i, n_i

        body = functools.partial(_tgmm_kernel, tm=tm, tk=tk, tn=tn)
        in_specs = [pl.BlockSpec((tm, tk), lhs_map),
                    pl.BlockSpec((tm, tn), rhs_map)]
        out_spec = pl.BlockSpec((None, tk, tn), out_map)
        out_shape = jax.ShapeDtypeStruct((groups, k, n), dtype)
        acc = pltpu.VMEM((tk, tn), jnp.float32)

    def call(sizes, lhs, rhs):
        (offsets, group_of, tile_of), steps = _metadata(
            sizes, m, tm, visit_empty=kind == "tgmm")
        grid = (tiles_n, steps, tiles_k) if kind == "gmm" \
            else (tiles_n, tiles_k, steps)
        return pl.pallas_call(
            body,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3, grid=grid, in_specs=in_specs,
                out_specs=out_spec, scratch_shapes=[acc]),
            out_shape=out_shape,
            compiler_params=params,
            cost_estimate=pl.CostEstimate(
                flops=2 * m * k * n, transcendentals=0,
                bytes_accessed=(m * k + groups * k * n + m * n)
                * dtype.itemsize),
            interpret=interpret,
            name=name,
        )(offsets, group_of, tile_of, lhs, rhs)

    # traced once a shape and tile, however many call sites (layers, the
    # block rule's two branches, the backward's) a step has: each site
    # binds the traced ops again under its own names
    rhs_shape = (m, n) if kind == "tgmm" else (
        (groups, n, k) if transpose_rhs else (groups, k, n))
    traced = jax.extend.core.jaxpr_as_fun(jax.make_jaxpr(call)(
        jax.ShapeDtypeStruct((groups,), jnp.int32),
        jax.ShapeDtypeStruct((m, k), dtype),
        jax.ShapeDtypeStruct(rhs_shape, dtype)))
    return lambda sizes, lhs, rhs: traced(sizes, lhs, rhs)[0]


def _tiles(kernel, m, k, n, groups, dtype, transpose_rhs, interpret, tiles):
    want = dict(zip(("tm", "tk", "tn"), tiles or (None, None, None)))
    return _schedule().grouped_mm_tiles(
        kernel, m, k, n, groups, str(dtype), transpose_rhs=transpose_rhs,
        interpret=interpret, **want)


def _check(m):
    sched = _schedule()
    if not sched.grouped_mm_shape_supported(m):
        raise sched.ScheduleError(
            f"grouped matmul kernels: a block of {m} rows (the rows lie on "
            f"the {2 * sched.MIN_SUBLANE}-row sublane grid)")


def grouped_matmul(lhs, rhs, group_sizes, transpose_rhs=False,
                   interpret=False, tiles=None):
    """lhs (m, k) x rhs (G, k, n) (``transpose_rhs``: (G, n, k)) ->
    (m, n) in lhs's dtype, which rhs shares: row r of group g (rows
    ``[sum(sizes[:g]), sum(sizes[:g + 1]))``) times rhs[g]; rows past
    ``sum(group_sizes)`` are not defined. ``tiles`` (tm, tk, tn)
    overrides the schedule's (a sweep's candidates)."""
    import jax.numpy as jnp

    m, k = lhs.shape
    groups = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    _check(m)
    tm, tk, tn = _tiles("grouped_mm", m, k, n, groups, lhs.dtype,
                        transpose_rhs, interpret, tiles)
    return _build("gmm", m, k, n, groups, str(lhs.dtype), tm, tk, tn,
                  bool(transpose_rhs), bool(interpret))(
                      group_sizes.astype(jnp.int32), lhs, rhs)


def grouped_matmul_t(lhs, rhs, group_sizes, interpret=False, tiles=None):
    """lhs (m, k), rhs (m, n) -> (G, k, n) in lhs's dtype, which rhs
    shares: for each group g, its rows of lhs transposed times its rows
    of rhs; zeros for a group with no rows. Rows past
    ``sum(group_sizes)`` are never read into a sum."""
    import jax.numpy as jnp

    m, k = lhs.shape
    n = rhs.shape[1]
    groups = group_sizes.shape[0]
    _check(m)
    tm, tk, tn = _tiles("grouped_mm_t", m, k, n, groups, lhs.dtype, False,
                        interpret, tiles)
    return _build("tgmm", m, k, n, groups, str(lhs.dtype), tm, tk, tn,
                  False, bool(interpret))(group_sizes.astype(jnp.int32),
                                          lhs, rhs)


def grouped_matmul_kernels(lhs, rhs, group_sizes, interpret=False,
                           tiles=None, bwd_tiles=None, wgrad_tiles=None):
    """``jax.lax.ragged_dot(lhs, rhs, group_sizes)`` through the kernels,
    differentiable in lhs and rhs: the forward ``grouped_matmul``; the
    input gradient ``grouped_matmul`` of the output's gradient on rhs
    read transposed, the weight gradient ``grouped_matmul_t``. Rows past
    ``sum(group_sizes)`` of the result and of lhs's gradient are not
    defined. ``tiles`` / ``bwd_tiles`` / ``wgrad_tiles`` override the
    three kernels' schedules. Raises ``ScheduleError`` for a block of
    rows the kernels do not take
    (``tune.schedule.grouped_mm_shape_supported``)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.result_type(lhs, rhs)
    lhs, rhs = lhs.astype(dtype), rhs.astype(dtype)
    sizes = jnp.asarray(group_sizes).astype(jnp.int32)

    def forward(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, sizes, interpret=interpret,
                              tiles=tiles)

    def f_fwd(lhs, rhs, sizes):
        return forward(lhs, rhs, sizes), (lhs, rhs, sizes)

    def f_bwd(res, dout):
        lhs, rhs, sizes = res
        dout = dout.astype(dtype)
        dlhs = grouped_matmul(dout, rhs, sizes, transpose_rhs=True,
                              interpret=interpret, tiles=bwd_tiles)
        drhs = grouped_matmul_t(lhs, dout, sizes, interpret=interpret,
                                tiles=wgrad_tiles)
        return dlhs, drhs, None

    f = jax.custom_vjp(forward)
    f.defvjp(f_fwd, f_bwd)
    return f(lhs, rhs, sizes)
