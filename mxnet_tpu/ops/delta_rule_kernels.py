"""The gated delta rule as a pair of Pallas TPU kernels.

``ops.linear_attention.gated_delta_rule`` states the mathematics (the
chunked WY form) and keeps its ``jax.numpy`` form for every place these
kernels do not run; this file is the same algorithm with the state and
every chunk's tensors in VMEM. ``gated_delta_rule_fwd`` walks the
chunks of a sequence in order, ``gated_delta_rule_bwd`` walks them back,
both under one ``jax.custom_vjp`` (:func:`gated_delta_rule_kernels`).

A grid step is one key head, the ``Hv / Hk`` value heads it serves and
a block of chunks (``tune.schedule.delta_rule_chunks``), the chunk axis
sequential. q, k, v and o are column blocks of the projections viewed as
(B, T, heads x size): (rows of the block, one head's lanes) at column
``h``, so nothing is transposed, the key head is read once for its
value heads and never repeated, and o lands in (B, T, Hv x Dv). As many
of those value heads as fill the MXU's 128 rows with their chunks
(``tune.schedule.delta_rule_heads``: two at chunks of 64) run as ONE
problem of ``heads x chunk`` rows, block diagonal by head
(:class:`_Group`), so every product of the chunk program serves them
all; g and beta (and dg, dbeta) travel with a group's chunks side by
side along lanes, (B, Hv / heads, steps, chunks a step, heads x chunk).
Per chunk and group, in VMEM: the L2 norm of q and k, the in-chunk
``cumsum`` of g (a product with a triangle of ones), the decay mask,
``k k^T`` and ``q k^T``, the unit lower-triangular system ``I + A`` and
its inverse, the WY factors ``u`` and ``w``, ``v_new = u - w S``,
``o = (q e^gc) S + (qk * decay) v_new`` and ``S <- e^g_end S +
(k e^(g_end - gc))^T v_new`` with each head's ``S`` (Dk x Dv, float32)
in scratch, zero at the first chunk.

There is no row-by-row substitution: the inverse of ``I + A`` is built
from those of its diagonal blocks, doubled five times
(:func:`unit_lower_inverses`), ten products for a chunk of 64.

Precision, as ``gated_delta_rule`` states it: decays, the inverse and
its products with the right-hand sides (``Precision.HIGHEST``: Mosaic
otherwise runs a float32 product in one bf16 pass, as XLA does on the
chip), the state and every accumulation are float32; ``k k^T``,
``q k^T`` and the products of ``q e^gc`` and ``k e^(g_end - gc)`` take
their operands in ``v``'s dtype; what comes out of the solve (u, w,
v_new) stays float32.

The forward under differentiation also writes the state entering each
chunk (64 KB a chunk and value head, float32) and the chunk's inverse
(64 KB a chunk and pair of heads), which is all the backward keeps: it
recomputes a chunk's other factors from q, k, v, g, beta, holds dS in
scratch, and writes dq, dk, dv, dg, dbeta in the inputs' layouts, the
gradient of the L2 norm and of the ``cumsum`` included.

Kernels compile for the TPU or raise; ``interpret=True`` runs them in
Pallas interpret mode on the CPU (the tests' parity runs). The chunks
of a grid step are unrolled on purpose: the state is the only thing a
chunk hands the next, so every chunk's factors and inverse are written
before the first state update and the compiler runs them side by
side.
"""
from __future__ import annotations

import functools

from ..remat import kernel_residuals

__all__ = ["gated_delta_rule_kernels", "unit_lower_inverse"]

_EPS = 1e-6      # the L2 norm's, as ``linear_attention._l2norm``


def _schedule():
    from ..tune import schedule

    return schedule


def _dot(a, b, dims, precise=False):
    """``dot_general`` with float32 accumulation; operands of different
    dtypes meet in float32 (Mosaic takes no mixed product). ``precise``
    is the solve's: float32 all the way."""
    import jax
    import jax.numpy as jnp

    if a.dtype != b.dtype:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=jax.lax.Precision.HIGHEST if precise else None,
        preferred_element_type=jnp.float32)


_AB = ((1,), (0,))      # a @ b
_ABT = ((1,), (1,))     # a @ b.T
_ATB = ((0,), (0,))     # a.T @ b


def unit_lower_inverses(systems, block):
    """``(I + a)^-1`` of each (n, n) float32 ``a`` in ``systems``, every
    one block diagonal in blocks of ``block`` rows and strictly lower
    triangular inside them, by blocks instead of ``block`` sequential
    rows. With the inverses ``T`` of the diagonal blocks of size s in
    place, the block of size 2 s is ``[[T11, 0], [-T22 a21 T11, T22]]``:
    ``T <- T - T a_off T`` with ``a_off`` the lower-left halves of the
    2 s blocks, two (n, n) products at ``Precision.HIGHEST`` a doubling
    and none for the first (``T = I - a_off``), ten for blocks of 64.
    Every intermediate is a block of the inverse itself: as stable as
    the substitution, where the Neumann series (ten products too)
    cancels powers of ``a`` that outgrow float32 once a chunk's keys
    resemble each other. The systems are independent and each doubling
    is written for all of them in turn, so that their products stand
    next to each other in the program."""
    import jax
    import jax.numpy as jnp

    n = systems[0].shape[-1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    # positions inside the ``block`` rows, and whether one block
    at_r, at_c = (x - sum(jnp.where(x >= m, block, 0)
                          for m in range(block, n, block))
                  for x in (rows, cols))
    same = rows - at_r == cols - at_c
    invs = [(rows == cols).astype(jnp.float32)] * len(systems)
    for level in range(max(block - 1, 1).bit_length()):
        # rows of the lower half, columns of the upper half of a 2 s block
        s = 1 << level
        half = same & ((at_r >> (level + 1)) == (at_c >> (level + 1))) \
            & ((at_r & s) != 0) & ((at_c & s) == 0)
        offs = [jnp.where(half, a, 0.0) for a in systems]
        if level == 0:
            invs = [inv - off for inv, off in zip(invs, offs)]
        else:
            right = [_dot(off, inv, _AB, precise=True)
                     for off, inv in zip(offs, invs)]
            invs = [inv - _dot(inv, x, _AB, precise=True)
                    for inv, x in zip(invs, right)]
    return invs


def unit_lower_inverse(a):
    """``(I + a)^-1`` for one strictly lower-triangular (C, C) float32
    ``a`` (:func:`unit_lower_inverses`)."""
    return unit_lower_inverses([a], a.shape[-1])[0]


class _Group:
    """The value heads of one key head that share a chunk's tiles, as
    one problem of ``n = heads x chunk`` rows, block diagonal by head:
    with two heads and chunks of 64 every product of the chunk program
    fills the MXU's 128 rows. Holds the masks of the (n, n) tile and
    the moves between a value along lanes, (1, n), and along sublanes,
    (n, 1)."""

    def __init__(self, heads, chunk):
        import jax
        import jax.numpy as jnp

        n = heads * chunk
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)

        def head_of(x):
            return sum(jnp.where(x >= m, 1, 0)
                       for m in range(chunk, n, chunk)) if heads > 1 else 0

        same = head_of(rows) == head_of(cols)
        self.heads, self.chunk, self.n, self.lane = heads, chunk, n, lane
        self.eye = rows == cols
        self.lower, self.strict = same & (rows >= cols), same & (rows > cols)
        # the tile that hands the last token of a head's chunk, along
        # lanes, to every row of the head
        self.to_last = same & (sum(
            jnp.where(lane == h * chunk + chunk - 1, 1, 0)
            for h in range(heads)) == 1)

    def col(self, row):
        import jax.numpy as jnp

        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, col):
        import jax.numpy as jnp

        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def rows_of(self, x, h):
        """Head ``h``'s rows of an (n, ..) value."""
        return x[h * self.chunk:(h + 1) * self.chunk]

    def cumsum(self, rows, reverse=False):
        """Running sums of (m, n) rows inside each head's chunk, from
        the left, or (``reverse``) from the right: one product with a
        triangle of ones, float32 all the way."""
        import jax.numpy as jnp

        ones = jnp.where(self.lower, 1.0, 0.0)     # [i, j] = 1, i >= j
        return _dot(rows, ones, _AB if reverse else _ABT, precise=True)

    def decays(self, gc_row):
        """A chunk's decays from its running sums of g along lanes: the
        (n, n) mask (0 above the diagonal and between heads, masked
        before the exp: the differences there are positive), and along
        sublanes e^gc, e^(g_end - gc) and e^g_end."""
        import jax.numpy as jnp

        gc = self.col(gc_row)
        g_end = jnp.sum(jnp.where(self.to_last, gc_row, 0.0), axis=1,
                        keepdims=True)
        return (jnp.exp(jnp.where(self.lower, gc - gc_row, -jnp.inf)),
                jnp.exp(gc), jnp.exp(g_end - gc), jnp.exp(g_end))


def _unit(x, scale):
    """Rows of ``x`` L2-normalised in float32 (times ``scale``), and the
    reciprocal norms."""
    import jax
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _EPS)
    return x * (r * scale), r * scale


def _unit_back(d, xn, r, inv_scale_sq):
    """The gradient of :func:`_unit`'s rows: ``d`` that of ``xn = scale
    x_hat`` with ``x_hat = x r / scale`` the unit rows, so
    ``dx = r (d - xn <d, xn> / scale^2)``."""
    import jax.numpy as jnp

    return r * (d - xn * (jnp.sum(d * xn, axis=1, keepdims=True)
                          * inv_scale_sq))


def _stack(parts):
    import jax.numpy as jnp

    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _chunk_factors(grp, qn, kn, v, gc_row, beta_row, mm):
    """What a chunk of one group computes before it meets the state:
    ``kn``, ``qn`` (C, Dk) float32 unit rows, ``v`` (n, Dv), the group's
    running decays and write strengths along lanes. Returns a dict of
    the group's (n, ..) tiles."""
    import jax.numpy as jnp

    k2, q2 = _stack([kn] * grp.heads), _stack([qn] * grp.heads)
    kb = k2.astype(mm)
    decay, e_gc, e_out, e_end = grp.decays(gc_row)
    beta = grp.col(beta_row)
    kk = _dot(kb, kb, _ABT)
    v = v.astype(jnp.float32)
    return dict(
        k2=k2, q2=q2, kb=kb, qb=q2.astype(mm), kk=kk, v=v, beta=beta,
        decay=decay, e_gc=e_gc, e_out=e_out, e_end=e_end,
        system=jnp.where(grp.strict, kk * beta * decay, 0.0),
        rhs=jnp.concatenate([v * beta, k2 * (beta * e_gc)], axis=1))


def _local(f):
    """``(q k^T) * decay`` (n, n): what a chunk's tokens read of each
    other's new values."""
    return _dot(f["qb"], f["kb"], _ABT) * f["decay"]


def _through_state(grp, f, states, mm, dv):
    """``v_new`` (n, Dv) float32 and ``q e^gc S`` (n, Dv) of a chunk
    whose factors ``f`` hold ``uw = inv rhs``: one product a head, the
    head's ``w`` rows above its ``q e^gc`` rows against its state."""
    import jax.numpy as jnp

    c = grp.chunk
    q_in = (f["q2"] * f["e_gc"]).astype(mm).astype(jnp.float32)
    v_new, read = [], []
    for h, s in enumerate(states):
        both = _dot(jnp.concatenate(
            [grp.rows_of(f["uw"][:, dv:], h), grp.rows_of(q_in, h)], axis=0),
            s, _AB)
        v_new.append(grp.rows_of(f["uw"][:, :dv], h) - both[:c])
        read.append(both[c:])
    return _stack(v_new), _stack(read)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, chunk, nb,
                rep, heads, dk, dv, save):
    """Grid (B, Hk, steps): q_ref, k_ref (1, nb x C, Dk); v_ref, o_ref
    (1, nb x C, rep x Dv); g_ref, b_ref (1, groups, 1, nb, n) float32,
    a group's ``heads`` value heads side by side along lanes; with
    ``save`` s_ref (1, rep, nb, Dk, Dv) and t_ref (1, groups, nb, n, n)
    float32 going out; state (rep, Dk, Dv) float32 scratch."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    if save:
        s_ref, t_ref, state = rest
    else:
        state, = rest
    mm = v_ref.dtype
    grp = _Group(heads, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _first():
        state[...] = jnp.zeros_like(state)

    # before the state: every chunk's and group's factors, the inverses
    # of all of them doubled side by side
    units = [(_unit(q_ref[0, c * chunk:(c + 1) * chunk, :], dk ** -0.5)[0],
              _unit(k_ref[0, c * chunk:(c + 1) * chunk, :], 1.0)[0])
             for c in range(nb)]
    gcs = [grp.cumsum(g_ref[0, gi, 0]) for gi in range(rep // heads)]
    todo = []
    for c in range(nb):
        rows = slice(c * chunk, (c + 1) * chunk)
        for gi in range(rep // heads):
            v = _stack([v_ref[0, rows, r * dv:(r + 1) * dv]
                        for r in range(gi * heads, (gi + 1) * heads)])
            todo.append((gi, c, _chunk_factors(
                grp, *units[c], v, gcs[gi][c:c + 1, :],
                b_ref[0, gi, 0, c:c + 1, :], mm)))
    invs = unit_lower_inverses([f["system"] for _, _, f in todo], chunk)
    for (_, _, f), inv in zip(todo, invs):
        f["inv"] = inv
        f["uw"] = _dot(inv, f["rhs"], _AB, precise=True)
        f["local"] = _local(f)
    # through the state, a chunk after the other
    for gi, c, f in todo:
        rows = slice(c * chunk, (c + 1) * chunk)
        mine = range(gi * heads, (gi + 1) * heads)
        states = [state[r] for r in mine]
        if save:
            for r, s in zip(mine, states):
                s_ref[0, r, c] = s
            t_ref[0, gi, c] = f["inv"]
        v_new, read = _through_state(grp, f, states, mm, dv)
        out = read + _dot(f["local"], v_new, _AB)
        k_out = (f["k2"] * f["e_out"]).astype(mm)
        for h, (r, s) in enumerate(zip(mine, states)):
            o_ref[0, rows, r * dv:(r + 1) * dv] = grp.rows_of(out, h).astype(
                o_ref.dtype)
            state[r] = s * grp.rows_of(f["e_end"], h)[:1] + _dot(
                grp.rows_of(k_out, h), grp.rows_of(v_new, h), _ATB)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s_ref, t_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate, *, chunk, nb,
                rep, heads, dk, dv):
    """The forward's grid walked back (the index maps reverse the
    steps, this body the chunks of a step): operands as the forward's,
    do_ref / dv_ref as v_ref, dq_ref / dk_ref as q_ref, dg_ref / db_ref
    as g_ref; s_ref, t_ref what the forward saved; dstate (rep, Dk, Dv)
    float32 scratch, the gradient of the state a chunk hands on."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    mm = v_ref.dtype
    f32 = jnp.float32
    grp = _Group(heads, chunk)

    @pl.when(pl.program_id(2) == 0)
    def _first():
        dstate[...] = jnp.zeros_like(dstate)

    gcs = [grp.cumsum(g_ref[0, gi, 0]) for gi in range(rep // heads)]
    for c in reversed(range(nb)):
        rows = slice(c * chunk, (c + 1) * chunk)
        qn, q_r = _unit(q_ref[0, rows, :], dk ** -0.5)
        kn, k_r = _unit(k_ref[0, rows, :], 1.0)
        dqn = jnp.zeros_like(qn)
        dkn = jnp.zeros_like(kn)
        for gi in range(rep // heads):
            mine = range(gi * heads, (gi + 1) * heads)
            v = _stack([v_ref[0, rows, r * dv:(r + 1) * dv] for r in mine])
            dout = _stack([do_ref[0, rows, r * dv:(r + 1) * dv]
                           for r in mine]).astype(f32)
            # the chunk's factors again
            f = _chunk_factors(grp, qn, kn, v, gcs[gi][c:c + 1, :],
                               b_ref[0, gi, 0, c:c + 1, :], mm)
            inv = t_ref[0, gi, c]
            f["uw"] = _dot(inv, f["rhs"], _AB, precise=True)
            states = [s_ref[0, r, c] for r in mine]
            ds_out = [dstate[r] for r in mine]
            v_new, _ = _through_state(grp, f, states, mm, dv)
            local = _local(f)
            beta, decay, e_gc, e_out = f["beta"], f["decay"], f["e_gc"], \
                f["e_out"]
            q_in, k_out = f["q2"] * e_gc, f["k2"] * e_out
            w = f["uw"][:, dv:]
            # back through the output and the state's update
            dv_new = _dot(local, dout, _ATB) + _stack([
                _dot(grp.rows_of(k_out, h).astype(mm), ds, _AB)
                for h, ds in enumerate(ds_out)])
            dlocal = jnp.where(grp.lower, _dot(dout, v_new, _ABT), 0.0)
            dq_in, dw, dk_out, d_end = [], [], [], []
            for h, (r, s, ds) in enumerate(zip(mine, states, ds_out)):
                # [dout; dv_new] S^T: d(q e^gc) above, -dw below
                pair = jnp.concatenate(
                    [grp.rows_of(dout, h), grp.rows_of(dv_new, h)], axis=0)
                both = _dot(pair, s, _ABT)
                dq_in.append(both[:chunk])
                dw.append(-both[chunk:])
                dk_out.append(_dot(grp.rows_of(v_new, h), ds, _ABT))
                e_end = grp.rows_of(f["e_end"], h)[:1]
                # dS <- e^g_end dS + (q e^gc)^T dout - w^T dv_new
                dstate[r] = ds * e_end + _dot(jnp.concatenate(
                    [grp.rows_of(q_in, h).astype(mm).astype(f32),
                     -grp.rows_of(w, h)], axis=0), pair, _ATB)
                d_end.append(jnp.sum(jnp.sum(
                    s * ds, axis=1, keepdims=True), axis=0, keepdims=True)
                    * e_end)
            dq_in, dw, dk_out = _stack(dq_in), _stack(dw), _stack(dk_out)
            # back through the solve: uw = inv [beta v, beta e^gc k]
            drhs = _dot(inv, jnp.concatenate([dv_new, dw], axis=1), _ATB,
                        precise=True)
            drhs_v, drhs_k = drhs[:, :dv], drhs[:, dv:]
            da = -_dot(drhs, f["uw"], _ABT)
            da_kk = jnp.where(grp.strict, da * f["kk"] * decay, 0.0)
            db_ref[0, gi, 0, c:c + 1, :] = grp.row(
                jnp.sum(da_kk, axis=1, keepdims=True)
                + jnp.sum(drhs_v * f["v"], axis=1, keepdims=True)
                + jnp.sum(drhs_k * f["k2"], axis=1, keepdims=True) * e_gc)
            # the decays: d gc_i from everything e^(gc_i - gc_j) scales
            moved = da_kk * beta + dlocal * local
            k_side = jnp.sum(dk_out * k_out, axis=1, keepdims=True)
            at_rows = jnp.sum(moved, axis=1, keepdims=True) \
                + jnp.sum(dq_in * q_in, axis=1, keepdims=True) - k_side \
                + jnp.sum(drhs_k * f["rhs"][:, dv:], axis=1, keepdims=True)
            at_end = jnp.sum(jnp.where(grp.to_last, k_side, 0.0), axis=0,
                             keepdims=True) + sum(
                jnp.where(grp.lane == (h + 1) * chunk - 1, d, 0.0)
                for h, d in enumerate(d_end))
            dg_ref[0, gi, 0, c:c + 1, :] = grp.row(at_rows) \
                - jnp.sum(moved, axis=0, keepdims=True) + at_end
            # back through k k^T and q k^T, the heads of the group summed
            tiles = jnp.concatenate(
                [jnp.where(grp.strict, da * decay * beta, 0.0),
                 dlocal * decay], axis=0)                       # (2 n, n)
            left = _dot(tiles, f["kb"], _AB)
            right = _dot(tiles, jnp.concatenate(
                [f["kb"], f["qb"]], axis=0), _ATB)
            dq2 = left[grp.n:] + dq_in * e_gc
            dk2 = left[:grp.n] + right + dk_out * e_out \
                + drhs_k * (beta * e_gc)
            dqn = dqn + sum(grp.rows_of(dq2, h) for h in range(heads))
            dkn = dkn + sum(grp.rows_of(dk2, h) for h in range(heads))
            dv2 = drhs_v * beta
            for h, r in enumerate(mine):
                dv_ref[0, rows, r * dv:(r + 1) * dv] = grp.rows_of(
                    dv2, h).astype(dv_ref.dtype)
        dq_ref[0, rows, :] = _unit_back(dqn, qn, q_r, dk).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = _unit_back(dkn, kn, k_r, 1.0).astype(
            dk_ref.dtype)
    for gi in range(rep // heads):
        # gc = cumsum(g): dg_i is the sum of d gc_j over j >= i
        dg_ref[0, gi, 0] = grp.cumsum(dg_ref[0, gi, 0], reverse=True)


@functools.lru_cache(maxsize=32)
def _build(kind, b, tp, hk, hv, dk, dv, chunk, heads, nb, dtype_str,
           interpret):
    """The ``pallas_call`` of one kernel (``kind``: 'fwd', 'fwd_saved',
    'bwd') at one shape, dtype and schedule (``heads`` a group, ``nb``
    chunks a grid step)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rep, n = hv // hk, tp // chunk
    steps = n // nb
    groups, wide = rep // heads, heads * chunk
    dtype = jnp.dtype(dtype_str)
    f32 = jnp.float32
    back = kind == "bwd"

    def step(j):
        return steps - 1 - j if back else j

    qk_spec = pl.BlockSpec((1, nb * chunk, dk),
                           lambda i, h, j: (i, step(j), h))
    v_spec = pl.BlockSpec((1, nb * chunk, rep * dv),
                          lambda i, h, j: (i, step(j), h))
    g_spec = pl.BlockSpec((1, groups, 1, nb, wide),
                          lambda i, h, j: (i, h, step(j), 0, 0))
    s_spec = pl.BlockSpec((1, rep, nb, dk, dv),
                          lambda i, h, j: (i, h, step(j), 0, 0))
    t_spec = pl.BlockSpec((1, groups, nb, wide, wide),
                          lambda i, h, j: (i, h, step(j), 0, 0))
    qk_shape = jax.ShapeDtypeStruct((b, tp, hk * dk), dtype)
    v_shape = jax.ShapeDtypeStruct((b, tp, hv * dv), dtype)
    g_shape = jax.ShapeDtypeStruct((b, hv // heads, steps, nb, wide), f32)
    s_shape = jax.ShapeDtypeStruct((b, hv, n, dk, dv), f32)
    t_shape = jax.ShapeDtypeStruct((b, hv // heads, n, wide, wide), f32)
    sizes = dict(chunk=chunk, nb=nb, rep=rep, heads=heads, dk=dk, dv=dv)
    if back:
        kernel = functools.partial(_bwd_kernel, **sizes)
        in_specs = [qk_spec, qk_spec, v_spec, g_spec, g_spec, v_spec,
                    s_spec, t_spec]
        out_specs = [qk_spec, qk_spec, v_spec, g_spec, g_spec]
        out_shape = [qk_shape, qk_shape, v_shape, g_shape, g_shape]
    else:
        save = kind == "fwd_saved"
        kernel = functools.partial(_fwd_kernel, save=save, **sizes)
        in_specs = [qk_spec, qk_spec, v_spec, g_spec, g_spec]
        out_specs = [v_spec] + ([s_spec, t_spec] if save else [])
        out_shape = [v_shape] + ([s_shape, t_shape] if save else [])
    name = "delta_rule_bwd" if back else "delta_rule_fwd"
    return pl.pallas_call(
        kernel,
        grid=(b, hk, steps),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rep, dk, dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_schedule().delta_rule_vmem_limit(
                name, nb, chunk, rep, dk, dv, dtype.itemsize)),
        interpret=interpret,
        name="gated_" + name,
    )


def _by_chunk(x, tp, chunk, nb, heads):
    """(B, T, Hv) -> (B, Hv / heads, steps, nb, heads x chunk) float32,
    zeros past T: a chunk of a group's heads side by side along lanes."""
    import jax.numpy as jnp

    b, t, hv = x.shape
    x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, tp - t), (0, 0)))
    x = x.reshape(b, tp // (nb * chunk), nb, chunk, hv // heads, heads)
    return x.transpose(0, 4, 1, 2, 5, 3).reshape(
        b, hv // heads, -1, nb, heads * chunk)


def _by_token(x, t, chunk):
    """:func:`_by_chunk` back: -> (B, T, Hv)."""
    b, groups, steps, nb, wide = x.shape
    x = x.reshape(b, groups, steps, nb, wide // chunk, chunk)
    return x.transpose(0, 2, 3, 5, 1, 4).reshape(
        b, steps * nb * chunk, -1)[:, :t]


def _flat(x, tp):
    """(B, T, H, D) -> (B, Tp, H x D), zero rows past T: a padded token
    writes nothing (beta 0) and does not decay (g 0)."""
    import jax.numpy as jnp

    b, t = x.shape[:2]
    return jnp.pad(x.reshape(b, t, -1), ((0, 0), (0, tp - t), (0, 0)))


def gated_delta_rule_kernels(q, k, v, g, beta, chunk=64, interpret=False,
                             chunks=None, bwd_chunks=None):
    """``gated_delta_rule`` through the kernels: same arguments, same
    result, differentiable in all five. ``chunks`` / ``bwd_chunks``
    override the schedules' chunks a grid step (the search driver's
    candidates). Raises ``ScheduleError`` for a shape the kernels do not
    take (``tune.schedule.delta_rule_shape_supported``)."""
    import jax

    sched = _schedule()
    b, t, hv, dv = v.shape
    hk, dk = k.shape[2:]
    if not sched.delta_rule_shape_supported(dk, dv, chunk) or hv % hk:
        raise sched.ScheduleError(
            f"gated delta rule kernels: unsupported shape, Dk={dk} Dv={dv} "
            f"chunk={chunk} heads {hk}/{hv} (head sizes on the "
            f"{sched.LANES}-lane grid, chunk a multiple of "
            f"{2 * sched.MIN_SUBLANE})")
    dtype = v.dtype
    tp = -(-t // chunk) * chunk
    n = tp // chunk
    interpret = bool(interpret)
    nb_f, nb_b = (sched.delta_rule_chunks(
        kernel, b * hv, t, n, dk, dv, str(dtype), interpret=interpret,
        chunks=want) for kernel, want in (("delta_rule_fwd", chunks),
                                          ("delta_rule_bwd", bwd_chunks)))

    heads = sched.delta_rule_heads(hv // hk, chunk)

    def build(kind, nb):
        return _build(kind, b, tp, hk, hv, dk, dv, int(chunk), heads, nb,
                      str(dtype), interpret)

    def operands(q, k, v, g, beta, nb):
        return (_flat(q.astype(dtype), tp), _flat(k.astype(dtype), tp),
                _flat(v, tp), _by_chunk(g, tp, chunk, nb, heads),
                _by_chunk(beta, tp, chunk, nb, heads))

    def out_of(o):
        return o[:, :t].reshape(b, t, hv, dv)

    @jax.custom_vjp
    def f(q, k, v, g, beta):
        return out_of(build("fwd", nb_f)(*operands(q, k, v, g, beta,
                                                    nb_f))[0])

    def f_fwd(q, k, v, g, beta):
        o, states, inverses = kernel_residuals(*build("fwd_saved", nb_f)(
            *operands(q, k, v, g, beta, nb_f)))
        return out_of(o), (q, k, v, g, beta, states, inverses)

    def f_bwd(res, dout):
        q, k, v, g, beta, states, inverses = res
        dq, dk_, dv_, dg, db = build("bwd", nb_b)(
            *operands(q, k, v, g, beta, nb_b),
            _flat(dout.astype(dtype), tp), states, inverses)
        return (dq[:, :t].reshape(q.shape).astype(q.dtype),
                dk_[:, :t].reshape(k.shape).astype(k.dtype),
                dv_[:, :t].reshape(v.shape),
                _by_token(dg, t, chunk).astype(g.dtype),
                _by_token(db, t, chunk).astype(beta.dtype))

    f.defvjp(f_fwd, f_bwd)
    return f(q, k, v, g, beta)
