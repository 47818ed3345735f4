"""Reference-name parity: internal op names + the remaining small-op tail.

The reference resolves ops by their NNVM registration names, many of which
are internal spellings (``_zeros``, ``_linalg_gemm``, ``_slice_assign``)
behind the public ``mx.nd`` functions. This module (a) registers those
internal names as aliases of the already-implemented TPU ops, and (b)
implements the residual small ops so that the full ``NNVM_REGISTER_OP``
name list (minus documented descopes, docs/DESCOPES.md) resolves.

tests/test_name_parity.py asserts resolution over the committed snapshot
of the reference's registration list (tests/data/reference_ops.txt).
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from ..base import np_dtype
from .registry import add_alias, register


# --------------------------------------------------------------- creation
# Parity: src/operator/tensor/init_op.cc (_zeros/_ones/_full/_eye/_arange/
# _linspace). Zero-input ops: params only.

def _dt(dtype, default=_np.float32):
    return np_dtype(dtype) if dtype is not None else default


@register("_zeros", no_grad=True, aliases=("_zeros_without_dtype",))
def _zeros_op(shape=(), ctx=None, dtype=None):
    return jnp.zeros(tuple(shape), _dt(dtype))


@register("_ones", no_grad=True)
def _ones_op(shape=(), ctx=None, dtype=None):
    return jnp.ones(tuple(shape), _dt(dtype))


@register("_full", no_grad=True)
def _full_op(shape=(), value=0.0, ctx=None, dtype=None):
    return jnp.full(tuple(shape), value, _dt(dtype))


@register("_eye", no_grad=True)
def _eye_op(N=0, M=0, k=0, ctx=None, dtype=None):
    m = int(M) if M else int(N)
    return jnp.eye(int(N), m, k=int(k), dtype=_dt(dtype))


@register("_arange", no_grad=True)
def _arange_op(start=0.0, stop=None, step=1.0, repeat=1, infer_range=False,
               ctx=None, dtype=None):
    a = _np.arange(start, stop, step, dtype=_dt(dtype))
    if int(repeat) > 1:
        a = _np.repeat(a, int(repeat))
    return jnp.asarray(a)


@register("_linspace", no_grad=True)
def _linspace_op(start=0.0, stop=1.0, num=50, endpoint=True, ctx=None,
                 dtype=None):
    return jnp.linspace(float(start), float(stop), int(num),
                        endpoint=bool(endpoint), dtype=_dt(dtype))


# ------------------------------------------------------------ linalg tail
# Parity: src/operator/tensor/la_op.cc:569-690 (extracttrian/maketrian).

def _trian_indices(n, offset, lower):
    if offset > 0:
        r, c = _np.triu_indices(n, k=offset)
    elif offset < 0:
        r, c = _np.tril_indices(n, k=offset)
    else:
        r, c = (_np.tril_indices(n) if lower else _np.triu_indices(n))
    return r, c


@register("linalg_extracttrian")
def _extracttrian(a, offset=0, lower=True):
    """Row-major triangle extraction from (..., n, n) -> (..., L)."""
    n = a.shape[-1]
    r, c = _trian_indices(n, int(offset), bool(lower))
    return a[..., r, c]


@register("linalg_maketrian")
def _maketrian(a, offset=0, lower=True):
    """Inverse of extracttrian: (..., L) -> (..., m, m) with the triangle
    entries placed and zeros elsewhere; m grows by |offset|."""
    L = a.shape[-1]
    n = int((_np.sqrt(8 * L + 1) - 1) / 2)
    off = int(offset)
    if n * (n + 1) // 2 != L:  # pure off-diagonal band input
        n = L
    m = n + abs(off)
    r, c = _trian_indices(m, off, bool(lower))
    r, c = r[:L], c[:L]
    out = jnp.zeros(a.shape[:-1] + (m, m), a.dtype)
    return out.at[..., r, c].set(a)


for _la in ("gemm", "gemm2", "potrf", "potri", "trmm", "trsm", "sumlogdiag",
            "syrk", "gelqf", "syevd", "det", "slogdet", "inverse",
            "extractdiag", "makediag", "extracttrian", "maketrian"):
    add_alias(f"_linalg_{_la}", f"linalg_{_la}")


# ----------------------------------------------------------- im2col family
# Parity: src/operator/nn/im2col.cc. The sliding-window unfold is expressed
# as K static strided slices stacked on a new axis (XLA fuses them); col2im
# is exactly the VJP of that unfold, so jax.vjp IS the reference's
# hand-written accumulation kernel.

def _sliding_norm(kernel, stride, dilate, pad):
    kernel = tuple(int(k) for k in kernel)
    nd = len(kernel)

    def norm(v, default):
        if v is None or (isinstance(v, (tuple, list)) and len(v) == 0):
            return (default,) * nd
        if isinstance(v, (int, float)):
            return (int(v),) * nd
        return tuple(int(x) for x in v)

    return kernel, norm(stride, 1), norm(dilate, 1), norm(pad, 0), nd


def _im2col_core(data, kernel, stride, dilate, pad):
    n, c = data.shape[:2]
    spatial = data.shape[2:]
    nd = len(kernel)
    padded = jnp.pad(data, ((0, 0), (0, 0)) +
                     tuple((p, p) for p in pad))
    out_sp = tuple(
        (spatial[i] + 2 * pad[i] - (1 + (kernel[i] - 1) * dilate[i]))
        // stride[i] + 1 for i in range(nd))
    pieces = []
    for koff in _np.ndindex(*kernel):
        idx = tuple(
            slice(koff[i] * dilate[i],
                  koff[i] * dilate[i] + (out_sp[i] - 1) * stride[i] + 1,
                  stride[i])
            for i in range(nd))
        pieces.append(padded[(slice(None), slice(None)) + idx])
    col = jnp.stack(pieces, axis=2)  # (N, C, K, *out_sp)
    K = int(_np.prod(kernel))
    L = int(_np.prod(out_sp))
    return col.reshape(n, c * K, L)


@register("im2col")
def _im2col(data, kernel=(), stride=(), dilate=(), pad=()):
    kernel, stride, dilate, pad, _ = _sliding_norm(kernel, stride, dilate, pad)
    return _im2col_core(data, kernel, stride, dilate, pad)


@register("col2im")
def _col2im(data, output_size=(), kernel=(), stride=(), dilate=(), pad=()):
    kernel, stride, dilate, pad, nd = _sliding_norm(kernel, stride, dilate,
                                                    pad)
    out_sp = tuple(int(s) for s in output_size)
    n = data.shape[0]
    K = int(_np.prod(kernel))
    c = data.shape[1] // K
    ref = jnp.zeros((n, c) + out_sp, data.dtype)
    _, vjp = jax.vjp(
        lambda x: _im2col_core(x, kernel, stride, dilate, pad), ref)
    return vjp(data)[0]


# ----------------------------------------------- assignment / scatter tail
# Parity: src/operator/tensor/matrix_op.cc:508 (_slice_assign family) and
# indexing_op.cc:1097 (_scatter_set_nd) — the imperative engines behind
# NDArray sliced set-item.

def _slice_tuple(nd, begin, end, step):
    begin = tuple(begin) if begin is not None else (None,) * nd
    end = tuple(end) if end is not None else (None,) * nd
    step = tuple(step) if step not in (None, ()) else (None,) * nd
    out = []
    for i in range(nd):
        b = begin[i] if i < len(begin) else None
        e = end[i] if i < len(end) else None
        s = step[i] if i < len(step) else None
        out.append(slice(b, e, s if s not in (0, None) else None))
    return tuple(out)


@register("_slice_assign", aliases=("_crop_assign",))
def _slice_assign(lhs, rhs, begin=None, end=None, step=None):
    lhs = jnp.asarray(lhs)
    return lhs.at[_slice_tuple(lhs.ndim, begin, end, step)].set(rhs)


@register("_slice_assign_scalar", aliases=("_crop_assign_scalar",))
def _slice_assign_scalar(lhs, scalar=0.0, begin=None, end=None, step=None):
    lhs = jnp.asarray(lhs)
    return lhs.at[_slice_tuple(lhs.ndim, begin, end, step)].set(scalar)


@register("_scatter_set_nd")
def _scatter_set_nd(lhs, rhs, indices, shape=None):
    """scatter_nd that keeps non-indexed lhs elements (indexing_op.cc:1097)."""
    lhs = jnp.asarray(lhs)
    idx = tuple(jnp.asarray(indices[i]).astype(jnp.int32)
                for i in range(indices.shape[0]))
    return lhs.at[idx].set(rhs)


# ---------------------------------------------------------- identity tail

@register("_identity_with_attr_like_rhs")
def _identity_with_attr_like_rhs(lhs, rhs):
    return lhs


@register("_rnn_param_concat")
def _rnn_param_concat(*arrays, dim=0, num_args=None):
    """Concat specialization used to fuse RNN parameter blobs
    (src/operator/rnn.cc _rnn_param_concat registration)."""
    return jnp.concatenate(arrays, axis=int(dim))


@register("IdentityAttachKLSparseReg", mutate=(1,),
          num_outputs=1)
def _identity_kl_sparse_reg(data, moving_avg, sparseness_target=0.1,
                            penalty=0.001, momentum=0.9):
    """Forward identity; updates the moving average of mean activation
    (the KL sparsity penalty the reference adds in backward is an
    autograd-visible regularizer here). Parity:
    src/operator/identity_attach_KL_sparse_reg.cc."""
    avg = momentum * moving_avg + (1 - momentum) * jnp.mean(data)
    return data, avg


# ------------------------------------------------------------ sparse tail
# The NDArray cell stores dense PJRT buffers; RowSparse/CSR live in
# ndarray/sparse.py as index+value views. These ops give the reference's
# storage-manipulation names dense-equivalent semantics.

@register("cast_storage")
def _cast_storage(data, stype="default"):
    return data


@register("_sparse_retain")
def _sparse_retain(data, indices):
    """Keep only the listed rows of a (row-sparse) array, zeroing the rest
    (src/operator/tensor/sparse_retain.cc)."""
    keep = jnp.zeros((data.shape[0],), jnp.bool_).at[
        jnp.asarray(indices).astype(jnp.int32)].set(True)
    data = jnp.asarray(data)
    return jnp.where(keep.reshape((-1,) + (1,) * (data.ndim - 1)), data, 0)


@register("_contrib_getnnz", no_grad=True, aliases=("getnnz",))
def _getnnz(data, axis=None):
    """Count of stored (non-zero) values (contrib/nnz.cc, CSR)."""
    if axis is None:
        return jnp.sum(data != 0).astype(jnp.int64)
    return jnp.sum(data != 0, axis=int(axis)).astype(jnp.int64)


@register("_contrib_edge_id", no_grad=True, aliases=("edge_id",))
def _edge_id(data, u, v):
    """Edge ids of (u[i], v[i]) pairs in a CSR adjacency; -1 when absent
    (src/operator/contrib/dgl_graph.cc EdgeID — the one DGL-family op
    with dense-tensor semantics; the sampling family is descoped, see
    docs/DESCOPES.md). data: dense (N, N) adjacency with edge ids + 0
    for absent edges."""
    ui = u.astype(jnp.int32)
    vi = v.astype(jnp.int32)
    vals = data[ui, vi]
    return jnp.where(vals != 0, vals, -1.0).astype(data.dtype)


# ------------------------------------------------------- optimizer mp tail

from .optimizer_ops import _multi_tuple, _rescale_clip  # noqa: E402


def _clip(g, c):
    return _rescale_clip(g, 1.0, c)


@register("_mp_adamw_update", mutate=(0, 2, 3, 4), no_grad=True,
          aliases=("mp_adamw_update",))
def _mp_adamw_update(weight, grad, mean, var, weight32, rescale_grad_arr=None,
                     lr=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8, wd=0.0,
                     eta=1.0, rescale_grad=1.0, clip_gradient=None):
    """Multi-precision AdamW (src/operator/contrib/adamw.cc): fp32 master
    weights; the scalar rescale may arrive as a device array (loss scale)."""
    rs = rescale_grad_arr if rescale_grad_arr is not None else rescale_grad
    g = _clip(grad.astype(jnp.float32) * rs, clip_gradient)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    new_w32 = weight32 - eta * (lr * new_mean / (jnp.sqrt(new_var) + epsilon)
                                + wd * weight32)
    new_w = new_w32.astype(weight.dtype)
    return new_w, new_w, new_mean, new_var, new_w32


@register("_multi_adamw_update", no_grad=True,
          num_outputs=lambda p: p.get("num_weights", 1),
          mutate=lambda p: tuple(
              s for i in range(p.get("num_weights", 1))
              for s in (4 * i, 4 * i + 2, 4 * i + 3)),
          aliases=("multi_adamw_update",))
def _multi_adamw_update(*tensors, num_weights=1, lrs=(0.001,), wds=(0.0,),
                        etas=(1.0,), beta1=0.9, beta2=0.999, epsilon=1e-8,
                        rescale_grad=1.0, clip_gradient=-1.0):
    """Grouped AdamW [w, g, mean, var]* + trailing rescale array
    (contrib/adamw.cc multi-tensor path)."""
    arrays = tensors
    rs = rescale_grad
    if len(arrays) == 4 * num_weights + 1:  # trailing loss-scale array
        rs = arrays[-1]
        arrays = arrays[:-1]
    lrs = _multi_tuple(lrs, num_weights)
    wds = _multi_tuple(wds, num_weights)
    etas = _multi_tuple(etas, num_weights)
    outs, mutated = [], []
    for i in range(num_weights):
        w, g, m, v = arrays[4 * i:4 * i + 4]
        g = _clip(g * rs, clip_gradient if clip_gradient > 0 else None)
        nm = beta1 * m + (1 - beta1) * g
        nv = beta2 * v + (1 - beta2) * jnp.square(g)
        nw = w - float(etas[i]) * (float(lrs[i]) * nm /
                                   (jnp.sqrt(nv) + epsilon) +
                                   float(wds[i]) * w)
        outs.append(nw)
        mutated.extend([nw, nm, nv])
    return tuple(outs) + tuple(mutated)


@register("_multi_mp_adamw_update", no_grad=True,
          num_outputs=lambda p: p.get("num_weights", 1),
          mutate=lambda p: tuple(
              s for i in range(p.get("num_weights", 1))
              for s in (5 * i, 5 * i + 2, 5 * i + 3, 5 * i + 4)),
          aliases=("multi_mp_adamw_update",))
def _multi_mp_adamw_update(*tensors, num_weights=1, lrs=(0.001,), wds=(0.0,),
                           etas=(1.0,), beta1=0.9, beta2=0.999, epsilon=1e-8,
                           rescale_grad=1.0, clip_gradient=-1.0):
    """Grouped multi-precision AdamW [w, g, mean, var, w32]*."""
    arrays = tensors
    rs = rescale_grad
    if len(arrays) == 5 * num_weights + 1:
        rs = arrays[-1]
        arrays = arrays[:-1]
    lrs = _multi_tuple(lrs, num_weights)
    wds = _multi_tuple(wds, num_weights)
    etas = _multi_tuple(etas, num_weights)
    outs, mutated = [], []
    for i in range(num_weights):
        w, g, m, v, w32 = arrays[5 * i:5 * i + 5]
        g = _clip(g.astype(jnp.float32) * rs,
                  clip_gradient if clip_gradient > 0 else None)
        nm = beta1 * m + (1 - beta1) * g
        nv = beta2 * v + (1 - beta2) * jnp.square(g)
        nw32 = w32 - float(etas[i]) * (float(lrs[i]) * nm /
                                       (jnp.sqrt(nv) + epsilon) +
                                       float(wds[i]) * w32)
        nw = nw32.astype(w.dtype)
        outs.append(nw)
        mutated.extend([nw, nm, nv, nw32])
    return tuple(outs) + tuple(mutated)


@register("_sparse_adagrad_update", mutate=(0, 2), no_grad=True,
          aliases=("adagrad_update",))
def _sparse_adagrad_update(weight, grad, history, lr=0.01, epsilon=1e-7,
                           wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    """AdaGrad (optimizer_op.cc:895 _sparse_adagrad_update); dense
    semantics — the row-sparse lazy path lives in optimizer/optimizer.py."""
    g = _clip(grad * rescale_grad,
              clip_gradient if clip_gradient > 0 else None)
    new_hist = history + jnp.square(g)
    new_w = weight - lr * g / (jnp.sqrt(new_hist) + epsilon)
    return new_w, new_w, new_hist


@register("mp_lamb_update_phase1", no_grad=True)
def _mp_lamb_update_phase1(weight, grad, mean, var, weight32, lr=0.001,
                           beta1=0.9, beta2=0.999, epsilon=1e-6, t=1,
                           bias_correction=True, wd=0.0, rescale_grad=1.0,
                           clip_gradient=-1.0):
    """Multi-precision LAMB phase 1 (optimizer_op.cc:1005): moment update
    in fp32 against the master copy; returns the raw update direction."""
    g = _clip(grad.astype(jnp.float32) * rescale_grad,
              clip_gradient if clip_gradient > 0 else None)
    new_mean = beta1 * mean + (1 - beta1) * g
    new_var = beta2 * var + (1 - beta2) * jnp.square(g)
    m_hat, v_hat = new_mean, new_var
    if bias_correction:
        m_hat = new_mean / (1 - beta1 ** t)
        v_hat = new_var / (1 - beta2 ** t)
    return m_hat / (jnp.sqrt(v_hat) + epsilon) + wd * weight32


@register("mp_lamb_update_phase2", mutate=(0, 4), no_grad=True)
def _mp_lamb_update_phase2(weight, g, r1, r2, weight32, lr=0.001,
                           lower_bound=-1.0, upper_bound=-1.0):
    """Phase 2 (optimizer_op.cc:1051): trust-ratio scaled step applied to
    the fp32 master; low-precision copy refreshed."""
    r1 = jnp.where(lower_bound > 0, jnp.maximum(r1, lower_bound), r1)
    r1 = jnp.where(upper_bound > 0, jnp.minimum(r1, upper_bound), r1)
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    new_w32 = weight32 - lr * ratio * g
    new_w = new_w32.astype(weight.dtype)
    return new_w, new_w, new_w32


@register("preloaded_multi_mp_sgd_update", no_grad=True,
          num_outputs=lambda p: p.get("num_weights", 1),
          mutate=lambda p: tuple(
              s for i in range(p.get("num_weights", 1))
              for s in (3 * i, 3 * i + 2)))
def _preloaded_multi_mp_sgd_update(*tensors, num_weights=1, rescale_grad=1.0,
                                   clip_gradient=-1.0):
    """[w0, g0, w32_0, ..., lrs, wds] with device-resident lrs/wds
    (contrib/preloaded_multi_sgd.cc mp variant)."""
    lrs, wds = tensors[-2], tensors[-1]
    new_ws, mutated = [], []
    for i in range(num_weights):
        w, g, w32 = tensors[3 * i:3 * i + 3]
        g = _clip(g.astype(jnp.float32) * rescale_grad,
                  clip_gradient if clip_gradient > 0 else None)
        nw32 = w32 - lrs[i] * (g + wds[i] * w32)
        nw = nw32.astype(w.dtype)
        new_ws.append(nw)
        mutated.extend([nw, nw32])
    return tuple(new_ws) + tuple(mutated)


@register("preloaded_multi_mp_sgd_mom_update", no_grad=True,
          num_outputs=lambda p: p.get("num_weights", 1),
          mutate=lambda p: tuple(
              s for i in range(p.get("num_weights", 1))
              for s in (4 * i, 4 * i + 2, 4 * i + 3)))
def _preloaded_multi_mp_sgd_mom_update(*tensors, num_weights=1, momentum=0.0,
                                       rescale_grad=1.0, clip_gradient=-1.0):
    """[w0, g0, mom0, w32_0, ..., lrs, wds]."""
    lrs, wds = tensors[-2], tensors[-1]
    new_ws, mutated = [], []
    for i in range(num_weights):
        w, g, mom, w32 = tensors[4 * i:4 * i + 4]
        g = _clip(g.astype(jnp.float32) * rescale_grad,
                  clip_gradient if clip_gradient > 0 else None)
        nmom = momentum * mom - lrs[i] * (g + wds[i] * w32)
        nw32 = w32 + nmom
        nw = nw32.astype(w.dtype)
        new_ws.append(nw)
        mutated.extend([nw, nmom, nw32])
    return tuple(new_ws) + tuple(mutated)


# ------------------------------------------------- straight alias wiring
# reference internal name -> repo canonical name
for _alias, _canon in {
    "_histogram": "histogram",
    "_split_v2": "split_v2",
    "_contrib_boolean_mask": "boolean_mask",
    "_contrib_BilinearResize2D": "BilinearResize2D",
    "_contrib_SparseEmbedding": "Embedding",
    "BatchNorm_v1": "BatchNorm",
    "_adamw_update": "adamw_update",
    "_multi_lamb_update": "multi_lamb_update",
    "_multi_mp_lamb_update": "multi_lamb_update",  # fp32 master == weights
}.items():
    add_alias(_alias, _canon)


@register("_contrib_SyncBatchNorm", mutate=(3, 4),
          aliases=("SyncBatchNorm",))
def _sync_batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                     momentum=0.9, fix_gamma=True, use_global_stats=False,
                     output_mean_var=False, ndev=1, key=None, _train=True):
    """Cross-device BatchNorm (src/operator/contrib/sync_batch_norm.cc).
    Single-device semantics equal BatchNorm; under pjit/GSPMD the batch
    axis is sharded and XLA's partitioner turns the batch reductions into
    cross-replica psums — which IS the sync (the reference needs its own
    key-coordinated allreduce because its engine can't see across
    devices). `key`/`ndev` are accepted for signature parity. The gluon
    layer lives in gluon/contrib (SyncBatchNorm)."""
    from .nn import _batch_norm

    return _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=eps,
                       momentum=momentum, fix_gamma=fix_gamma,
                       use_global_stats=use_global_stats,
                       output_mean_var=output_mean_var, _train=_train)


@register("_contrib_calibrate_entropy", num_outputs=2, no_grad=True,
          aliases=("calibrate_entropy",))
def _calibrate_entropy_op(hist, hist_edges, num_quantized_bins=255):
    """Entropy (KL) calibration threshold from an activation histogram
    (src/operator/quantization/calibrate.cc). Host computation — the
    branch-heavy threshold search runs once at calibration time, never in
    the hot path.
    Returns (min, max) range."""
    import jax.core as jcore

    if isinstance(hist, jcore.Tracer) or isinstance(hist_edges, jcore.Tracer):
        raise NotImplementedError(
            "_contrib_calibrate_entropy is a host-side calibration op; "
            "call it eagerly, outside jit")
    from ..contrib.quantization import _entropy_threshold

    th = _entropy_threshold(_np.asarray(hist), _np.asarray(hist_edges),
                            int(num_quantized_bins))
    return jnp.float32(-th), jnp.float32(th)
