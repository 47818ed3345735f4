"""gluon.contrib.nn — auxiliary blocks.

Capability parity with python/mxnet/gluon/contrib/nn/basic_layers.py:
Concurrent/HybridConcurrent (parallel branches, concatenated),
Identity, SparseEmbedding, SyncBatchNorm.
"""
from __future__ import annotations

import warnings

from .. import nn as _nn
from ... import jit as _jit
from ..block import Block, HybridBlock

__all__ = ["Remat", "Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "SyncBatchNorm"]


class Concurrent(_nn.Sequential):
    """Feed input to every child, concat outputs along `axis`."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def forward(self, x):
        from ... import ndarray as nd

        return nd.concat(*[block(x) for block in self._children.values()],
                         dim=self.axis)


class HybridConcurrent(_nn.HybridSequential):
    """Hybridizable Concurrent. HybridSequential short-circuits its children
    chain in _call_with_params / the Symbol path, so both are overridden
    here to concatenate instead."""

    def __init__(self, axis=-1, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self.axis = axis

    def _concat(self, F, x):
        return F.concat(*[block(x) for block in self._children.values()],
                        dim=self.axis)

    def hybrid_forward(self, F, x):
        return self._concat(F, x)

    def _call_with_params(self, *args):
        from ... import ndarray as F

        return self._concat(F, args[0])

    def forward(self, x, *args):
        from ... import symbol as _sym
        from ...symbol import Symbol

        if isinstance(x, Symbol):
            return self._concat(_sym, x)
        return HybridBlock.forward(self, x, *args)


class Identity(HybridBlock):
    def hybrid_forward(self, F, x):
        return x


class SparseEmbedding(Block):
    """API parity for contrib.nn.SparseEmbedding: on TPU the dense-gradient
    Embedding is the efficient path (XLA scatter-add), so this delegates
    and documents the difference."""

    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, **kwargs):
        super().__init__(**kwargs)
        warnings.warn("SparseEmbedding uses dense gradients on TPU "
                      "(row_sparse grads are a GPU/PS optimization)")
        with self.name_scope():
            self._embed = _nn.Embedding(input_dim, output_dim, dtype=dtype,
                                        weight_initializer=weight_initializer)

    def forward(self, x):
        return self._embed(x)


class SyncBatchNorm(_nn.BatchNorm):
    """Cross-device BatchNorm (contrib SyncBatchNorm / sync_batch_norm.cc).
    Under GSPMD the batch axis is sharded over the mesh and XLA computes
    batch statistics with cross-replica collectives automatically, so the
    standard BatchNorm IS synchronized; this subclass exists for API
    parity (num_devices is accepted and ignored)."""

    def __init__(self, in_channels=0, num_devices=None, momentum=0.9,
                 epsilon=1e-5, **kwargs):
        super().__init__(momentum=momentum, epsilon=epsilon,
                         in_channels=in_channels, **kwargs)


class Remat(HybridBlock):
    """Segment-level activation rematerialization around any block.

    Inside a functional trace (ShardedTrainer / parallel.functional_call —
    the compiled-training paths, where parameter cells hold jax tracers)
    the wrapped block runs under ``jax.checkpoint``: its internal
    activations are recomputed during the backward instead of kept —
    the segment-granular form of the reference's gradient mirroring
    (src/nnvm/gradient.cc:107-148). In plain eager mode and under
    hybridize's discovery trace (where cells hold concrete values that
    must be *captured*, not baked in) it is a transparent pass-through.

    Example::

        stage = contrib.nn.Remat(resnet_stage)   # per-stage remat
    """

    def __init__(self, block, policy=None, **kwargs):
        super().__init__(**kwargs)
        from ...remat import resolve_policy
        with self.name_scope():
            self.block = block
        self._policy = resolve_policy(policy)

    def forward(self, *args):
        from ...jit import _active, _notify_io, _notify_mutation
        from ...ndarray.ndarray import NDArray

        if _active() is None:  # eager: no compiled backward to remat
            return self.block(*args)

        import jax

        # only checkpoint when the cells are already functional (tracers):
        # in a TracedFunction discovery run the cells hold concrete arrays
        # and reading them here would bake weights into the compiled cache
        # as constants — pass through and let the tape capture them
        cell_vals = [p.data().data_
                     for p in self.block.collect_params().values()]
        cell_vals += [a.data_ for a in args if isinstance(a, NDArray)]
        if not any(isinstance(v, jax.core.Tracer) for v in cell_vals):
            return self.block(*args)

        from ... import autograd
        from ...parallel.functional import (
            functional_call, param_arrays, aux_arrays, RNG_KEY)
        from ... import random as _random

        fn = functional_call(self.block, train=autograd.is_training())
        pvals = param_arrays(self.block)
        avals = aux_arrays(self.block)
        xs = [a.data_ if isinstance(a, NDArray) else a for a in args]
        out, new_aux = jax.checkpoint(fn, policy=self._policy)(
            pvals, avals, *xs)
        # surface the sub-block's aux mutations (BN stats, rng key) to the
        # enclosing trace session
        cells = {name: p.data()
                 for name, p in self.block.collect_params().items()}
        for name, val in new_aux.items():
            if name == RNG_KEY:
                cell = _random.generator_key()
            else:
                cell = cells[name]
            cell._data = val
            _notify_mutation(cell)
        outs = ([NDArray(o) for o in out] if isinstance(out, tuple)
                else [NDArray(out)])
        _notify_io([a for a in args if isinstance(a, NDArray)], outs)
        return outs[0] if len(outs) == 1 else tuple(outs)

    def hybrid_forward(self, F, *args):  # pragma: no cover - forward() used
        return self.block(*args)


class MultiHeadAttention(HybridBlock):
    """Multi-head attention block with a selectable attention kernel —
    the Block-API door to the framework's best attention paths (round-5:
    previously the Pallas kernel was reachable only through
    parallel.attention, invisible to gluon models).

    impl:
      - 'dense': fused XLA composition (differentiable, any backend)
      - 'flash': Pallas streaming kernel, O(T) HBM, trainable via
        custom_vjp (ops/pallas_kernels.flash_attention_with_grad)
      - 'ring':  sequence-parallel ring attention over `mesh`'s
        `sp_axis` (parallel/ring_attention.py) — for T beyond one chip
      - 'auto':  picks per shape/backend (parallel.attention)

    Self-attention: ``block(x)`` with x (B, L, units). Cross-attention:
    ``block(x, key_value)`` with key_value (B, S, units) — q projects
    from x, k/v from key_value (the reference's encdec interleaved
    layout, contrib/transformer.cc:736-819). Output (B, L, units).
    """

    def __init__(self, units, num_heads, impl="dense", causal=False,
                 use_bias=True, mesh=None, sp_axis="sp", dtype=None,
                 cross_attention=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise ValueError(f"units {units} not divisible by num_heads "
                             f"{num_heads}")
        self._units = units
        self._heads = num_heads
        self._impl = impl
        self._causal = causal
        self._mesh = mesh
        self._sp_axis = sp_axis
        with self.name_scope():
            if cross_attention:
                # q from the query stream, interleaved k/v from the
                # key_value stream (the encdec layout); created only on
                # request so self-attention blocks don't carry ~3·units²
                # dead parameters
                self.q_proj = _nn.Dense(units, use_bias=use_bias,
                                        flatten=False, in_units=units,
                                        prefix="q_")
                self.kv_proj = _nn.Dense(2 * units, use_bias=use_bias,
                                         flatten=False, in_units=units,
                                         prefix="kv_")
                self.qkv_proj = None
            else:
                self.qkv_proj = _nn.Dense(3 * units, use_bias=use_bias,
                                          flatten=False, prefix="qkv_")
                self.q_proj = self.kv_proj = None
            self.out_proj = _nn.Dense(units, use_bias=use_bias,
                                      flatten=False, prefix="out_")

    def _split_heads(self, F, x, n):
        # (B, L, n*units) -> n tensors (B, H, L, d)
        b_l_u = x.shape
        h, d = self._heads, self._units // self._heads
        x = F.reshape(x, shape=(b_l_u[0], b_l_u[1], n * h, d))
        x = F.transpose(x, axes=(0, 2, 1, 3))  # (B, n*H, L, d)
        return [F.slice_axis(x, axis=1, begin=i * h, end=(i + 1) * h)
                for i in range(n)]

    def hybrid_forward(self, F, x, key_value=None):
        if key_value is None:
            if self.qkv_proj is None:
                raise ValueError("this block was built with "
                                 "cross_attention=True; pass key_value")
            q, k, v = self._split_heads(F, self.qkv_proj(x), 3)
        else:
            if self.q_proj is None:
                raise ValueError("pass cross_attention=True at construction "
                                 "for the cross-attention path")
            (q,) = self._split_heads(F, self.q_proj(x), 1)
            k, v = self._split_heads(F, self.kv_proj(key_value), 2)
        # scores, softmax, values -- not the projections -- under one
        # name whatever implements them (a kernel, a scan, a ring, plain
        # XLA), so a trace reads attention as the same work
        with _jit.scope("attention"):
            if self._impl in ("dense", "flash"):
                out = F.scaled_dot_product_attention(
                    q, k, v, causal=self._causal, impl=(
                        "flash" if self._impl == "flash" else "xla"))
            elif self._impl in ("ring", "auto"):
                from ... import parallel

                # per-hop kernel: 'auto' picks the Pallas flash kernel on TPU
                # and the dense composition on CPU meshes (virtual-device CI)
                out = parallel.attention(
                    q, k, v, causal=self._causal, mesh=self._mesh,
                    axis_name=self._sp_axis, impl="auto")
            else:
                raise ValueError(f"unknown impl {self._impl!r}")
        b, h, l, d = out.shape
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(b, l, h * d))
        return self.out_proj(out)
