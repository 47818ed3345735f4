"""gluon.contrib.nn — auxiliary blocks.

Capability parity with python/mxnet/gluon/contrib/nn/basic_layers.py:
Concurrent/HybridConcurrent (parallel branches, concatenated),
Identity, SparseEmbedding, SyncBatchNorm. Beyond the reference: Remat,
MultiHeadAttention, and the blocks of today's decoder layers --
GatedAttention (grouped K/V heads, rotary on part of a head, an output
gate), GroupedQueryAttention (grouped K/V heads and nothing else),
GatedDeltaNet (linear attention by the gated delta rule), Mamba2Mixer
(the Mamba-2 state-space mixer), GatedMLP, SquaredReLUMLP and SparseMoE
(an expert layer told which experts it holds).
"""
from __future__ import annotations

import math as _math
import time
import warnings

from .. import nn as _nn
from ... import initializer as _init
from ... import jit as _jit
from ...observability import trace as _obs_trace
from ..block import Block, HybridBlock

__all__ = ["Remat", "Concurrent", "HybridConcurrent", "Identity", "SparseEmbedding",
           "SyncBatchNorm", "GatedAttention", "GroupedQueryAttention",
           "GatedDeltaNet", "Mamba2Mixer", "GatedMLP", "SquaredReLUMLP",
           "SparseMoE"]


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

    ``policy`` is a ``remat.resolve_policy`` spec. The default keeps the
    block's input and what its hand-written kernels hand their own
    backward kernels (``remat.KERNEL_RESIDUAL``: flash attention's
    ``out`` and ``lse``, the gated delta rule's ``o``, chunk states and
    inverses), so a kernel runs once a step and everything else is
    recomputed; ``policy='nothing_saveable'`` runs the kernels again in
    the backward too. Each time the block is traced under
    ``jax.checkpoint`` it records one ``remat.trace`` span (no time in
    it; ``block``, ``policy``) when span tracing is on.

    Example::

        stage = contrib.nn.Remat(resnet_stage)   # per-stage remat
    """

    def __init__(self, block, policy=None, **kwargs):
        super().__init__(**kwargs)
        from ...remat import policy_name, resolve_policy
        with self.name_scope():
            self.block = block
        self._policy = resolve_policy(policy)
        self._policy_name = policy_name(policy)

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
        _obs_trace.record("remat.trace", time.perf_counter_ns(), 0,
                          block=self.block.name, policy=self._policy_name)
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


def _dense(units, in_units, prefix):
    return _nn.Dense(units, use_bias=False, flatten=False, in_units=in_units,
                     prefix=prefix)


class _Own(_init.Initializer):
    """``fn(shape) -> values``, whatever the parameter's name ends in
    (the base class picks zeros or ones from a name's suffix)."""

    def __init__(self, fn):
        super().__init__()
        self._fn = fn

    def __call__(self, desc, arr):
        arr[:] = self._fn(arr.shape)


class GatedAttention(HybridBlock):
    """Causal grouped-query self-attention with an output gate, as
    Qwen3-Next's full-attention layers have it: ``q_proj`` gives, per
    head, ``head_dim`` of query then ``head_dim`` of gate; zero-centred
    RMSNorm over the head on q and on k; rotary positions (half-split
    pairing) on the first ``rotary_dim`` channels of each head; each of
    the ``num_kv_heads`` K/V heads serves ``num_heads / num_kv_heads``
    query heads; the result times ``sigmoid(gate)``; ``out_proj``. No
    biases; ``head_dim`` is free of ``units / num_heads``. ``impl`` as
    MultiHeadAttention's 'dense' / 'flash'. x (B, T, units).

    As Trinity's (``afmoe``) layers have it: ``window`` lets a query see
    the keys ``0 <= i - j < window`` only (the kernels skip the tiles
    behind it), under the scope ``window_attention`` inside
    ``attention``; ``rotary_dim=0`` rotates nothing (its full-attention
    layers carry no positions); ``zero_centered_norm=False`` takes the
    plain RMSNorm, its weight trained from one; ``gate_proj=True`` gives
    the gate a projection of its own beside a ``q_proj`` of queries
    alone."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 rotary_dim=None, rope_theta=10000.0, epsilon=1e-6,
                 impl="dense", window=None, zero_centered_norm=True,
                 gate_proj=False, **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_kv_heads {num_kv_heads}")
        if impl not in ("dense", "flash"):
            raise ValueError(f"unknown impl {impl!r}")
        self._heads, self._kv, self._dim = num_heads, num_kv_heads, head_dim
        self._rotary = {"rotary_dim": head_dim if rotary_dim is None
                        else int(rotary_dim), "theta": float(rope_theta)}
        self._impl = impl
        self._window = None if window is None else int(window)

        def head_norm(prefix):
            return _nn.RMSNorm(head_dim, epsilon,
                               zero_centered=zero_centered_norm,
                               prefix=prefix)

        with self.name_scope():
            self.q_proj = _dense(
                num_heads * head_dim * (1 if gate_proj else 2), units, "q_")
            self.k_proj = _dense(num_kv_heads * head_dim, units, "k_")
            self.v_proj = _dense(num_kv_heads * head_dim, units, "v_")
            self.gate_proj = _dense(num_heads * head_dim, units, "gate_") \
                if gate_proj else None
            self.q_norm = head_norm("qnorm_")
            self.k_norm = head_norm("knorm_")
            self.out_proj = _dense(units, num_heads * head_dim, "out_")

    def hybrid_forward(self, F, x):
        b, t = x.shape[0], x.shape[1]
        h, kv, d = self._heads, self._kv, self._dim
        if self.gate_proj is None:
            qg = F.reshape(self.q_proj(x), shape=(b, t, h, 2 * d))
            q = F.slice_axis(qg, axis=-1, begin=0, end=d)
            gate = F.reshape(F.slice_axis(qg, axis=-1, begin=d, end=2 * d),
                             shape=(b, t, h * d))
        else:
            q = F.reshape(self.q_proj(x), shape=(b, t, h, d))
            gate = self.gate_proj(x)
        k = F.reshape(self.k_proj(x), shape=(b, t, kv, d))
        v = F.reshape(self.v_proj(x), shape=(b, t, kv, d))

        def heads_first(x, norm=None):
            x = F.transpose(x if norm is None else norm(x),
                            axes=(0, 2, 1, 3))
            if norm is None or not self._rotary["rotary_dim"]:
                return x
            return F.rotary_embedding(x, pos, **self._rotary)

        pos = F.arange(0, t, dtype="int32") \
            if self._rotary["rotary_dim"] else None
        q, k = heads_first(q, self.q_norm), heads_first(k, self.k_norm)
        v = heads_first(v)
        if h != kv:
            k = F.repeat(k, repeats=h // kv, axis=1)
            v = F.repeat(v, repeats=h // kv, axis=1)
        impl = "flash" if self._impl == "flash" else "xla"
        with _jit.scope("attention"):
            if self._window is None:
                out = F.scaled_dot_product_attention(q, k, v, causal=True,
                                                     impl=impl)
            else:
                with _jit.scope("window_attention"):
                    out = F.scaled_dot_product_attention(
                        q, k, v, causal=True, impl=impl,
                        window=self._window)
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(b, t, h * d))
        return self.out_proj(out * F.sigmoid(gate))


class GatedDeltaNet(HybridBlock):
    """Linear attention by the gated delta rule (Yang et al.,
    arXiv:2412.06464), as Qwen3-Next's linear layers have it.
    ``qkvz_proj`` gives q, k (``num_k_heads`` of ``head_k_dim``), v and
    the output gate z (``num_v_heads`` of ``head_v_dim``), laid
    [q | k | v | z]; ``ba_proj`` gives b then a (``num_v_heads`` each).
    [q, k, v] pass a causal depthwise convolution of ``conv_kernel``
    taps without bias, then SiLU (``causal_conv_silu``: on a TPU one
    Pallas kernel pass each way that hands q, k, v on as three arrays);
    ``beta = sigmoid(b)``,
    ``g = -exp(A_log) * softplus(a + dt_bias)`` in float32; the chunked
    delta rule (``ops/linear_attention.py``: its Pallas kernels on a
    TPU where both head sizes are multiples of 128, ``jax.numpy``
    elsewhere, the same result); a gated RMSNorm per value
    head with z; ``out_proj``. All but the four projections sits under
    the scope ``linear_attention``. x (B, T, units)."""

    def __init__(self, units, num_k_heads, num_v_heads, head_k_dim,
                 head_v_dim, conv_kernel=4, epsilon=1e-6, chunk=64,
                 **kwargs):
        super().__init__(**kwargs)
        if num_v_heads % num_k_heads:
            raise ValueError(f"num_v_heads {num_v_heads} not divisible by "
                             f"num_k_heads {num_k_heads}")
        self._hk, self._hv = num_k_heads, num_v_heads
        self._dk, self._dv = head_k_dim, head_v_dim
        self._chunk = chunk
        key_dim, value_dim = num_k_heads * head_k_dim, num_v_heads * head_v_dim
        from ... import ndarray as nd

        with self.name_scope():
            self.qkvz_proj = _dense(2 * key_dim + 2 * value_dim, units,
                                    "qkvz_")
            self.ba_proj = _dense(2 * num_v_heads, units, "ba_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(2 * key_dim + value_dim, conv_kernel),
                init=_init.Uniform(conv_kernel ** -0.5))
            self.A_log = self.params.get(
                "A_log", shape=(num_v_heads,), init=_Own(
                    lambda shape: nd.log(nd.random.uniform(1e-6, 16,
                                                           shape))))
            self.dt_bias = self.params.get("dt_bias", shape=(num_v_heads,),
                                           init=_Own(nd.ones))
            self.norm = _nn.RMSNorm(head_v_dim, epsilon, prefix="norm_")
            self.out_proj = _dense(units, value_dim, "out_")

    def hybrid_forward(self, F, x, conv_weight, A_log, dt_bias):
        b, t = x.shape[0], x.shape[1]
        hk, hv, dk, dv = self._hk, self._hv, self._dk, self._dv
        key_dim, value_dim = hk * dk, hv * dv
        qkvz, ba = self.qkvz_proj(x), self.ba_proj(x)

        with _jit.scope("linear_attention"):
            q, k, v, z = F.causal_conv_silu(
                qkvz, conv_weight, parts=(key_dim, key_dim, value_dim))
            q, k = (F.reshape(a, shape=(b, t, hk, dk)) for a in (q, k))
            v, z = (F.reshape(a, shape=(b, t, hv, dv)) for a in (v, z))
            beta, a = (F.cast(F.slice_axis(ba, axis=-1, begin=begin,
                                           end=begin + hv), dtype="float32")
                       for begin in (0, hv))
            beta = F.sigmoid(beta)
            g = -F.exp(F.cast(A_log, dtype="float32")) * F.Activation(
                a + F.cast(dt_bias, dtype="float32"), act_type="softrelu")
            out = self.norm(F.gated_delta_rule(q, k, v, g, beta,
                                               chunk=self._chunk), z)
        return self.out_proj(F.reshape(out, shape=(b, t, value_dim)))


class GroupedQueryAttention(HybridBlock):
    """Causal grouped-query self-attention with nothing around its core,
    as ``nemotron_h``'s attention layers have it: ``q_proj`` gives
    ``num_heads`` queries of ``head_dim``, ``k_proj`` and ``v_proj``
    ``num_kv_heads`` keys and values, each serving ``num_heads /
    num_kv_heads`` query heads; softmax of ``q k^T / sqrt(head_dim)``
    over every earlier key; ``out_proj``. No positions (rotary or any
    other), no gate, no norm on q or k, no biases. ``impl`` as
    MultiHeadAttention's 'dense' / 'flash'; scores, softmax and values
    under the scope ``attention``. x (B, T, units)."""

    def __init__(self, units, num_heads, num_kv_heads, head_dim,
                 impl="dense", **kwargs):
        super().__init__(**kwargs)
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"num_kv_heads {num_kv_heads}")
        if impl not in ("dense", "flash"):
            raise ValueError(f"unknown impl {impl!r}")
        self._heads, self._kv, self._dim = num_heads, num_kv_heads, head_dim
        self._impl = impl
        with self.name_scope():
            self.q_proj = _dense(num_heads * head_dim, units, "q_")
            self.k_proj = _dense(num_kv_heads * head_dim, units, "k_")
            self.v_proj = _dense(num_kv_heads * head_dim, units, "v_")
            self.out_proj = _dense(units, num_heads * head_dim, "out_")

    def hybrid_forward(self, F, x):
        b, t = x.shape[0], x.shape[1]
        h, kv, d = self._heads, self._kv, self._dim

        def heads_first(proj, n):
            return F.transpose(F.reshape(proj(x), shape=(b, t, n, d)),
                               axes=(0, 2, 1, 3))

        q = heads_first(self.q_proj, h)
        k, v = heads_first(self.k_proj, kv), heads_first(self.v_proj, kv)
        if h != kv:
            k = F.repeat(k, repeats=h // kv, axis=1)
            v = F.repeat(v, repeats=h // kv, axis=1)
        with _jit.scope("attention"):
            out = F.scaled_dot_product_attention(
                q, k, v, causal=True,
                impl="flash" if self._impl == "flash" else "xla")
        out = F.reshape(F.transpose(out, axes=(0, 2, 1, 3)),
                        shape=(b, t, h * d))
        return self.out_proj(out)


class Mamba2Mixer(HybridBlock):
    """The Mamba-2 mixer (Dao & Gu, arXiv:2405.21060), as
    ``nemotron_h``'s ``M`` layers have it. ``in_proj`` gives, laid
    [x | B | C | z | dt]: x (``num_heads`` of ``head_dim``), B and C
    (``n_groups`` of ``state_size`` each), the output gate z (as wide as
    x) and dt (one a head). [x, B, C] pass a causal depthwise
    convolution of ``conv_kernel`` taps with a bias, then SiLU
    (``causal_conv_silu``: on a TPU one Pallas kernel pass each way that
    hands x, B, C on as three arrays); ``dt = softplus(dt + dt_bias)``
    (no clamp: the published ``time_step_limit`` ``(0, inf)`` holds
    every softplus), ``A = -exp(A_log)``, in float32; the chunked state-space scan (``ops/state_space.py``)
    with the skip ``D x``; the gated RMSNorm ``norm(y * silu(z))`` over
    ``n_groups`` groups of the channels, float32 inside, times its
    weight; ``out_proj``. No other biases. All but the two projections
    sits under the scope ``ssm``. x (B, T, units).

    Initial values as the published modeling code sets them:
    ``A_log = log(1 .. num_heads)``, ``D = 1``, ``dt_bias`` the inverse
    softplus of a step drawn log-uniform in ``dt_init`` = (min, max,
    floor); the convolution uniform in +-``conv_kernel ** -0.5``."""

    def __init__(self, units, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk=128, epsilon=1e-5,
                 dt_init=(0.001, 0.1, 1e-4), **kwargs):
        super().__init__(**kwargs)
        if num_heads % n_groups:
            raise ValueError(f"num_heads {num_heads} not divisible by "
                             f"n_groups {n_groups}")
        self._h, self._p, self._g, self._n = (num_heads, head_dim, n_groups,
                                              state_size)
        self._chunk, self._eps = int(chunk), float(epsilon)
        inner = num_heads * head_dim
        conv_dim = inner + 2 * n_groups * state_size
        from ... import ndarray as nd

        def dt_bias(shape):
            lo, hi, floor = (float(v) for v in dt_init)
            dt = nd.exp(nd.random.uniform(0, 1, shape)
                        * (float(_math.log(hi)) - float(_math.log(lo)))
                        + float(_math.log(lo)))
            dt = nd.clip(dt, floor, float("inf"))
            return dt + nd.log(-nd.expm1(-dt))     # softplus(this) = dt

        with self.name_scope():
            self.in_proj = _dense(conv_dim + inner + num_heads, units, "in_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(conv_dim, conv_kernel),
                init=_init.Uniform(conv_kernel ** -0.5))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(conv_dim,),
                init=_init.Uniform(conv_kernel ** -0.5))
            self.A_log = self.params.get(
                "A_log", shape=(num_heads,), init=_Own(
                    lambda shape: nd.log(nd.arange(1, shape[0] + 1))))
            self.D = self.params.get("D", shape=(num_heads,),
                                     init=_Own(nd.ones))
            self.dt_bias = self.params.get("dt_bias", shape=(num_heads,),
                                           init=_Own(dt_bias))
            self.norm_weight = self.params.get("norm_weight", shape=(inner,),
                                               init=_Own(nd.ones))
            self.out_proj = _dense(units, inner, "out_")

    def hybrid_forward(self, F, x, conv_weight, conv_bias, A_log, D, dt_bias,
                       norm_weight):
        b, t = x.shape[0], x.shape[1]
        h, p, g, n = self._h, self._p, self._g, self._n
        inner = h * p
        proj = self.in_proj(x)

        with _jit.scope("ssm"):
            xs, bs, cs, rest = F.causal_conv_silu(
                proj, conv_weight, conv_bias, parts=(inner, g * n, g * n))
            z = F.slice_axis(rest, axis=-1, begin=0, end=inner)
            dt = F.Activation(
                F.cast(F.slice_axis(rest, axis=-1, begin=inner,
                                    end=inner + h), dtype="float32")
                + F.cast(dt_bias, dtype="float32"), act_type="softrelu")
            y = F.mamba_chunk_scan(
                F.reshape(xs, shape=(b, t, h, p)), dt,
                -F.exp(F.cast(A_log, dtype="float32")),
                F.reshape(bs, shape=(b, t, g, n)),
                F.reshape(cs, shape=(b, t, g, n)), D, chunk=self._chunk)
            gated = F.cast(F.reshape(y, shape=(b, t, g, inner // g)),
                           dtype="float32") * F.Activation(
                F.cast(F.reshape(z, shape=(b, t, g, inner // g)),
                       dtype="float32"), act_type="silu")
            # the weight as (groups, channels of a group): one norm a group
            y = F.RMSNorm(gated, F.reshape(norm_weight,
                                           shape=(g, inner // g)),
                          eps=self._eps)
            y = F.cast(F.reshape(y, shape=(b, t, inner)),
                       dtype=str(proj.dtype))
        return self.out_proj(y)


class SquaredReLUMLP(HybridBlock):
    """Ungated MLP without biases: ``down(relu(up x)^2)``
    (``nemotron_h``'s ``relu2``)."""

    def __init__(self, units, hidden, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.up = _dense(hidden, units, "up_")
            self.down = _dense(units, hidden, "down_")

    def hybrid_forward(self, F, x):
        return self.down(F.square(F.relu(self.up(x))))


class GatedMLP(HybridBlock):
    """SiLU-gated MLP without biases:
    ``down(silu(gate x) * up x)``, gate and up in one projection
    (``gate_up_``: gate rows first)."""

    def __init__(self, units, hidden, **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden
        with self.name_scope():
            self.gate_up = _dense(2 * hidden, units, "gate_up_")
            self.down = _dense(units, hidden, "down_")

    def hybrid_forward(self, F, x):
        h = self.gate_up(x)
        gate = F.slice_axis(h, axis=-1, begin=0, end=self._hidden)
        up = F.slice_axis(h, axis=-1, begin=self._hidden,
                          end=2 * self._hidden)
        return self.down(F.Activation(gate, act_type="silu") * up)


class SparseMoE(HybridBlock):
    """A sparse expert layer that is told which experts it holds.

    The router scores ALL ``num_experts_total`` experts in float32,
    takes the ``top_k`` largest of the softmax and (``renormalize``)
    renormalises their weights over themselves. Of those assignments,
    the ones whose expert is in ``experts_held`` -- ``(first, count)``
    or a ``range``; default all -- are computed here:
    ``sum w_e * down_e(silu(gate_e x) * up_e x)``, as grouped matrix
    products over the sorted assignments (``ops/moe.py``): no capacity
    factor, no token dropped for any routing. What the other experts
    would add is left out: that is the part of the result one device of
    an expert-parallel group gives. ``shared_hidden`` adds a shared
    expert behind a sigmoid gate, ``sigmoid(shared_gate x) * shared(x)``
    (``shared_gate=False``: ``shared(x)`` as it is).

    ``activation='relu2'`` takes ungated experts, each
    ``down_e(relu(up_e x)^2)`` with its up matrix ``experts_up_weight``
    (count, units, hidden), and the shared expert a ``SquaredReLUMLP``
    (``nemotron_h``'s); the default 'swiglu' is the gated form above.

    ``score_func='sigmoid'`` scores each expert on its own;
    ``route_scale`` multiplies the chosen weights; ``expert_bias=True``
    keeps a per-expert state (all ``num_experts_total``, no gradient,
    zero at first) that is added to the scores for the choice alone
    (``ops.moe.moe_router``): what moves it between steps to balance the
    experts is not built.

    The auxiliary state ``expert_tokens`` (count + 1, moved by every
    call as BatchNorm moves its statistics) holds the last call's
    assignments to each held expert and the tokens that chose no held
    expert. Scopes: ``moe``, with
    ``moe_router`` and ``moe_experts`` inside."""

    def __init__(self, units, hidden, num_experts_total, top_k,
                 experts_held=None, shared_hidden=0, renormalize=True,
                 score_func="softmax", route_scale=1.0, expert_bias=False,
                 shared_gate=True, activation="swiglu", **kwargs):
        super().__init__(**kwargs)
        from ...ops.moe import ACTIVATIONS

        if activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {activation!r}; known: "
                             f"{ACTIVATIONS}")
        gated = activation == "swiglu"
        self._activation = activation
        if experts_held is None:
            experts_held = (0, num_experts_total)
        if isinstance(experts_held, range):
            experts_held = (experts_held.start, len(experts_held))
        first, count = (int(n) for n in experts_held)
        if not 0 <= first < first + count <= num_experts_total:
            raise ValueError(f"experts_held {experts_held} is not a range "
                             f"of the {num_experts_total} experts")
        self._route = {"top_k": int(top_k), "renormalize": bool(renormalize),
                       "score_func": score_func, "scale": float(route_scale)}
        self._first = first

        def per_expert(fan_in, fan_out):    # Xavier of one expert's matrix
            return _init.Uniform((6.0 / (fan_in + fan_out)) ** 0.5)

        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(num_experts_total, units))
            if gated:
                self.experts_gate_up_weight = self.params.get(
                    "experts_gate_up_weight",
                    shape=(count, units, 2 * hidden),
                    init=per_expert(units, hidden))
            else:
                self.experts_up_weight = self.params.get(
                    "experts_up_weight", shape=(count, units, hidden),
                    init=per_expert(units, hidden))
            self.experts_down_weight = self.params.get(
                "experts_down_weight", shape=(count, hidden, units),
                init=per_expert(hidden, units))
            self.expert_tokens = self.params.get(
                "expert_tokens", shape=(count + 1,), grad_req="null",
                init="zeros", differentiable=False)
            if expert_bias:
                self.expert_bias = self.params.get(
                    "expert_bias", shape=(num_experts_total,),
                    grad_req="null", init="zeros", differentiable=False)
            if shared_hidden:
                self.shared = (GatedMLP if gated else SquaredReLUMLP)(
                    units, shared_hidden, prefix="shared_")
                self.shared_gate = _dense(1, units, "shared_gate_") \
                    if shared_gate else None
            else:
                self.shared = self.shared_gate = None

    def hybrid_forward(self, F, x, router_weight, experts_down_weight,
                       expert_tokens, experts_gate_up_weight=None,
                       experts_up_weight=None, expert_bias=None):
        with _jit.scope("moe"):
            with _jit.scope("moe_router"):
                scored = (x, router_weight) if expert_bias is None \
                    else (x, router_weight, expert_bias)
                weights, experts = F.moe_router(*scored, **self._route)
            with _jit.scope("moe_experts"):
                up = experts_gate_up_weight if experts_up_weight is None \
                    else experts_up_weight
                out = F.moe_experts(x, weights, experts, up,
                                    experts_down_weight, expert_tokens,
                                    first_expert=self._first,
                                    activation=self._activation)
            if self.shared is not None:
                if self.shared_gate is None:
                    out = out + self.shared(x)
                else:
                    out = out + F.sigmoid(self.shared_gate(x)) \
                        * self.shared(x)
        return out
