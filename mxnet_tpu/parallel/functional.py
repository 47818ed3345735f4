"""Gluon net -> pure jax function bridge.

The TPU-native counterpart of the reference's executor bind: a Block's
imperative forward is re-run with its parameter cells temporarily rebound to
tracers, producing a pure ``(params, inputs) -> outputs`` function that
jax.jit / pjit can compile and shard. This is the same mutation->functional
discipline as mxnet_tpu.jit (SURVEY.md §7 hard part 2), packaged for the
distributed path.
"""
from __future__ import annotations

from ..ndarray.ndarray import NDArray

__all__ = ["functional_call", "param_arrays", "aux_arrays", "RNG_KEY"]

# Reserved aux-dict entry threading the global PRNG key through the pure
# function: stochastic ops (Dropout) split it per call, and the advanced key
# rides back out in new_aux — so repeated jitted steps draw fresh masks
# instead of baking one key in as a compile-time constant.
RNG_KEY = "__rng_key__"


def _split_params(net):
    params, aux = {}, {}
    for name, p in net.collect_params().items():
        (params if p.grad_req != "null" else aux)[name] = p
    return params, aux


def param_arrays(net):
    """Trainable parameter values as a {name: jax.Array} dict."""
    return {k: p.data().data_ for k, p in _split_params(net)[0].items()}


def aux_arrays(net):
    """Auxiliary state (BatchNorm running stats, RNG key, ...) as
    {name: jax.Array}. Includes the threaded PRNG key under ``RNG_KEY``."""
    from .. import random as _random

    out = {k: p.data().data_ for k, p in _split_params(net)[1].items()}
    out[RNG_KEY] = _random.generator_key().data_
    return out


def functional_call(net, train=False):
    """Returns ``fn(params, aux, *inputs) -> (outputs, new_aux)`` — a pure,
    jittable view of ``net``.

    ``params``/``aux`` are {name: array} dicts matching param_arrays /
    aux_arrays. In train mode, mutated aux state (running stats) is returned
    as ``new_aux``; in eval mode new_aux == aux.
    """
    from .. import autograd
    from .. import random as _random
    from ..jit import TraceSession

    params, aux = _split_params(net)
    cells = {name: p.data() for name, p in {**params, **aux}.items()}
    key_cell = _random.generator_key()

    def fn(pvals, avals, *inputs):
        saved = {n: c._data for n, c in cells.items()}
        saved_key = key_cell._data
        vals = {**pvals, **avals}
        try:
            for n, c in cells.items():
                if n in vals:
                    c._data = vals[n]
            if RNG_KEY in avals:
                key_cell._data = avals[RNG_KEY]
            in_nds = [NDArray(x) for x in inputs]
            with TraceSession(name_blocks=True) as sess:
                for a in in_nds:
                    sess.note_created(a)
                with autograd.pause(train_mode=train):
                    out = net(*in_nds)
            outs = [o.data_ for o in (out if isinstance(out, (list, tuple))
                                      else [out])]
            new_aux = {n: cells[n]._data for n in avals if n != RNG_KEY}
            if RNG_KEY in avals:
                new_aux[RNG_KEY] = key_cell._data
        finally:
            for n, c in cells.items():
                c._data = saved[n]
            key_cell._data = saved_key
        return (outs[0] if len(outs) == 1 else tuple(outs)), new_aux

    return fn
