"""Utility toggles and decorators.

Parity: python/mxnet/util.py — the NumPy-semantics switches (set_np/use_np,
is_np_array, is_np_shape) that gate the `mx.np` frontend, plus misc helpers.
TPU-native: the flags only flip Python-side semantics (true scalars, zero-dim
shapes); the kernels are shared with the nd namespace.
"""
from __future__ import annotations

import functools
import threading

__all__ = ["set_np", "reset_np", "set_np_shape", "is_np_shape",
           "set_np_array", "is_np_array", "use_np", "use_np_shape",
           "use_np_array", "np_shape", "np_array", "getenv", "setenv",
           "get_gpu_count", "get_gpu_memory", "default_array",
           "get_cuda_compute_capability"]

_STATE = threading.local()


def _state():
    if not hasattr(_STATE, "np_shape"):
        _STATE.np_shape = False
        _STATE.np_array = False
    return _STATE


def set_np_shape(active):
    """Allow zero-dim/zero-size shapes (reference util.py set_np_shape)."""
    st = _state()
    prev = st.np_shape
    st.np_shape = bool(active)
    return prev


def is_np_shape():
    return _state().np_shape


def set_np_array(active):
    st = _state()
    prev = st.np_array
    st.np_array = bool(active)
    return prev


def is_np_array():
    return _state().np_array


def set_np(shape=True, array=True):
    """Enter NumPy semantics: mx.np arrays returned from Gluon blocks,
    numpy-style shapes. Parity: util.py set_np."""
    if not shape and array:
        raise ValueError("invalid: array semantics require shape semantics")
    set_np_shape(shape)
    set_np_array(array)


def reset_np():
    """Parity: util.py reset_np."""
    set_np(False, False)


class _NpScope:
    def __init__(self, shape, array):
        self._shape, self._array = shape, array

    def __enter__(self):
        self._prev_s = set_np_shape(self._shape)
        self._prev_a = set_np_array(self._array) if self._shape else \
            set_np_array(False)
        return self

    def __exit__(self, *a):
        set_np_shape(self._prev_s)
        set_np_array(self._prev_a)


def np_shape(active=True):
    return _NpScope(active, is_np_array())


def np_array(active=True):
    return _NpScope(is_np_shape(), active)


def _make_decorator(shape, array):
    def deco(func):
        if isinstance(func, type):
            # class decorator: wrap every callable attr's entry
            for name in dir(func):
                if name.startswith("__") and name not in ("__call__",):
                    continue
                attr = getattr(func, name, None)
                if callable(attr) and not isinstance(attr, type):
                    setattr(func, name, _make_decorator(shape, array)(attr))
            return func

        @functools.wraps(func)
        def wrapper(*args, **kwargs):
            with _NpScope(shape, array):
                return func(*args, **kwargs)
        return wrapper
    return deco


def use_np_shape(func):
    """Decorator: run with np-shape semantics (util.py use_np_shape)."""
    return _make_decorator(True, is_np_array())(func)


def use_np_array(func):
    return _make_decorator(is_np_shape(), True)(func)


def use_np(func):
    """Decorator: run with full NumPy semantics (util.py use_np)."""
    return _make_decorator(True, True)(func)


def getenv(name):
    """Parity: util.py getenv (reads the process env MXNET_* flags)."""
    import os

    return os.environ.get(name)


def setenv(name, value):
    import os

    os.environ[name] = value


def get_gpu_count():
    from .context import num_gpus

    return num_gpus()


def get_gpu_memory(dev_id=0):
    """(free, total) HBM bytes of accelerator ``dev_id``, from PJRT's
    allocator statistics."""
    from .context import tpu

    stats = tpu(dev_id).jax_device().memory_stats()
    return (stats["bytes_limit"] - stats["bytes_in_use"],
            stats["bytes_limit"])


def get_cuda_compute_capability(ctx=None):
    """No CUDA in this build; kept for API-compat probes."""
    return None


def default_array(source_array, ctx=None, dtype=None):
    """Create an ndarray of the active (np or nd) flavor — util.py."""
    if is_np_array():
        from . import numpy as _mx_np

        return _mx_np.array(source_array, ctx=ctx, dtype=dtype)
    from . import ndarray as _nd

    return _nd.array(source_array, ctx=ctx, dtype=dtype)
