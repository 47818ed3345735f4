"""Base utilities: errors, dtype maps, registries.

TPU-native re-design of the reference's dmlc-core surface
(/root/reference/include/mxnet/base.h, 3rdparty dmlc-core usage sites):
typed parameter structs become plain keyword arguments validated at the
registry layer, logging/CHECK become Python exceptions, and `dmlc::GetEnv`
becomes :func:`getenv`.
"""
from __future__ import annotations

import os

import numpy as _np

__all__ = ["MXNetError", "getenv", "string_types", "numeric_types", "integer_types"]

MXNET_TPU_MAJOR = 2
MXNET_TPU_MINOR = 0
__version__ = "2.0.0.tpu0"


class MXNetError(RuntimeError):
    """Error raised by the framework (parity: dmlc::Error / MXGetLastError)."""


string_types = (str,)
numeric_types = (float, int, _np.generic)
integer_types = (int, _np.integer)

# dtype name <-> numpy dtype tables (reference: include/mxnet/base.h TypeFlag).
_DTYPE_NAMES = [
    "float32", "float64", "float16", "uint8", "int32", "int8", "int64",
    "bool", "int16", "uint16", "uint32", "uint64", "bfloat16",
]
DTYPE_NAME_TO_NP = {n: _np.dtype(n) if n != "bfloat16" else None for n in _DTYPE_NAMES}


def np_dtype(dtype):
    """Canonicalize a dtype-ish value to something jax/numpy accepts."""
    if dtype is None:
        return None
    if isinstance(dtype, str) and dtype == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return _np.dtype(dtype) if not hasattr(dtype, "dtype") else dtype


def getenv(name, default):
    """Typed env lookup (parity: dmlc::GetEnv, env list in
    docs/static_site/src/pages/api/faq/env_var.md)."""
    val = os.environ.get(name)
    if val is None:
        return default
    if isinstance(default, bool):
        return val not in ("0", "false", "False", "")
    return type(default)(val)


def compile_cache_limit_bytes():
    """``MXNET_TPU_COMPILE_CACHE_MAX_MB`` in bytes: the one size cap of
    both compile-cache layers (jax's persistent executable cache and
    the program artifacts of capture.AOTCache)."""
    try:
        mb = float(os.environ.get("MXNET_TPU_COMPILE_CACHE_MAX_MB", "2048"))
    except ValueError:
        mb = 2048.0
    return int(mb * 1e6)


def evict_oldest(directory, limit_bytes):
    """Delete oldest-mtime files of ``directory`` until it holds at most
    ``limit_bytes``; returns how many went. One scan; a file another
    process removed first is not an error."""
    entries, total = [], 0
    try:
        with os.scandir(directory) as it:
            for e in it:
                try:
                    st = e.stat()
                except OSError:
                    continue
                entries.append((st.st_mtime, st.st_size, e.path))
                total += st.st_size
    except OSError:
        return 0
    evicted = 0
    for _, size, path in sorted(entries):
        if total <= limit_bytes:
            break
        try:
            os.remove(path)
        except OSError:
            continue
        total -= size
        evicted += 1
    return evicted


class _Registry:
    """Minimal name->object registry with alias support."""

    def __init__(self, kind):
        self.kind = kind
        self._map = {}

    def register(self, obj, name=None):
        key = (name or getattr(obj, "__name__", None) or str(obj)).lower()
        self._map[key] = obj
        return obj

    def get(self, name):
        key = name.lower()
        if key not in self._map:
            raise MXNetError(
                f"{self.kind} '{name}' is not registered. "
                f"Known: {sorted(self._map)}"
            )
        return self._map[key]

    def find(self, name):
        return self._map.get(name.lower())

    def keys(self):
        return sorted(self._map)


def listify(x):
    """Normalize control-flow data/state arguments: None -> ([], False),
    list/tuple -> (list, True), scalar -> ([x], False). Shared by the
    eager (ndarray/contrib.py) and symbolic (symbol/contrib.py) control
    flow so the nesting contract cannot drift."""
    if x is None:
        return [], False
    if isinstance(x, (list, tuple)):
        return list(x), True
    return [x], False
