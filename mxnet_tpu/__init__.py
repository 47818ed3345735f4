"""mxnet_tpu — a TPU-native deep learning framework with MXNet 1.6 capability
parity (reference: Apache MXNet 1.6.0). Built on JAX/XLA/PJRT with Pallas for
custom kernels; see SURVEY.md at the repo root for the blueprint.

Usage mirrors the reference:

    import mxnet_tpu as mx
    x = mx.nd.zeros((2, 3), ctx=mx.tpu())
    with mx.autograd.record():
        y = mx.nd.FullyConnected(x, w, b, num_hidden=10)
    y.backward()
"""
from __future__ import annotations

import time as _time

_T_IMPORT_NS = _time.perf_counter_ns()     # for the setup.import span


def _configure_jax():
    """Process-wide jax settings, resolved once at import.

    Numerics: float32 default (f64 is emulated/slow on TPU and silently
    changes promotion semantics). Opt into x64 per-process with
    MXNET_TPU_ENABLE_X64=1 (e.g. for float64 parity testing on CPU).

    Compile cache: ONE persistent XLA-executable cache per checkout.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is jax's own setting and
    nothing here touches it (its size is then jax's business too:
    ``JAX_COMPILATION_CACHE_MAX_SIZE``); otherwise the cache lives at
    the fixed ``<checkout>/.jax_cache`` (the directory is part of what a
    later process must find again, so never a temporary name, a pid or
    a time), held under ``MXNET_TPU_COMPILE_CACHE_MAX_MB`` by one
    oldest-first sweep when the process ends. No other code sets a
    cache directory. On an accelerator every executable is kept, not
    only those past jax's 1 s compile-time floor: eager mode compiles
    hundreds of small per-op programs, and a second process in the same
    checkout should compile none of them again. A process pinned to
    XLA-CPU keeps jax's floor: its compiles are cheap, and jaxlib's CPU
    loader logs a multi-KB "machine feature" error on every hit."""
    import atexit
    import os

    import jax

    from .base import compile_cache_limit_bytes, evict_oldest

    if os.environ.get("MXNET_TPU_ENABLE_X64") == "1":
        jax.config.update("jax_enable_x64", True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cache = os.path.join(checkout, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache)
        # swept once per process, not by jax_compilation_cache_max_size:
        # that rescans the directory on every write (a cold 337-compile
        # chip_smoke took 203 s with it against 150 s without)
        atexit.register(evict_oldest, cache, compile_cache_limit_bytes())
    pinned_to_cpu = (jax.config.jax_platforms or "").startswith("cpu")
    if not pinned_to_cpu and not os.environ.get(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


_configure_jax()

from .attribute import AttrScope
from .base import MXNetError, __version__
from .context import (Context, cpu, cpu_pinned, current_context, gpu,
                      num_gpus, num_tpus, tpu)

from . import base
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from . import jit

__all__ = ["MXNetError", "Context", "cpu", "gpu", "tpu", "cpu_pinned",
           "current_context", "num_gpus", "num_tpus", "nd", "ndarray",
           "autograd", "random", "jit", "__version__"]


def __getattr__(name):
    """Lazy subpackage loading keeps `import mxnet_tpu` light."""
    import importlib

    lazy = {
        "sym": ".symbol", "symbol": ".symbol", "gluon": ".gluon",
        "module": ".module", "mod": ".module", "optimizer": ".optimizer",
        "opt": ".optimizer", "metric": ".metric", "io": ".io",
        "kv": ".kvstore", "kvstore": ".kvstore", "initializer": ".initializer",
        "init": ".initializer", "lr_scheduler": ".lr_scheduler",
        "callback": ".callback", "image": ".image", "recordio": ".recordio",
        "model": ".model", "np": ".numpy", "numpy": ".numpy",
        "parallel": ".parallel", "profiler": ".profiler", "amp": ".amp",
        "util": ".util", "runtime": ".runtime", "test_utils": ".test_utils",
        "executor": ".executor", "monitor": ".monitor",
        "visualization": ".visualization", "contrib": ".contrib",
        "engine": ".engine", "operator": ".operator",
        "npx": ".numpy_extension", "numpy_extension": ".numpy_extension",
        "resilience": ".resilience", "serving": ".serving",
        "capture": ".capture", "observability": ".observability",
    }
    if name in lazy:
        mod = importlib.import_module(lazy[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")


# this import, from its first line to here (jax's too where this is what
# loads jax; the lazy subpackages above come later, where they are used)
from .observability import trace as _obs_trace  # noqa: E402

_obs_trace.record("setup.import", _T_IMPORT_NS,
                  _time.perf_counter_ns() - _T_IMPORT_NS)
