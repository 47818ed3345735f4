"""Device contexts.

Parity with the reference's Context (include/mxnet/base.h:74-200,
python/mxnet/context.py): `cpu()`, `tpu()` (first-class, the north star),
plus `gpu()` as an alias for the local accelerator so reference scripts run
unmodified. A Context maps onto a concrete `jax.Device`; storage placement
goes through PJRT via `jax.device_put` rather than a custom allocator —
HBM pooling, streams and copy engines are PJRT's job.
"""
from __future__ import annotations

import os
import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]


def _jax():
    import jax

    return jax


class Context:
    """A device context. devtype: cpu=1, gpu=2 (alias->accelerator), cpu_pinned=3, tpu=13."""

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 13: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._jax_device = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *args):
        Context._default_ctx.stack.pop()

    # -- jax bridge ------------------------------------------------------
    def jax_device(self):
        """Resolve to a concrete jax.Device (PJRT device)."""
        if self._jax_device is not None:
            return self._jax_device
        jax = _jax()
        dt = self.device_type
        # Addressable devices only: under jax.distributed, jax.devices()
        # is the GLOBAL list and device 0 may belong to another process.
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError as e:
                raise MXNetError(
                    f"{self}: jax's cpu backend is not initialised "
                    f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
                    "excludes it); add 'cpu' to JAX_PLATFORMS or leave it "
                    "unset for explicit host placement") from e
        else:  # tpu / gpu both mean "the local accelerator"
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    f"{self}: no accelerator — jax.local_devices() reports "
                    f"only {jax.default_backend()} devices. An accelerator "
                    "context never resolves to the host CPU; use mx.cpu() "
                    "or the default context")
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: only {len(devs)} device(s) of this type are visible"
            )
        self._jax_device = devs[self.device_id]
        return self._jax_device

    def empty_cache(self):
        """Parity: Context.empty_cache (pooled allocator flush). PJRT manages
        the HBM pool; this is a best-effort hint."""
        import gc

        gc.collect()


def _accelerator_devices():
    return [d for d in _jax().local_devices() if d.platform != "cpu"]


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Alias context for the local accelerator (reference scripts use mx.gpu())."""
    return Context("gpu", device_id)


def num_tpus():
    return len(_accelerator_devices())


def num_gpus():
    return num_tpus()


def current_context():
    """The innermost ``with ctx:`` scope, else the first local device of
    jax's default backend: ``tpu(0)`` on a chip host, ``cpu(0)`` under
    ``JAX_PLATFORMS=cpu`` — arrays, parameters and predictors created
    without a ``ctx`` land on the chip when there is one."""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return Context("cpu" if _jax().default_backend() == "cpu" else "tpu", 0)


def context_from_jax_device(dev):
    """Inverse mapping jax.Device -> Context."""
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    accel = _accelerator_devices()
    for i, d in enumerate(accel):
        if d == dev:
            return Context("tpu", i)
    return Context("tpu", getattr(dev, "id", 0))
