"""Device contexts: ``mx.cpu()``, ``mx.gpu()``, ``mx.tpu()``.

TPU-native analogue of the reference's ``python/mxnet/context.py`` and the
C++ ``Context`` struct in ``include/mxnet/base.h`` [unverified]. A Context
names a logical device ``(device_type, device_id)`` and resolves to a concrete
``jax.Device``. The north-star adds ``mx.tpu()`` as the accelerator context;
``mx.gpu()`` is kept as a migration alias that resolves to the platform's
accelerator so reference-era scripts run unchanged.

A thread-local default-context stack supports ``with mx.tpu(0):`` scoping,
mirroring the reference's ``Context.default_ctx`` behavior.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "cpu_pinned",
    "gpu",
    "tpu",
    "current_context",
    "num_gpus",
    "num_tpus",
    "num_devices",
]

_ACCEL_PLATFORMS = ("tpu", "gpu", "cuda", "rocm")


class Context:
    """A logical device. ``device_type`` in {'cpu', 'gpu', 'tpu', 'cpu_pinned'}.

    ``gpu`` and ``tpu`` both resolve to the platform accelerator (TPU on TPU
    machines); ``cpu_pinned`` is an alias of cpu (host memory is unified from
    XLA's point of view).
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 4}

    _default_ctx = threading.local()

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_id = device_type.device_id
            device_type = device_type.device_type
        if device_type not in self.devstr2type:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)

    # -- resolution to concrete jax devices ---------------------------------
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete ``jax.Device`` (raises if absent)."""
        devs = self._platform_devices(self.device_type)
        if not devs:
            raise MXNetError(
                f"no devices available for context {self}; "
                f"jax backend has {[d.platform for d in jax.devices()]}"
            )
        if self.device_id >= len(devs):
            raise MXNetError(f"{self}: only {len(devs)} such device(s) present")
        return devs[self.device_id]

    @staticmethod
    def _platform_devices(device_type: str):
        all_devs = jax.devices()
        if device_type in ("cpu", "cpu_pinned"):
            cpus = [d for d in all_devs if d.platform == "cpu"]
            if cpus:
                return cpus
            try:
                return jax.devices("cpu")
            except RuntimeError:
                return all_devs  # single-backend runtime: one device namespace
        # gpu/tpu: any accelerator platform
        accels = [d for d in all_devs if d.platform in _ACCEL_PLATFORMS]
        return accels or all_devs

    # -- identity -----------------------------------------------------------
    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- default-context stack ---------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()
        return False

    def empty_cache(self):
        """Reference freed the GPU memory pool here; XLA manages HBM itself."""

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._default_ctx, "stack", None)
        if stack:
            return stack[-1]
        return _initial_default_context()


def _initial_default_context() -> Context:
    """Accelerator if present, else cpu (reference defaulted to cpu(0))."""
    global _CACHED_INITIAL
    if _CACHED_INITIAL is None:
        accels = [d for d in jax.devices() if d.platform in _ACCEL_PLATFORMS]
        _CACHED_INITIAL = Context("tpu", 0) if accels else Context("cpu", 0)
    return _CACHED_INITIAL


_CACHED_INITIAL: Optional[Context] = None


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Migration alias: resolves to the platform accelerator (TPU here)."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def current_context() -> Context:
    return Context.default_ctx()


def num_devices(device_type: str = "tpu") -> int:
    return len(Context._platform_devices(device_type))


def num_gpus() -> int:
    devs = [d for d in jax.devices() if d.platform in _ACCEL_PLATFORMS]
    return len(devs)


def num_tpus() -> int:
    return num_gpus()
