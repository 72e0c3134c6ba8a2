"""Device contexts.

Reference parity: python/mxnet/context.py (Context, mx.cpu()/mx.gpu(i),
thread-local default context, num_gpus ~L1-300).

TPU-native mapping:
  * ``mx.tpu(i)``  -> i-th accelerator device reported by jax (the north-star
    first-class context from BASELINE.json).  Raises when jax shows no
    accelerator: a TPU context never computes on the host.
  * ``mx.gpu(i)``  -> alias of ``mx.tpu(i)``: reference scripts that say
    ``mx.gpu(0)`` should run unmodified on the accelerator that is present.
  * ``mx.cpu(i)``  -> i-th jax CPU device (host), also in a process whose
    default backend is the accelerator.
  * ``mx.cpu_pinned()`` -> host CPU (PjRt manages pinned staging internally).

A Context is resolved lazily to a ``jax.Device`` so importing mxnet_tpu does
not force backend initialization (tests re-point jax at a virtual CPU mesh
before first use).
"""
from __future__ import annotations

import threading
from typing import List, Optional

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "cpu_pinned",
    "current_context",
    "num_gpus",
    "num_tpus",
    "pin_platform",
    "normalize_memory_stats",
]


def normalize_memory_stats(raw) -> dict:
    """Normalize a PjRt ``Device.memory_stats()`` result to a stable
    schema: ``{"bytes_in_use", "peak_bytes_in_use", "bytes_limit",
    "available"}``.

    PjRt's dict is backend-dependent (TPU/GPU expose the TCMalloc-style
    allocator counters; XLA:CPU returns ``None``), and the raw shape was
    leaking to callers — ``Context.memory_stats()`` used to hand back the
    raw dict or a silent ``None``.  The CPU fallback is documented:
    ``available=False`` with zeroed counters, so callers branch on ONE
    flag instead of probing for keys; ``mxnet_tpu.memwatch`` then derives
    usage from the ``jax.live_arrays()`` census instead.  A dict without
    ``bytes_in_use`` counts as unavailable too — all-zero counters must
    never masquerade as a real reading."""
    if not isinstance(raw, dict) or "bytes_in_use" not in raw:
        return {"bytes_in_use": 0, "peak_bytes_in_use": 0,
                "bytes_limit": 0, "available": False}

    def _int(key, default=0):
        try:
            return int(raw.get(key, default))
        except (TypeError, ValueError):
            return default

    in_use = _int("bytes_in_use")
    return {"bytes_in_use": in_use,
            "peak_bytes_in_use": _int("peak_bytes_in_use", in_use),
            "bytes_limit": _int("bytes_limit"),
            "available": True}

_ACCEL_TYPES = ("tpu", "gpu")


def _jax():
    import jax

    return jax


def pin_platform(name: str) -> None:
    """Pin the jax backend platform (e.g. "cpu") before first device touch:
    ``jax.devices()`` and every default mesh then resolve to that platform.
    What the examples' and tools' ``--device cpu`` does, so that a run that
    names the CPU uses it even on a host with a chip.
    """
    _jax().config.update("jax_platforms", name)


class Context:
    """A device context; compares by (device_type, device_id)."""

    _default_ctx = threading.local()
    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}

    def __init__(self, device_type: str, device_id: int = 0):
        if device_type not in self.devstr2type:
            raise MXNetError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)
        self._old_ctx: Optional["Context"] = None

    # -- identity ----------------------------------------------------------
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

    # -- jax resolution ----------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device (lazy; raises if absent)."""
        jax = _jax()
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            # LOCAL devices: under multi-process SPMD, jax.devices() lists
            # every host's devices; a context must resolve to one this
            # process can address (reference semantics: each worker sees
            # only its own devices).
            devs = jax.local_devices(backend="cpu")
            return devs[min(self.device_id, len(devs) - 1)]
        # accelerator: gpu is an alias for whatever accelerator jax exposes
        devs = _accel_devices()
        if not devs:
            raise MXNetError(
                f"{self} requested but no accelerator device is visible to jax"
            )
        if self.device_id >= len(devs):
            raise MXNetError(f"{self} out of range: {len(devs)} device(s) visible")
        return devs[self.device_id]

    # -- scope -------------------------------------------------------------
    def __enter__(self):
        self._old_ctx = current_context()
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    def empty_cache(self):
        """Reference: mx.context.Context.empty_cache; PjRt pools internally."""

    def memory_stats(self) -> dict:
        """This device's memory stats, normalized to the stable schema of
        :func:`normalize_memory_stats` — never ``None``: backends without
        allocator stats (XLA:CPU) return ``available=False`` with zeroed
        counters (``mxnet_tpu.memwatch`` falls back to the live-array
        census there)."""
        dev = self.jax_device
        stats = getattr(dev, "memory_stats", None)
        raw = None
        if stats is not None:
            try:
                raw = stats()
            except Exception:
                raw = None
        return normalize_memory_stats(raw)


def _accel_devices() -> List:
    return [d for d in _jax().local_devices() if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def num_gpus() -> int:
    """Number of accelerator devices (gpu alias — see module docstring)."""
    try:
        return len(_accel_devices())
    except RuntimeError:
        return 0


def num_tpus() -> int:
    return num_gpus()


def current_context() -> Context:
    if not hasattr(Context._default_ctx, "value") or Context._default_ctx.value is None:
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value
