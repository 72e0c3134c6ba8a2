"""Async step pipeline primitives: lazy loss handles and the bounded
in-flight dispatch window (docs/PERFORMANCE.md §Async pipeline).

The reference's dependency engine makes every ``engine.push`` asynchronous:
the host thread races ahead preparing the next batch while the device
computes, and only ``WaitToRead`` blocks.  jax already queues execution
asynchronously on every backend, so the only thing standing between this
tree and the same pipeline was the per-step host round-trip the callers
imposed by forcing each loss to a host scalar immediately.

This module supplies the missing pieces:

  * :class:`AsyncLoss` — the lazy handle ``DataParallelStep.step()``
    returns instead of a host scalar.  ``float()`` / ``.asnumpy()`` /
    ``.wait()`` force the readback; until then the host never blocks on
    the device.
  * :class:`StepFence` — the same discipline for executors that update
    buffers in place and have no scalar to hand back (``gluon.Trainer``,
    ``module.Module``): waiting on the fence syncs that step's updates.
  * :class:`InflightRing` — the bounded window.  ``MX_ASYNC_INFLIGHT``
    caps how many dispatched-but-unforced steps may be pending; admitting
    a new step past the cap blocks on the *oldest* pending handle first,
    so the dispatch queue can never run away from the device.  Unset,
    the window is 2, and the compiled step's (its handle pins one scalar,
    where a fence pins a generation of buffers) grows to as many as 8
    while a step goes from dispatch to its end in under 1.25 s: about a
    second of work is then queued on the device, and a host that is held
    for less costs the device nothing.  ``MX_ASYNC_INFLIGHT=0`` restores
    fully synchronous behavior (every step forced at dispatch).
  * :func:`drain_all` — force every pending handle in the process; the
    SIGTERM preemption path (``fault.install_preemption_handler``) calls
    it so a final sync checkpoint never snapshots ahead of an in-flight
    step it hasn't observed failing.

Asynchrony changes *when* the host observes results, never what is
computed: per-step losses and final weights are bitwise identical across
window sizes (asserted by ``tests/test_async_step.py``).  Exceptions a
deferred step raises surface at the forcing site, wrapped in an
``MXNetError`` naming the dispatching step.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
import weakref
from collections import deque
from typing import Optional

import numpy as np

from .. import memwatch
from .. import telemetry
from ..base import MXNetError

__all__ = ["AsyncLoss", "AsyncResult", "StepFence", "InflightRing",
           "inflight_limit", "drain_all"]

_DEFAULT_INFLIGHT = 2
# DataParallelStep's window where MX_ASYNC_INFLIGHT is unset, as (most
# handles, seconds from a step's dispatch to its end).  An AsyncLoss pins
# one scalar, so depth costs no memory; what it costs is how far the host
# is ahead (a drain, a deferred error), hence the bound in seconds.  On a
# host that shares its cores the process is held for 0.1 to 1.2 s now and
# then (PERF.md, PR 31): with two steps in flight every hold past one
# step's time idles the device
_COMPILED_STEP_DEEP = (8, 1.25)

# every ring in the process, so preemption/checkpoint paths can drain
# pending work they never saw dispatched (weak: a dropped step object
# must not be kept alive by the registry)
_live_rings: "weakref.WeakSet[InflightRing]" = weakref.WeakSet()
_rings_lock = threading.Lock()


def inflight_limit() -> int:
    """The in-flight window size, re-read from ``MX_ASYNC_INFLIGHT`` on
    every call so tests/benches can flip modes without rebuilding steps.
    0 means synchronous (force at dispatch)."""
    try:
        return max(0, int(os.environ.get("MX_ASYNC_INFLIGHT",
                                         _DEFAULT_INFLIGHT)))
    except (TypeError, ValueError):
        return _DEFAULT_INFLIGHT


def compiled_step_window():
    """``(limit, deep)`` for :meth:`InflightRing.make_room` as
    ``DataParallelStep.step`` calls it: the variable's count alone where
    it is set, else the default with ``_COMPILED_STEP_DEEP``."""
    deep = None if "MX_ASYNC_INFLIGHT" in os.environ else _COMPILED_STEP_DEEP
    return inflight_limit(), deep


class _PendingHandle:
    """One dispatched-but-unforced step.  Subclasses define `_force()`."""

    def __init__(self, step: int, executor: str,
                 ring: Optional["InflightRing"] = None):
        self._step = int(step)
        self._executor = executor
        self._ring = ring
        self._dispatched = time.perf_counter()
        self._admitted = 0  # the ring's depth with this handle in it
        self._forced = False
        self._host = None
        self._exc: Optional[BaseException] = None

    @property
    def step(self) -> int:
        """The step counter value at dispatch (names the step in errors)."""
        return self._step

    @property
    def forced(self) -> bool:
        return self._forced

    def _force(self):
        raise NotImplementedError

    def wait(self, _span: bool = True):
        """Force the readback/sync.  Blocks until the device has produced
        this step's result; re-raises (wrapped) anything the deferred
        computation failed with, naming the dispatching step.  Idempotent:
        later calls return the cached host value (or re-raise).

        ``_span=False`` skips the ``loss_wait`` span (NOT the aggregate
        rollup) for callers that record the same blocked interval under
        their own span — one wall fact must reach the phase breakdown
        once."""
        if self._forced:
            if self._exc is not None:
                raise self._exc
            return self._host
        # the span makes the host's device-blocked time VISIBLE on the
        # trace timeline (trace_report's idle-gap straggler rule relies on
        # waits being accounted); the aggregate rollup below stays the
        # cheap always-on form
        with (telemetry.span("loss_wait", paired=True,
                             executor=self._executor, step=self._step)
              if _span else contextlib.nullcontext()):
            t0 = time.perf_counter()
            try:
                self._host = self._force()
                return self._host
            except Exception as exc:
                # under async dispatch a real device OOM surfaces HERE,
                # at the deferred readback — post-mortem before wrapping
                if memwatch.is_resource_exhausted(exc):
                    memwatch.emit_oom_report(
                        executor=self._executor, step=self._step,
                        inflight_depth=(self._ring.depth
                                        if self._ring is not None else 0))
                # the failure belongs to the step that DISPATCHED the
                # program, not to whatever line happened to force it later
                self._exc = MXNetError(
                    f"async step {self._step} dispatched by "
                    f"{self._executor} failed at deferred readback: {exc}")
                raise self._exc from exc
            finally:
                self._forced = True
                if self._ring is not None:
                    self._ring.discard(self)
                # all host time spent blocked on the device funnels into
                # one per-executor rollup
                # (summary()['steps'][name]['block_wait_ms'])
                telemetry.record_block_wait(self._executor,
                                            time.perf_counter() - t0)

    def __repr__(self):
        state = "forced" if self._forced else "pending"
        return (f"<{type(self).__name__} step={self._step} "
                f"executor={self._executor!r} {state}>")


class AsyncLoss(_PendingHandle):
    """Lazy scalar loss.  ``float()``, ``np.asarray()``, ``.asnumpy()``,
    ``.asscalar()``, ``.item()`` and ``.wait()`` all force readback."""

    def __init__(self, value, step: int, executor: str,
                 ring: Optional["InflightRing"] = None, host_fn=None):
        super().__init__(step, executor, ring)
        self._value = value
        self._host_fn = host_fn

    def _force(self):
        value, self._value = self._value, None  # drop the device ref
        if self._host_fn is not None:
            value = self._host_fn(value)
        return np.asarray(value)

    def asnumpy(self) -> np.ndarray:
        return np.asarray(self.wait())

    def asscalar(self):
        return self.asnumpy().item()

    def item(self):
        return self.asscalar()

    def __float__(self):
        return float(self.asscalar())

    def __int__(self):
        return int(self.asscalar())

    def __bool__(self):
        return bool(self.asscalar())

    def __array__(self, dtype=None, *args, **kwargs):
        out = self.asnumpy()
        return out if dtype is None else out.astype(dtype)


class AsyncResult(AsyncLoss):
    """Generic lazy device->host handle over ANY array-valued dispatch —
    the same forcing/ring/error semantics as :class:`AsyncLoss`, result
    returned as the raw ``np.ndarray``.  The serving engine
    (``mxnet_tpu.serving.engine``) admits one per compiled decode step
    (the (S,) per-slot token vector) through its bounded ring, so token
    readbacks happen at stream cadence, never per token."""


class StepFence(_PendingHandle):
    """Pending handle over in-place buffer updates (Trainer/Module steps):
    waiting blocks until every listed device array is ready."""

    def __init__(self, arrays, step: int, executor: str,
                 ring: Optional["InflightRing"] = None):
        super().__init__(step, executor, ring)
        self._arrays = list(arrays)

    def _force(self):
        import jax

        arrays, self._arrays = self._arrays, []
        jax.block_until_ready(arrays)
        return None


def _pending_arrays(ring):
    """memwatch provider: device buffers pinned by unforced handles."""
    with ring._lock:
        handles = list(ring._pending)
    out = []
    for h in handles:
        v = getattr(h, "_value", None)
        if v is not None:
            out.append(v)
        out.extend(getattr(h, "_arrays", None) or ())
    return out


class InflightRing:
    """Bounded ring of pending handles for ONE executor.

    ``make_room(limit)`` blocks (oldest-first) until fewer than ``limit``
    handles are pending — the only place the async pipeline ever waits.
    ``admit()`` registers a freshly dispatched handle and returns the
    depth, which telemetry reports as ``inflight_depth`` (the window-bound
    assertion in tests rides on it never exceeding the limit)."""

    def __init__(self, executor: str):
        self._executor = executor
        self._pending: deque = deque()
        self._deep = 0  # the window make_room(deep=...) has grown to
        self._lock = threading.Lock()
        with _rings_lock:
            _live_rings.add(self)
        # live-array census: pending handles pin this step's loss/fence
        # buffers — the "inflight" category of the memory watchdog
        memwatch.register("inflight", self, _pending_arrays)

    def discard(self, handle) -> None:
        """Drop a handle the consumer forced out-of-band (float(loss))."""
        with self._lock:
            try:
                self._pending.remove(handle)
            except ValueError:
                pass

    def _oldest_over(self, limit: int):
        with self._lock:
            while self._pending and self._pending[0].forced:
                self._pending.popleft()
            if len(self._pending) < max(1, limit):
                return None
            return self._pending[0]

    def make_room(self, limit: int, wait_span: bool = True,
                  deep=None) -> float:
        """Ensure the window has a free slot; returns seconds spent
        blocked (0.0 when the ring wasn't full).  ``wait_span=False``
        suppresses the inner waits' ``loss_wait`` spans for a caller that
        records the returned duration as its own ``block_wait`` span —
        the same blocked wall must not land in the trace twice.
        ``deep=(most, seconds)`` lets the window find its own size between
        ``limit`` and ``most``: a handle that was waited for ended as the
        wait did, so dispatch to end is the work queued with it, and the
        next call's window is one larger where that was under ``seconds``
        (one smaller past half as much again).  Only a handle that filled
        the window as it is now is read: another tells of a window of
        another size, and the size would swing."""
        if deep is not None:
            limit = self._deep = min(max(self._deep, limit), deep[0])
        waited = 0.0
        while True:
            oldest = self._oldest_over(limit)
            if oldest is None:
                return waited
            t0 = time.perf_counter()
            oldest.wait(_span=wait_span)  # discards itself from the ring
            now = time.perf_counter()
            waited += now - t0
            if (deep is not None and now - t0 > 1e-3
                    and oldest._admitted == limit):
                queued_s = now - oldest._dispatched
                if queued_s < deep[1]:
                    self._deep = limit + 1
                elif queued_s > 1.5 * deep[1]:
                    self._deep = limit - 1

    def admit(self, handle) -> int:
        with self._lock:
            self._pending.append(handle)
            handle._admitted = len(self._pending)
            return handle._admitted

    @property
    def depth(self) -> int:
        with self._lock:
            return sum(1 for h in self._pending if not h.forced)

    def drain(self) -> None:
        """Force every pending handle, oldest first (epoch end, shutdown,
        checkpoint sync).  Raises the first deferred failure it hits."""
        if self.depth == 0:
            return  # no span noise for the common already-empty drain
        with telemetry.span("inflight_drain", paired=True,
                            executor=self._executor):
            while True:
                with self._lock:
                    while self._pending and self._pending[0].forced:
                        self._pending.popleft()
                    if not self._pending:
                        return
                    oldest = self._pending[0]
                oldest.wait()


def drain_all():
    """Drain every live ring in the process (preemption/checkpoint paths).
    Best-effort: deferred failures are collected and returned, not raised —
    the caller is usually about to snapshot-and-exit and must not die on a
    step that was doomed anyway."""
    errors = []
    with _rings_lock:
        rings = list(_live_rings)
    for ring in rings:
        try:
            ring.drain()
        except Exception as exc:  # noqa: BLE001 — survey, don't die
            errors.append(exc)
    return errors
