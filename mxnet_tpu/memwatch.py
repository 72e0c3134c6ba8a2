"""Device-memory & compile-cost observability (docs/OBSERVABILITY.md §Memory).

PRs 2 and 5 made *time* observable (step events, spans, Perfetto traces);
this module makes *memory* and *compile cost* observable — the two inputs
the serving path (memory headroom is its binding constraint) and the
learned planner (per-executable cost records are its feature set; *A
Learned Performance Model for TPUs*, arXiv:2008.01040) need.  Four
pieces, all riding the PR 2/5 telemetry spine rather than growing a
second pipeline:

  * **sampler** — ``on_step()`` / ``on_checkpoint()`` are called at step
    boundaries and checkpoint save/load (never inside hot dispatch: the
    memory APIs below are on mxlint's hot-sync list precisely so nobody
    ever polls memory from ``_step_impl``).  Every ``MX_MEMWATCH_EVERY``
    (default 10) observations it snapshots per-device
    ``memory_stats()`` (normalized by ``context.normalize_memory_stats``)
    plus a categorized census of ``jax.live_arrays()`` and records one
    ``mem`` event with watermark tracking;
  * **category attribution** — components *weakly* register providers
    (``register(category, obj, fn)``): ``DataParallelStep`` (params /
    optimizer state), ``FusedUpdater`` (optimizer state),
    ``InflightRing`` + ``DevicePrefetchIter`` (in-flight batches and
    pending step buffers), ``AsyncCheckpointer`` (queued host snapshot
    buffers).  The census attributes each live array to its category by
    identity; everything unclaimed is ``other``.  Weak registration: a
    dropped step object must not be kept alive by the watchdog;
  * **leak detector** — a sliding window (``MX_MEMWATCH_LEAK_WINDOW``,
    default 12 samples) of census totals; strictly monotonic growth
    across the full window above a noise floor warns ONCE (re-armed when
    growth stops) naming the top-growing category, and records a
    ``mem_leak`` event;
  * **compile accounting** — every jit construction site
    (``data_parallel._build``, ``fused._jitted``, the kvstore
    ``_psum_cache``, ``CachedOp``) reports ``note_compile()``: one
    ``compile`` event per cache entry (deduped in-process) carrying
    compile wall time, a **stable executable fingerprint** (sha256 of
    structural identity — shapes/dtypes/static hypers, never object ids,
    so it survives a process restart), and — where this jax exposes
    them — ``cost_analysis()`` FLOPs/bytes-accessed from the (cached)
    retrace.  ``MX_MEMWATCH=full``
    additionally captures ``memory_analysis()`` temp/argument/output
    bytes at the cost of ONE duplicate XLA compile per executable;
  * **OOM post-mortem** — dispatch/readback paths that catch a
    RESOURCE_EXHAUSTED call ``emit_oom_report()``: one ``oom_report``
    event (last watermark, live-array census with the largest category
    named, top executables by temp/accessed bytes, in-flight depth) is
    recorded and flushed before the error re-raises, so the
    ``tools/launch.py`` supervisor can echo *why* the rank died next to
    its flight tail.

Enabled whenever the telemetry recorder is enabled; ``MX_MEMWATCH=0``
is the kill switch.  Like spans, sampling is bitwise-invisible to the
computation (asserted by ``tests/test_memwatch.py``) and the
``memwatch_overhead`` bench metric keeps the steady-state cost in the
noise floor.  ``tools/mem_report.py`` is the offline consumer;
``telemetry.export_prometheus`` exposes ``mx_mem_*`` gauges and
``export_chrome_trace`` renders ``mem`` events as per-rank counter
tracks under the span timeline.
"""
from __future__ import annotations

import hashlib
import logging
import os
import threading
import weakref
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from . import telemetry
from .context import normalize_memory_stats

__all__ = ["enabled", "register", "census", "device_memory", "sample",
           "on_step", "on_checkpoint", "fingerprint", "note_compile",
           "shape_structs", "emit_oom_report", "is_resource_exhausted",
           "peak_bytes", "summary", "reset"]

_LOG = logging.getLogger("mxnet_tpu.memwatch")

_DEFAULT_EVERY = 10
_DEFAULT_LEAK_WINDOW = 12
# leak floor: total live bytes must grow by at least this much across the
# whole window before the monotonic trend is worth a warning — strictly
# increasing growth of a few KB is allocator jitter, not a leak
_LEAK_MIN_GROWTH = 1 << 16
# bounded registry of compile records (oom_report's "top executables" and
# summary() read it; mem_report reads the events instead)
_COMPILE_RECORDS_MAX = 512


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def enabled() -> bool:
    """Memwatch rides the telemetry recorder: on whenever telemetry is on,
    unless ``MX_MEMWATCH=0`` kills it.  (``MX_MEMWATCH=full`` additionally
    enables the duplicate-compile ``memory_analysis()`` capture.)"""
    if not telemetry.enabled():
        return False
    return os.environ.get("MX_MEMWATCH", "1").lower() not in (
        "0", "false", "off")


def _full_analysis() -> bool:
    return os.environ.get("MX_MEMWATCH", "").lower() == "full"


def _every() -> int:
    return max(1, _env_int("MX_MEMWATCH_EVERY", _DEFAULT_EVERY))


def _leak_window() -> int:
    return max(2, _env_int("MX_MEMWATCH_LEAK_WINDOW", _DEFAULT_LEAK_WINDOW))


# ---------------------------------------------------------------------------
# state
# ---------------------------------------------------------------------------
class _State:
    def __init__(self):
        self.lock = threading.Lock()
        self.step_calls = 0
        self.samples = 0
        self.watermark = 0            # max observed device/live bytes
        self.window: List[Tuple[int, Dict[str, int]]] = []
        self.leak_active = False
        self.leak_category: Optional[str] = None
        self.leak_events = 0
        self.last_categories: Dict[str, int] = {}
        self.compile_seen: set = set()
        self.compiles: List[dict] = []
        self.compile_ms = 0.0
        self.oom_reported = False


_state = _State()

# providers survive reset(): registration happens at object construction,
# and tests resetting aggregates must not blind the census to still-live
# steps/rings (dead weakrefs are pruned at census time)
_providers: List[Tuple[str, "weakref.ref", Callable]] = []
_providers_lock = threading.Lock()
# amortized dead-ref pruning for processes that never sample (telemetry
# off): register() prunes whenever the list doubles past this watermark,
# so churning short-lived steps/rings can't grow the registry forever
_providers_prune_at = 64


def reset() -> None:
    """Drop aggregates/window/compile registry (tests).  Registered
    providers are kept — their objects are still alive."""
    global _state
    _state = _State()


# ---------------------------------------------------------------------------
# category registration + census
# ---------------------------------------------------------------------------
def register(category: str, obj: Any, fn: Callable[[Any], Any]) -> None:
    """Weakly register ``fn(obj) -> iterable of arrays`` as the provider
    of ``category``'s live arrays.  ``fn`` runs at *sample* time (step
    boundaries, never hot dispatch) and may return jax arrays, NDArrays
    (their ``._data`` is used), or numpy arrays (counted as host bytes —
    e.g. queued checkpoint snapshots).  The registry holds only a weakref
    to ``obj``: dropping the object retires its provider."""
    global _providers_prune_at
    with _providers_lock:
        _providers.append((category, weakref.ref(obj), fn))
        if len(_providers) >= _providers_prune_at:
            # amortized O(1): census() also prunes, but a telemetry-off
            # process never runs a census and must still stay bounded
            _providers[:] = [(c, r, f) for c, r, f in _providers
                             if r() is not None]
            _providers_prune_at = max(64, 2 * len(_providers))


def _live_providers():
    with _providers_lock:
        alive = [(c, r, f) for c, r, f in _providers if r() is not None]
        _providers[:] = alive
        return list(alive)


def census() -> dict:
    """Categorized census of ``jax.live_arrays()``:
    ``{"total_bytes", "live_count", "categories": {cat: {count, nbytes}},
    "host_bytes": {cat: bytes}}``.  Attribution is by array identity
    against the registered providers; unclaimed arrays are ``other``.
    Never call this from a per-step dispatch body (mxlint hot-sync)."""
    import jax

    cat_of: Dict[int, str] = {}
    host_bytes: Dict[str, int] = {}
    for category, ref, fn in _live_providers():
        obj = ref()
        if obj is None:
            continue
        try:
            arrs = fn(obj)
        except Exception:  # a torn-down provider must not kill sampling
            continue
        for a in arrs or ():
            if a is None:
                continue
            data = getattr(a, "_data", a)  # NDArray -> backing jax array
            if isinstance(data, np.ndarray):
                host_bytes[category] = (host_bytes.get(category, 0)
                                        + int(data.nbytes))
            else:
                cat_of[id(data)] = category
    categories: Dict[str, Dict[str, int]] = {}
    total = 0
    count = 0
    for arr in jax.live_arrays():
        try:
            nb = int(arr.nbytes)
        except Exception:
            continue
        cat = cat_of.get(id(arr), "other")
        row = categories.setdefault(cat, {"count": 0, "nbytes": 0})
        row["count"] += 1
        row["nbytes"] += nb
        total += nb
        count += 1
    return {"total_bytes": total, "live_count": count,
            "categories": categories, "host_bytes": host_bytes}


def device_memory() -> dict:
    """Aggregated normalized ``memory_stats()`` over the local devices:
    ``{"available", "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
    "devices": n}``.  ``available=False`` on backends without allocator
    stats (XLA:CPU) — callers fall back to the live-array census."""
    out = {"available": False, "bytes_in_use": 0, "peak_bytes_in_use": 0,
           "bytes_limit": 0, "devices": 0}
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return out
    for d in devices:
        stats_fn = getattr(d, "memory_stats", None)
        raw = None
        if stats_fn is not None:
            try:
                raw = stats_fn()
            except Exception:
                raw = None
        norm = normalize_memory_stats(raw)
        out["devices"] += 1
        if norm["available"]:
            out["available"] = True
            out["bytes_in_use"] += norm["bytes_in_use"]
            out["peak_bytes_in_use"] += norm["peak_bytes_in_use"]
            out["bytes_limit"] += norm["bytes_limit"]
    return out


# ---------------------------------------------------------------------------
# sampling + leak detection
# ---------------------------------------------------------------------------
def on_step(step: Optional[int] = None) -> None:
    """Step-boundary observation.  Samples every ``MX_MEMWATCH_EVERY``-th
    call; the off-cadence cost is one counter increment.  Called from the
    ``DataParallelStep.step``/``Trainer.step`` wrappers and
    ``AsyncCheckpointer.step`` — boundaries, never inside ``_step_impl``."""
    if not enabled():
        return
    with _state.lock:
        _state.step_calls += 1
        due = _state.step_calls % _every() == 0
    if due:
        sample("step", step=step)


def on_checkpoint(event: str, step: Optional[int] = None) -> None:
    """Checkpoint save/load boundary — always samples (rare, and the
    moment checkpoint buffers are actually resident)."""
    if not enabled():
        return
    sample(f"checkpoint_{event}", step=step)


def sample(site: str, step: Optional[int] = None) -> Optional[dict]:
    """Take one memory sample now: census + device stats -> one ``mem``
    telemetry event; feeds the watermark and the leak window.  Returns
    the event fields (None when disabled)."""
    if not enabled():
        return None
    try:
        c = census()
    except Exception as e:  # the watchdog must never kill training
        _LOG.warning("memwatch census failed: %s", e)
        return None
    dev = device_memory()
    in_use = dev["bytes_in_use"] if dev["available"] else c["total_bytes"]
    leak = None
    with _state.lock:
        _state.samples += 1
        _state.watermark = max(_state.watermark, in_use, c["total_bytes"])
        watermark = _state.watermark
        _state.last_categories = {
            cat: row["nbytes"] for cat, row in c["categories"].items()}
        win = _state.window
        win.append((c["total_bytes"],
                    dict(_state.last_categories)))
        w = _leak_window()
        if len(win) > w:
            del win[:-w]
        if len(win) == w:
            totals = [t for t, _cats in win]
            growing = all(b > a for a, b in zip(totals, totals[1:]))
            growth = totals[-1] - totals[0]
            if growing and growth > _LEAK_MIN_GROWTH:
                if not _state.leak_active:
                    _state.leak_active = True
                    _state.leak_events += 1
                    first_cats, last_cats = win[0][1], win[-1][1]
                    deltas = {cat: last_cats.get(cat, 0)
                              - first_cats.get(cat, 0)
                              for cat in set(first_cats) | set(last_cats)}
                    top = max(deltas, key=deltas.get) if deltas else "other"
                    _state.leak_category = top
                    leak = {"category": top, "growth_bytes": growth,
                            "window": w,
                            "category_growth_bytes": deltas.get(top, 0)}
            else:
                # growth stopped: re-arm so a later real leak warns again
                _state.leak_active = False
    ev: Dict[str, Any] = {
        "site": site,
        "live_bytes": c["total_bytes"],
        "live_count": c["live_count"],
        "watermark_bytes": watermark,
        "categories": c["categories"],
    }
    if step is not None:
        ev["step"] = int(step)
    if dev["available"]:
        ev["bytes_in_use"] = dev["bytes_in_use"]
        ev["peak_bytes_in_use"] = dev["peak_bytes_in_use"]
        ev["bytes_limit"] = dev["bytes_limit"]
    if c["host_bytes"]:
        ev["host_bytes"] = c["host_bytes"]
    telemetry.record("mem", **ev)
    if leak is not None:
        _LOG.warning(
            "memwatch: live device memory grew monotonically across the "
            "last %d samples (+%d bytes); top-growing category: %s "
            "(+%d bytes).  If this trend continues the run will hit "
            "RESOURCE_EXHAUSTED — check for accumulating references "
            "(un-drained AsyncLoss handles, growing python-side caches).",
            leak["window"], leak["growth_bytes"], leak["category"],
            leak["category_growth_bytes"])
        telemetry.record("mem_leak", total_bytes=c["total_bytes"], **leak)
    return ev


def peak_bytes() -> int:
    """Best-effort process peak device bytes: PjRt's summed
    ``peak_bytes_in_use`` where the backend exposes it, else the
    watchdog's live-array watermark (refreshed from a census total here,
    so the profiler's ``profile_memory`` plumb works even between
    samples).  Blocking-context callers only (mx.profiler.timed_call)."""
    dev = device_memory()
    if dev["available"]:
        with _state.lock:
            _state.watermark = max(_state.watermark,
                                   dev["peak_bytes_in_use"])
            return _state.watermark
    try:
        import jax

        total = sum(int(a.nbytes) for a in jax.live_arrays())
    except Exception:
        total = 0
    with _state.lock:
        _state.watermark = max(_state.watermark, total)
        return _state.watermark


# ---------------------------------------------------------------------------
# compiled-executable accounting
# ---------------------------------------------------------------------------
def fingerprint(parts: Any) -> str:
    """Stable executable fingerprint: sha256 over the repr of structural
    identity (optimizer/static hypers/shapes/dtypes) — deliberately no
    object ids or memory addresses, so the same program in a restarted
    process maps to the same fingerprint (asserted by
    tests/test_memwatch.py).  A NAME for compile telemetry events and
    checkpoint layouts, not a cache key: the traced code and a block's
    non-shape configuration are not in it."""
    return hashlib.sha256(repr(parts).encode()).hexdigest()[:16]


def shape_structs(tree):
    """ShapeDtypeStruct mirror of a pytree of arrays (shardings kept
    where present): host metadata only, so a jit site can hand
    ``note_compile`` enough to retrace for analysis WITHOUT pinning the
    real parameter/batch buffers past the step that placed them."""
    import jax

    def one(a):
        try:
            return jax.ShapeDtypeStruct(
                np.shape(a), a.dtype, sharding=getattr(a, "sharding", None))
        except Exception:
            return a

    return jax.tree_util.tree_map(one, tree)


def _tree_bytes(tree) -> int:
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            total += int(np.prod(shape)) * np.dtype(dtype).itemsize
        except Exception:
            continue
    return total


def _analyze(jitted, args) -> dict:
    """Best-effort cost/memory analysis of one executable.  The retrace
    behind ``jitted.lower(*args)`` is cached after the real call (sub-ms);
    ``cost_analysis()`` is an HLO-level pass (no XLA compile).  Only
    ``MX_MEMWATCH=full`` pays the duplicate XLA compile that
    ``memory_analysis()`` (temp bytes) requires."""
    out: Dict[str, Any] = {}
    try:
        out["arg_bytes"] = _tree_bytes(args)
    except Exception:
        # analysis fields are best-effort garnish on the compile event
        pass
    try:
        import jax

        out_struct = jax.eval_shape(jitted, *args)
        out["out_bytes"] = _tree_bytes(out_struct)
    except Exception:
        # ragged call signatures (vjp-wrapped, scope-dependent lowering)
        # simply lose the out-bytes field
        pass
    try:
        lowered = jitted.lower(*args)
    except Exception:
        return out
    try:
        ca = lowered.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        if isinstance(ca, dict):
            if ca.get("flops") is not None:
                out["flops"] = float(ca["flops"])
            if ca.get("bytes accessed") is not None:
                out["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        # cost_analysis availability varies per jax/backend — optional
        pass
    if _full_analysis():
        try:
            ma = lowered.compile().memory_analysis()
            out["temp_bytes"] = int(ma.temp_size_in_bytes)
            out["arg_bytes"] = int(ma.argument_size_in_bytes)
            out["out_bytes"] = int(ma.output_size_in_bytes)
            out["generated_code_bytes"] = int(
                ma.generated_code_size_in_bytes)
        except Exception:
            # MX_MEMWATCH=full is explicitly best-effort (duplicate
            # compile may be unsupported for this program)
            pass
    return out


def note_compile(executor: str, parts: Any, wall_s: float, site: str = "",
                 jitted=None, args=None, **extra) -> Optional[str]:
    """Report one jit-site compilation.  Emits exactly ONE ``compile``
    event per (executor, fingerprint) — a steady-state step re-calling
    the cached executable never re-emits — carrying the compile wall
    (the traced first call's wall, per the record_step convention) and
    whatever analysis this jax exposes; ``extra`` fields ride on the
    event.  Returns the fingerprint (None when the watchdog is off —
    ``MX_MEMWATCH=0`` kills compile accounting, including the analysis
    retrace, along with sampling)."""
    if not enabled():
        return None
    fp = fingerprint(parts)
    with _state.lock:
        key = (executor, fp)
        if key in _state.compile_seen:
            return fp
        _state.compile_seen.add(key)
    ev: Dict[str, Any] = {"executor": executor, "fingerprint": fp,
                          "site": site, "wall_ms": round(wall_s * 1e3, 3)}
    ev.update(extra)
    if jitted is not None and args is not None:
        try:
            ev.update(_analyze(jitted, args))
        except Exception:  # analysis is garnish; the event is the record
            pass
    with _state.lock:
        _state.compile_ms += wall_s * 1e3
        _state.compiles.append(dict(ev))
        if len(_state.compiles) > _COMPILE_RECORDS_MAX:
            del _state.compiles[:-_COMPILE_RECORDS_MAX]
    telemetry.record("compile", **ev)
    return fp


# ---------------------------------------------------------------------------
# OOM post-mortem
# ---------------------------------------------------------------------------
def is_resource_exhausted(exc: BaseException) -> bool:
    """Does this exception look like a device OOM?  Matches the
    RESOURCE_EXHAUSTED status text PjRt puts in XlaRuntimeError — and the
    synthetic ``oom:step=N`` fault (mxnet_tpu.fault), which spells it the
    same way so the post-mortem path is testable without real HBM
    exhaustion."""
    return "RESOURCE_EXHAUSTED" in str(exc)


def emit_oom_report(executor: str = "", step: Optional[int] = None,
                    inflight_depth: Optional[int] = None) -> None:
    """Record and FLUSH one ``oom_report`` event: last watermark, a fresh
    live-array census with the largest category named, the top
    executables by temp/accessed bytes, and the in-flight window depth —
    everything the supervisor needs to say *why* the rank died.  One per
    process (an OOM storm across the in-flight window is one fact);
    exception-safe: the report must never mask the original error.
    ``MX_MEMWATCH=0`` suppresses it (the census is exactly what that
    switch turns off) — the RESOURCE_EXHAUSTED itself still propagates
    normally."""
    try:
        if not enabled():
            return
        with _state.lock:
            if _state.oom_reported:
                return
            _state.oom_reported = True
            watermark = _state.watermark
            compiles = list(_state.compiles)
        try:
            c = census()
        except Exception:
            c = {"total_bytes": 0, "live_count": 0, "categories": {},
                 "host_bytes": {}}
        cats = {cat: row["nbytes"] for cat, row in c["categories"].items()}
        largest = max(cats, key=cats.get) if cats else None

        def _weight(rec):
            return rec.get("temp_bytes",
                           rec.get("bytes_accessed",
                                   rec.get("arg_bytes", 0)))

        top = sorted(compiles, key=_weight, reverse=True)[:3]
        ev: Dict[str, Any] = {
            "executor": executor,
            "watermark_bytes": max(watermark, c["total_bytes"]),
            "live_bytes": c["total_bytes"],
            "live_count": c["live_count"],
            "categories": cats,
            "largest_category": largest,
            "top_executables": [
                {"executor": r.get("executor"),
                 "fingerprint": r.get("fingerprint"),
                 "temp_bytes": r.get("temp_bytes"),
                 "bytes_accessed": r.get("bytes_accessed"),
                 "arg_bytes": r.get("arg_bytes")}
                for r in top],
        }
        if step is not None:
            ev["step"] = int(step)
        if inflight_depth is not None:
            ev["inflight_depth"] = int(inflight_depth)
        dev = device_memory()
        if dev["available"]:
            ev["bytes_in_use"] = dev["bytes_in_use"]
            ev["bytes_limit"] = dev["bytes_limit"]
        telemetry.record("oom_report", **ev)
        # the process is about to die on the re-raise: do not trust the
        # flusher thread's cadence (or atexit, under a supervisor's
        # SIGKILL escalation) to land the post-mortem on disk
        telemetry.flush()
    except Exception:
        # the post-mortem must never mask the original RESOURCE_EXHAUSTED
        pass


# ---------------------------------------------------------------------------
# rollup
# ---------------------------------------------------------------------------
def summary() -> dict:
    """JSON-serializable rollup (export_prometheus derives the
    ``mx_mem_*`` gauges from this)."""
    with _state.lock:
        return {
            "enabled": enabled(),
            "samples": _state.samples,
            "watermark_bytes": _state.watermark,
            "categories": dict(_state.last_categories),
            "leak": {"active": _state.leak_active,
                     "category": _state.leak_category,
                     "events": _state.leak_events},
            "compiles": {"count": len(_state.compile_seen),
                         "wall_ms": round(_state.compile_ms, 3)},
            "oom_reported": _state.oom_reported,
        }
