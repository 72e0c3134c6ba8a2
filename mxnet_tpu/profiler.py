"""Profiler facade (reference: python/mxnet/profiler.py over src/profiler/ —
set_config/start/stop/dump, aggregate stats; SURVEY §5.1).

TPU-native: bridges to jax.profiler — start()/stop() capture a TensorBoard/
perfetto trace of XLA execution (the analog of the reference's Chrome
trace), and `scope`/`Task` map onto jax trace annotations.
"""
from __future__ import annotations

import os
import time
from typing import Optional

from .base import MXNetError, env_bool

__all__ = ["set_config", "start", "stop", "dump", "dumps", "pause", "resume",
           "scope", "Task", "Frame", "Marker", "state"]

_config = {"filename": "profile.json", "profile_all": False,
           "trace_dir": None}
_running = False
# one numbered subdirectory per start()/resume() — jax.profiler.start_trace
# into the SAME directory twice clobbers the first trace, so each segment
# gets a fresh dir and dump() lists them all
_segments: list = []

# ---------------------------------------------------------------------------
# aggregate per-op stats (reference: src/profiler/aggregate_stats.cc — the
# table printed by mx.profiler.dumps()).  Populated by the op dispatch layer
# (ops/registry.invoke) and the compiled-step executors while a profile is
# running: on TPU the engine-level hook of the reference
# (ThreadedEngine::ExecuteOprBlock profiler brackets) becomes a hook at the
# two places work is issued — eager op dispatch and jitted step execution.
# ---------------------------------------------------------------------------
_aggregate: dict = {}


def is_recording() -> bool:
    """True while op timings should be collected (profile running)."""
    return _running


def record_op(name: str, seconds: float, memory: int = 0) -> None:
    """Record one execution of `name` (called from the dispatch layer).

    ``memory`` is the peak device bytes observed for this call —
    ``timed_call`` plumbs it from ``mxnet_tpu.memwatch.peak_bytes()``
    whenever ``profile_memory`` (or ``profile_all``) is configured, so
    the reference's ``profile_memory`` flag is no longer a no-op: the
    aggregate keeps the max and ``dumps()`` surfaces a Peak(MB) column /
    ``peak_mem_bytes`` json field."""
    ent = _aggregate.get(name)
    if ent is None:
        _aggregate[name] = [1, seconds, seconds, seconds, memory]
    else:
        ent[0] += 1
        ent[1] += seconds
        ent[2] = min(ent[2], seconds)
        ent[3] = max(ent[3], seconds)
        ent[4] = max(ent[4], memory)


def _profile_memory_on() -> bool:
    return bool(_config.get("profile_memory") or _config.get("profile_all"))


def reset_stats() -> None:
    _aggregate.clear()


def timed_call(name: str, fn, *args, **kwargs):
    """Run fn(*args, **kwargs), block on every jax-array leaf of the result,
    and record the wall time under `name`.  The single shared scaffold for
    all profiled call sites (op dispatch, CachedOp, fused step)."""
    import time as _time

    import jax

    t0 = _time.perf_counter()
    result = fn(*args, **kwargs)
    leaves = [getattr(x, "_data", x) for x in jax.tree_util.tree_leaves(result)]
    jax.block_until_ready([x for x in leaves
                           if not isinstance(x, (int, float, str, bool))])
    dt = _time.perf_counter() - t0
    mem = 0
    if _profile_memory_on():
        # this scaffold already blocked on the result, so the (blocking-
        # context-only) peak probe is in its contract; memwatch prefers
        # PjRt's peak_bytes_in_use and falls back to the live-array total
        from . import memwatch

        try:
            mem = memwatch.peak_bytes()
        except Exception:
            mem = 0
    record_op(name, dt, memory=mem)
    return result


def set_config(**kwargs):
    """Accepts the reference's kwargs (profile_all, profile_symbolic,
    profile_imperative, profile_memory, profile_api, filename, ...)."""
    _config.update(kwargs)
    if "filename" in kwargs:
        base = kwargs["filename"]
        _config["trace_dir"] = os.path.splitext(base)[0] + "_jax_trace"


def _trace_dir():
    if _config["trace_dir"] is None:
        _config["trace_dir"] = "mxnet_tpu_profile"
    return _config["trace_dir"]


def start():
    global _running
    import jax

    if _running:
        return
    segment = os.path.join(_trace_dir(), f"segment-{len(_segments):03d}")
    jax.profiler.start_trace(segment)
    _segments.append(segment)
    _running = True


def stop():
    global _running
    import jax

    if not _running:
        return
    jax.profiler.stop_trace()
    _running = False


def state():
    return "running" if _running else "stopped"


def pause():
    """Suspend tracing; resume() continues into a FRESH numbered segment
    (resuming into the same directory clobbered the prior trace)."""
    stop()


def resume():
    start()


def dump(finished=True, profile_process="worker"):
    """The jax trace is written on stop_trace; this flushes and returns the
    list of trace segment directories captured so far (one per
    start()/resume() cycle)."""
    if _running:
        stop()
    return list(_segments)


def dumps(reset=False, format="table", sort_by="total", ascending=False):
    """Ranked per-op aggregate table (reference: MXAggregateProfileStatsPrint
    over aggregate_stats.cc) plus the jax trace location.

    format: 'table' (human) or 'json' (machine-readable list of rows).
    The table form also appends the runtime-telemetry rollup and trace
    segment list; the json form stays a bare row list for compatibility —
    machine consumers read the rollup from its first-class API,
    ``mxnet_tpu.telemetry.summary()``, and segments from ``dump()``."""
    if format not in ("table", "json"):
        raise MXNetError(f"unsupported dumps format {format!r}")
    if not _aggregate:
        if format == "json":
            import json as _json

            return _json.dumps([])
        return (f"profile trace directory: {_trace_dir()}\n"
                "(no per-op stats recorded — run ops between profiler."
                "start() and stop())" + _telemetry_rollup_lines())
    key = {"total": lambda e: e[1][1], "count": lambda e: e[1][0],
           "avg": lambda e: e[1][1] / e[1][0], "min": lambda e: e[1][2],
           "max": lambda e: e[1][3]}.get(sort_by, lambda e: e[1][1])
    rows = sorted(_aggregate.items(), key=key, reverse=not ascending)
    has_mem = any(m for _n, (_c, _t, _mn, _mx, m) in rows)
    if format == "json":
        import json as _json

        out = [dict({"name": n, "count": c, "total_ms": t * 1e3,
                     "avg_ms": t / c * 1e3, "min_ms": mn * 1e3,
                     "max_ms": mx * 1e3},
                    **({"peak_mem_bytes": m} if has_mem else {}))
               for n, (c, t, mn, mx, m) in rows]
        if reset:
            reset_stats()
        return _json.dumps(out)
    name_w = max(24, max(len(n) for n, _ in rows) + 2)
    header = (f"{'Name':<{name_w}}{'Calls':>8}{'Total(ms)':>12}"
              f"{'Avg(ms)':>10}{'Min(ms)':>10}{'Max(ms)':>10}")
    if has_mem:
        header += f"{'Peak(MB)':>10}"
    lines = ["Profile Statistics:", header,
             "-" * (name_w + 50 + (10 if has_mem else 0))]
    for name, (count, total, mn, mx, mem) in rows:
        line = (f"{name:<{name_w}}{count:>8}{total * 1e3:>12.3f}"
                f"{total / count * 1e3:>10.3f}{mn * 1e3:>10.3f}"
                f"{mx * 1e3:>10.3f}")
        if has_mem:
            line += f"{mem / 1e6:>10.2f}"
        lines.append(line)
    lines.append(f"\nprofile trace directory: {_trace_dir()}")
    if len(_segments) > 1:
        lines.append("trace segments: " + ", ".join(_segments))
    lines.append(_telemetry_rollup_lines().lstrip("\n"))
    if reset:
        reset_stats()
    return "\n".join(lines)


def _telemetry_rollup_lines() -> str:
    """The runtime-telemetry rollup appended to dumps() so one call answers
    both 'which op is slow' and 'what did the steps/collectives/retraces
    look like' (docs/OBSERVABILITY.md)."""
    import json as _json

    from . import telemetry

    return "\n\nTelemetry rollup:\n" + _json.dumps(telemetry.summary(),
                                                   sort_keys=True)


class scope:
    """Named annotation scope (reference: profiler.scope): ``mx:<name>`` in
    a running jax.profiler trace, beside the program's own telemetry
    spans."""

    def __init__(self, name="<unk>", append_mode=False):
        self._name = name
        self._ctx = None

    def __enter__(self):
        from . import telemetry

        self._ctx = telemetry.trace_annotation(self._name)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        self._ctx.__exit__(*exc)
        return False


class Task:
    """Named task object (reference: profiler.Task)."""

    def __init__(self, domain=None, name="task"):
        self.name = name
        self._ctx = None

    def start(self):
        from . import telemetry

        self._ctx = telemetry.trace_annotation(self.name)
        self._ctx.__enter__()

    def stop(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None


Frame = Task
Marker = Task

if env_bool("MXNET_PROFILER_AUTOSTART"):
    start()
