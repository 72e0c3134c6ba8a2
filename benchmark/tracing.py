"""The traced sub-window of a ``--trace 1`` run, and the reduction from the
profiler's ``.xplane.pb`` to the numbers the per-layer metrics read.

The reduction works on a compact form of the trace (``compact``): a dict of
planes -> lines -> events ``[name, start_ns, duration_ns]``, which is
also the form of the small recorded trace the tests keep.  Device busy time
is the union of the intervals in which an operation ran on a device
(``XLA Ops`` line of each ``/device:TPU:n`` plane), averaged over the chips
used; idle gaps are laid against the benchmark's own host spans
(``bench:<name>`` annotations in the same trace).
"""
import glob
import os
import re
import shutil
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
OPS_LINE = "XLA Ops"
NO_SPAN = "no benchmark span (inside the program's own loop)"
WINDOW_SPAN = "traced_window"


def memory_peak(device):
    """The peak the chip held.  On the TPU runtime here
    ``peak_bytes_in_use`` covers live buffers only; a compiled program's
    temporaries sit in a region of their own, counted by
    ``peak_bytes_reserved`` (PERF.md section 3 has the readings that show
    it), so the peak is their sum where the runtime reports both."""
    s = device.memory_stats() or {}
    return int(s.get("peak_bytes_in_use", 0)) \
        + int(s.get("peak_bytes_reserved", 0))


def compact(path):
    """An ``.xplane.pb`` in the compact form.  Of a device plane only the
    ``XLA Ops`` line is kept; of a host plane only the benchmark's own spans
    (``bench:<name>``)."""
    from jax.profiler import ProfileData

    out = {"planes": []}
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            evs = [[e.name, int(e.start_ns), int(e.duration_ns)]
                   for e in line.events
                   if device or e.name.startswith("bench:")]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            out["planes"].append({"name": plane.name, "lines": lines})
    return out


def _union(intervals):
    """Merged [(start, end)] of possibly overlapping intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def reduce(trace, kernels=None, top=10):
    """The compact trace -> busy_s, window_s, per-op and per-kernel device
    seconds and the breakdown.  The window is the ``bench:traced_window``
    span the harness wrote into the trace (the whole extent of the device's
    events where there is none); device events are clipped to it.
    ``kernels`` maps a kernel's name to a regular expression matched against
    the op's name, which on the TPU is the HLO instruction's text (a Mosaic
    kernel shows as ``custom_call_target="tpu_custom_call"`` with its
    operand shapes; the kernel's own name is not in the trace)."""
    kernels = kernels or {}
    device_planes = [p for p in trace["planes"]
                     if re.match(r"^/device:TPU:\d+$", p["name"])]
    if not device_planes:
        device_planes = [p for p in trace["planes"]
                         if p["name"].startswith("/device:")]
    spans = [(n[len("bench:"):], s, s + d) for p in trace["planes"]
             if not p["name"].startswith("/device:")
             for ln in p["lines"] for n, s, d in ln["events"]
             if n.startswith("bench:")]
    marks = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    per_plane = [[e for ln in p["lines"] if ln["name"] == OPS_LINE
                  for e in ln["events"]] for p in device_planes]
    if marks:
        w0, w1 = marks[0]
    else:
        flat = [e for evs in per_plane for e in evs]
        w0 = min((s for _n, s, _d in flat), default=0)
        w1 = max((s + d for _n, s, d in flat), default=0)
    busy, ops = [], {}
    kern = {k: [0.0, 0] for k in kernels}
    merged0 = None
    n = max(len(device_planes), 1)
    for evs in per_plane:
        clipped = [(name, max(s, w0), min(s + d, w1)) for name, s, d in evs
                   if s + d > w0 and s < w1]
        merged = _union((a, b) for _n, a, b in clipped)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if merged0 is None:
            merged0 = merged
        for name, a, b in clipped:
            ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9 / n
            for k, pat in kernels.items():
                if re.search(pat, name):
                    kern[k][0] += (b - a) * 1e-9 / n
                    kern[k][1] += 1
    gaps = {}
    edges = [[w0, w0]] + (merged0 or []) + [[w1, w1]]
    for (_a0, b0), (a1, _b1) in zip(edges, edges[1:]):
        if a1 <= b0:
            continue
        mid = (b0 + a1) / 2
        inside = sorted(nm for nm, s, e in spans
                        if s <= mid <= e and nm != WINDOW_SPAN)
        name = inside[0] if inside else NO_SPAN
        gaps[name] = gaps.get(name, 0.0) + (a1 - b0) * 1e-9
    by_time = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {"busy_s": sum(busy) / len(busy) if busy else 0.0,
            "window_s": (w1 - w0) * 1e-9, "chips": len(device_planes),
            "op_seconds": ops,
            "kernel_seconds": {k: v[0] for k, v in kern.items()},
            "kernel_calls": {k: v[1] for k, v in kern.items()},
            "breakdown": {"device_ops": [[short(nm), t]
                                         for nm, t in by_time(ops)],
                          "idle_gaps": [[nm, t] for nm, t in by_time(gaps)]}}


def short(op_name, width=120):
    """An HLO instruction's text cut to what names it: result name, opcode,
    first result shape and, of a custom call, its target."""
    m = re.match(r"^(%[^ ]+) = (.+?) ([a-z][\w\-]*)\(", op_name)
    if not m:
        return op_name[:width]
    shape = m.group(2).lstrip("(").split("{")[0]
    target = re.search(r'custom_call_target="([^"]+)"', op_name)
    out = f"{m.group(1)} {m.group(3)} {shape}"
    if target:
        out += " " + target.group(1)
    return out[:width]


class Window:
    """The profiler over the last ``trace_seconds`` of the measured window:
    ``poll`` (called from the loop that drives the window) starts it,
    ``close`` stops it at the window's close.  The file is read only by
    ``reduced``, after the window, so that reading it costs the window
    nothing.  ``sync`` (when given) closes the device's work so that the
    traced interval holds whole steps; ``steps_fn`` reads the count of steps
    done, for the steps inside it."""

    def __init__(self, cell, rec, traffic, seconds, steps_fn):
        self.cell, self.rec, self.steps_fn = cell, rec, steps_fn
        self.begin = max(0.0, seconds - traffic["trace_seconds"])
        self.dir = os.path.join(TRACE_DIR, cell.name)
        self.state, self.t_on, self.t_off = "before", None, None
        self._mark = None

    def poll(self, elapsed, sync=None):
        if self.state != "before" or elapsed < self.begin:
            return
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        if sync is not None:
            sync()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.rec.annotate = True
        self._mark = jax.profiler.TraceAnnotation("bench:" + WINDOW_SPAN)
        self._mark.__enter__()
        self.t_on, self.n_on = time.perf_counter(), self.steps_fn()
        self.state = "on"

    def close(self):
        """At the window's close (the device's work already closed), on the
        thread that called ``poll``."""
        if self.state != "on":
            raise RuntimeError("the window closed before the trace began")
        import jax

        self.t_off, self.n_off = time.perf_counter(), self.steps_fn()
        self._mark.__exit__(None, None, None)
        self.rec.annotate = False
        jax.profiler.stop_trace()
        self.state = "closed"

    def reduced(self):
        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        trace = compact(paths[-1])
        shutil.rmtree(self.dir, ignore_errors=True)
        out = reduce(trace, self.cell.checks.get("kernels"))
        out["t_on"], out["t_off"] = self.t_on, self.t_off
        out["steps"] = self.n_off - self.n_on     # whole steps: both ends synced
        return out
