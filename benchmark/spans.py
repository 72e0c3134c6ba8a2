"""The benchmark's own host spans and counters, kept in memory.

``span(name)`` times a block on the host's clock and, while a profiler
trace is running, writes the same interval into the trace as
``bench:<name>`` so that a device gap can be laid against what the host was
doing.  Nothing here is the program's: spans inside it are a later issue's.
"""
import contextlib
import time


class Recorder:
    def __init__(self):
        self.spans = []          # (name, t0, t1)
        self.counters = {}
        self.annotate = False    # set while a profiler trace runs

    @contextlib.contextmanager
    def span(self, name):
        t0 = time.perf_counter()
        if self.annotate:
            import jax

            with jax.profiler.TraceAnnotation("bench:" + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def count(self, name, n=1):
        self.counters[name] = self.counters.get(name, 0) + n

    def durations(self, name, t0=None, t1=None):
        return [b - a for n, a, b in self.spans if n == name
                and (t0 is None or a >= t0) and (t1 is None or b <= t1)]


class CompileCounter:
    """Counts what XLA compiles or loads from the persistent cache, through
    jax.monitoring: the compiler-and-runtime layer's counter."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.times.append(time.perf_counter())

    def between(self, t0, t1):
        return sum(1 for t in self.times if t0 <= t <= t1)
