"""The program's own spans on the profiler's clock (docs/OBSERVABILITY.md
§Tracing): one liveness rule (the JSONL recorder OR a running jax.profiler
session), ``mx:<name>`` events in the ``.xplane.pb``, the bounded in-memory
store ``spans_between`` reads, and the kill switch over both."""
import glob
import json
import os

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry

STEP_SPANS = ["train_step", "block_wait", "input_stage", "step_prep",
              "dispatch"]
EVER = (0.0, float("inf"))


@pytest.fixture
def tele(monkeypatch):
    """Fresh recorder state, no sink named, spans not killed."""
    monkeypatch.delenv("MX_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("MX_TELEMETRY_SPANS", raising=False)
    telemetry.reset()
    yield telemetry
    telemetry.reset()


class _Trace:
    """A jax.profiler session over a block; ``events()`` afterwards gives
    the ``mx:`` events of the host planes, line by line."""

    def __init__(self, directory):
        self.dir = str(directory)

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()
        return False

    def events(self):
        (path,) = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        lines = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns),
                        dict(e.stats))
                       for e in line.events
                       if e.name.startswith(telemetry.TRACE_PREFIX)]
                if evs:
                    lines.append(sorted(evs, key=lambda e: e[1]))
        return lines


def _tiny_step():
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.init.Xavier())
    step = DataParallelStep(net, gluon.loss.L2Loss(),
                            mesh=local_mesh(devices=jax.devices()[:1]),
                            optimizer="sgd")
    x = nd.array(np.random.rand(8, 8).astype(np.float32))
    y = nd.array(np.random.rand(8, 4).astype(np.float32))
    return step, x, y


def test_nothing_live_gives_the_null_span_and_an_empty_store(tele):
    assert not tele.spans_enabled()
    assert tele.span("x") is tele.span("y")
    for _ in range(1000):                   # 1,000 steps' worth of calls
        for name in STEP_SPANS:
            with tele.span(name, step_num=1):
                pass
        tele.record_span("block_wait", 1.0, 2.0)
    assert tele.spans_between(*EVER) == []
    assert tele.summary()["spans"] == {}


def test_a_profiler_trace_holds_the_steps_spans_nested_on_one_host_line(
        tele, tmp_path):
    step, x, y = _tiny_step()
    step.step(x, y)                         # compiles outside the trace
    step.drain()
    assert tele.spans_between(*EVER) == []  # nobody was listening
    with _Trace(tmp_path) as trace:
        assert tele.spans_enabled()
        for _ in range(3):
            step.step(x, y)
        step.drain()
    assert not tele.spans_enabled()
    kept = [s for s in tele.spans_between(*EVER) if s.name in STEP_SPANS]
    assert [s.name for s in kept] == STEP_SPANS * 3
    (line,) = [ln for ln in trace.events()
               if any(n == "mx:train_step" for n, *_ in ln)]
    in_trace = [e for e in line if e[0][3:] in STEP_SPANS]
    # the same spans, in the same order, under the same ids
    assert [(n[3:], st["span"], st["parent"]) for n, _s, _d, st in in_trace] \
        == [(s.name, s.span, s.parent) for s in kept]
    steps = [e for e in in_trace if e[0] == "mx:train_step"]
    assert len(steps) == 3
    assert [st["step_num"] for *_x, st in steps] == [2, 3, 4]
    assert all(st["_r"] == 1 for *_x, st in steps)   # StepTraceAnnotation
    for _n, s0, dur, st in steps:
        inside = [e for e in in_trace if e[3]["parent"] == st["span"]]
        assert [n for n, *_ in inside] == ["mx:" + n for n in STEP_SPANS[1:]]
        assert all(s0 <= s and s + d <= s0 + dur for _n, s, d, _st in inside)
    # the trace's clock and perf_counter tick alike: durations agree
    for (_n, _s, dur, _st), rec in zip(in_trace, kept):
        assert dur * 1e-9 <= rec.t1 - rec.t0 + 1e-6
    # only the profiler was live: no sink, no aggregates
    assert tele.summary()["spans"] == {}
    assert tele.summary()["events"] == {}


def test_the_store_is_bounded(tele, tmp_path):
    tele.enable(str(tmp_path))
    n = tele.SPAN_STORE_SIZE + 10
    for i in range(n):
        with tele.span("s"):
            pass
    kept = tele.spans_between(*EVER)
    assert len(kept) == tele.SPAN_STORE_SIZE
    # the newest are kept: ids are handed out in order
    every = tele.summary()["spans"]["s"]["count"]
    assert every == n and kept[0].span == kept[-1].span - (len(kept) - 1)
    with tele.span("last"):
        pass
    assert tele.spans_between(*EVER)[-1].name == "last"


def test_spans_between_keeps_what_lies_wholly_inside_by_start(tele,
                                                              tmp_path):
    tele.enable(str(tmp_path))
    tele.record_span("late", 5.0, 6.0)
    tele.record_span("early", 1.0, 2.0, executor="X")
    tele.record_span("straddles", 1.5, 7.0)
    assert [s.name for s in tele.spans_between(0.5, 6.0)] == ["early", "late"]
    assert [s.name for s in tele.spans_between(1.0, 7.0)] == [
        "early", "straddles", "late"]
    assert tele.spans_between(1.1, 5.9) == []
    early = tele.spans_between(0.0, 3.0)[0]
    assert (early.t0, early.t1, early.parent) == (1.0, 2.0, 0)


def test_the_kill_switch_silences_both(tele, tmp_path, monkeypatch):
    monkeypatch.setenv("MX_TELEMETRY_SPANS", "0")
    tele.enable(str(tmp_path / "sink"))
    step, x, y = _tiny_step()
    step.step(x, y)
    with _Trace(tmp_path / "trace") as trace:
        assert not tele.spans_enabled()
        assert tele.span("x") is tele.span("y")
        step.step(x, y)
        step.drain()
    assert trace.events() == []
    assert tele.spans_between(*EVER) == []
    tele.flush()
    kinds = {json.loads(ln)["kind"]
             for ln in open(tele.event_path(str(tmp_path / "sink"), 0))}
    assert "step" in kinds and not kinds & {"span", "span_begin", "span_end"}


def test_with_only_the_recorder_the_stream_is_what_it_was(tele, tmp_path):
    tele.enable(str(tmp_path))
    step, x, y = _tiny_step()
    for _ in range(2):
        step.step(x, y)
    step.drain()
    tele.flush()
    events = [json.loads(ln) for ln in open(tele.event_path(str(tmp_path), 0))]
    spans = [e for e in events if e["kind"] == "span"]
    assert [e["name"] for e in sorted(spans, key=lambda e: e["mono"])
            if e["name"] in STEP_SPANS] == STEP_SPANS * 2
    for e in spans:
        assert {"t", "kind", "rank", "name", "span", "parent", "depth",
                "tid", "mono", "dur_ms"} <= set(e)
    by_id = {e["span"]: e for e in spans}
    for e in spans:
        if e["name"] in STEP_SPANS[1:]:
            assert by_id[e["parent"]]["name"] == "train_step"
            assert e["depth"] == 1
    # the waits of the drain stay begin/end pairs
    assert {e["name"] for e in events if e["kind"] == "span_begin"} == {
        "inflight_drain", "loss_wait"}
    assert tele.summary()["spans"]["train_step"]["count"] == 2
    # the store holds what the sink got
    assert sorted(s.span for s in tele.spans_between(*EVER)) == sorted(
        [e["span"] for e in spans]
        + [e["span"] for e in events if e["kind"] == "span_end"])


def test_profiler_scope_and_task_share_the_prefix(tele, tmp_path):
    with _Trace(tmp_path) as trace:
        with mx.profiler.scope("epoch"):
            task = mx.profiler.Task(name="eval")
            task.start()
            task.stop()
    (line,) = trace.events()
    assert [n for n, *_ in line] == ["mx:epoch", "mx:eval"]


def test_a_stat_string_with_the_profilers_separators_keeps_the_later_stats(
        tele, tmp_path):
    with _Trace(tmp_path) as trace:
        with tele.span("s", executor="Step:Dense#1", why="a=b,c", n=7,
                       skipped=[1, 2]):
            pass
    ((_name, _s, _d, stats),) = trace.events()[0]
    assert stats["executor"] == "Step:Dense_1" and stats["why"] == "a_b_c"
    assert stats["n"] == 7 and "skipped" not in stats
