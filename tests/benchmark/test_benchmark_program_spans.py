"""The three ``.train`` metrics that read the program's own spans
(``metrics/program_span_ms.py``): the arithmetic on a hand-written span list,
and a traced rehearsal on the CPU in which the program's ``train_step`` and
the benchmark's ``step_call`` close the books step by step."""
import argparse
import os
import statistics
import sys
from collections import namedtuple

import pytest

from benchmark import loader, spans

BENCH = loader.load_benchmark()
TRAIN = "bert_base_mlm.train_1chip"
NEW = ("step_host_busy_ms.train", "step_dispatch_ms.train",
       "step_block_wait_ms.train")
Span = namedtuple("Span", "name span parent t0 t1")
READER = loader.load_module(os.path.join(
    loader.HERE, "metrics", "program_span_ms.py"), "program_span_ms")

# three whole steps inside [10, 20], one straddling each end; seconds
HAND = [
    Span("train_step", 1, 0, 9.9, 10.2),       # straddles t_on
    Span("dispatch", 2, 1, 10.0, 10.1),
    Span("train_step", 3, 0, 11.0, 11.010),    # no wait: the ring is empty
    Span("input_stage", 4, 3, 11.001, 11.002),
    Span("dispatch", 5, 3, 11.004, 11.007),
    Span("train_step", 6, 0, 12.0, 12.260),
    Span("block_wait", 7, 6, 12.001, 12.251),
    Span("dispatch", 8, 6, 12.255, 12.257),
    Span("train_step", 9, 0, 13.0, 13.256),
    Span("block_wait", 10, 9, 13.001, 13.249),
    Span("dispatch", 11, 9, 13.250, 13.251),
    Span("loss_wait", 12, 0, 14.0, 14.5),      # no step's child
    Span("train_step", 13, 0, 19.9, 20.1),     # straddles t_off
    Span("block_wait", 14, 13, 19.91, 20.0),
]


def _obs(monkeypatch, kept, t_on=10.0, t_off=20.0):
    """What a reader is handed, with the program's store put in by hand."""
    from mxnet_tpu import telemetry

    monkeypatch.setattr(
        telemetry, "spans_between",
        lambda t0, t1: [s for s in kept if s.t0 >= t0 and s.t1 <= t1])
    return {"trace": {"t_on": t_on, "t_off": t_off}}


@pytest.mark.parametrize("metric, per_step_ms", [
    # the step less the part its block_wait child covers
    ("step_host_busy_ms.train", [10.0, 10.0, 8.0]),
    ("step_dispatch_ms.train", [3.0, 2.0, 1.0]),
    # a step with no block_wait counts 0
    ("step_block_wait_ms.train", [0.0, 250.0, 248.0]),
])
def test_the_reader_takes_the_median_over_the_whole_steps_of_the_window(
        monkeypatch, metric, per_step_ms):
    spec, read = loader.metric_reader(metric)
    assert spec["reader"] == "program_span_ms"
    got = read(_obs(monkeypatch, HAND), spec["args"])
    assert got == pytest.approx(statistics.median(per_step_ms), abs=1e-9)
    inside = [s for s in HAND if 10.0 <= s.t0 and s.t1 <= 20.0]
    steps = READER.per_step_ms(inside, spec["args"]["span"],
                               spec["args"].get("less", ()))
    assert [s.span for s, _ms in steps] == [3, 6, 9]
    assert [ms for _s, ms in steps] == pytest.approx(per_step_ms, abs=1e-9)


@pytest.mark.parametrize("metric", NEW)
def test_the_reader_returns_nothing_where_it_finds_no_step(monkeypatch,
                                                           metric):
    from mxnet_tpu import telemetry

    spec, read = loader.metric_reader(metric)
    assert read(_obs(monkeypatch, []), spec["args"]) is None
    only_waits = [s for s in HAND if s.name != "train_step"]
    assert read(_obs(monkeypatch, only_waits), spec["args"]) is None
    assert read({"trace": None}, spec["args"]) is None
    # a program without the store (the parent commit under these files)
    monkeypatch.delattr(telemetry, "spans_between")
    assert read({"trace": {"t_on": 10.0, "t_off": 20.0}},
                spec["args"]) is None


@pytest.mark.parametrize("metric", NEW)
def test_the_new_metrics_are_program_spans_of_the_training_step(metric):
    # only what must hold: later PRs append metrics, and cells to these lists
    (row,) = [m for m in BENCH["per_layer"] if m["name"] == metric]
    assert (row["source"], row["layer"], row["moves"]) == (
        "program_span", "training step", "train_throughput")
    assert (row["unit"], row["better"]) == ("ms", "lower")
    assert TRAIN in row["workloads"]


def test_a_traced_rehearsal_reports_the_three_and_the_books_close(
        monkeypatch):
    import jax

    from mxnet_tpu import telemetry

    monkeypatch.delenv("MX_TELEMETRY_DIR", raising=False)
    monkeypatch.delenv("MX_TELEMETRY_SPANS", raising=False)
    sys.path.insert(0, loader.HERE)
    try:
        import run as bench_run
    finally:
        sys.path.remove(loader.HERE)
    recs = []

    class Recorder(spans.Recorder):
        def __init__(self):
            super().__init__()
            recs.append(self)

    monkeypatch.setattr(spans, "Recorder", Recorder)
    telemetry.reset()
    cell = loader.Cell(BENCH, TRAIN, rehearse=True)
    args = argparse.Namespace(workload=TRAIN, seed=2**31 + 25, seconds=0.6,
                              trace=1, rehearse=1)
    result, _lines = bench_run.run_cell(cell, args, jax.devices()[:1])
    assert result["correct"] is True
    assert result["metrics"] == {}          # a rehearsal carries no metric
    for name in NEW:
        assert result["rehearsal"]["cpu." + name] > 0.0
    # the program's spans were live for the traced part of the window only
    (rec,) = recs
    calls = [(a, b) for n, a, b in rec.spans if n == "step_call"]
    steps = [s for s in telemetry.spans_between(0.0, float("inf"))
             if s.name == "train_step"]
    assert 3 <= len(steps) < len(calls)
    # bench:step_call encloses mx:train_step and nothing else
    outside = []
    for s in steps:
        (call,) = [(a, b) for a, b in calls if a <= s.t0 and s.t1 <= b]
        outside.append((call[1] - call[0]) - (s.t1 - s.t0))
    assert min(outside) >= 0.0
    assert statistics.median(outside) < 0.2e-3
    telemetry.reset()
