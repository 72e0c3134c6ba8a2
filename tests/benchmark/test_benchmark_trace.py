"""The reduction from a profiler trace to busy/idle share, per-kernel time
and the idle gaps, on a small trace recorded on the chip and kept beside
this file.  The expected numbers were worked out apart from the reduction,
by marking every nanosecond of the window busy or idle."""
import os

import pytest

from benchmark import loader, tracing

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = loader.read_json(os.path.join(HERE, "data", "small_trace.json"))
KERNELS = loader.read_json(os.path.join(
    loader.HERE, "checks", "bert_base_mlm.train_1chip.json"))["kernels"]

WINDOW_NS = 12_735_837
BUSY_NS = 453_942
LAYER_NORM_NS, LAYER_NORM_CALLS = 1_963, 2
GAP_WAIT_ARRIVAL_NS, GAP_NO_SPAN_NS = 7_514_344, 4_767_551


def test_busy_idle_and_kernel_time_of_the_recorded_trace():
    r = tracing.reduce(TRACE, KERNELS)
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(WINDOW_NS * 1e-9, rel=1e-12)
    assert r["busy_s"] == pytest.approx(BUSY_NS * 1e-9, rel=1e-9)
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(
        100 * (1 - BUSY_NS / WINDOW_NS), rel=1e-9)
    assert r["kernel_calls"] == {"layer_norm": LAYER_NORM_CALLS}
    assert r["kernel_seconds"]["layer_norm"] == pytest.approx(
        LAYER_NORM_NS * 1e-9, rel=1e-9)


def test_idle_gaps_are_laid_against_the_host_spans_and_sum_to_the_idle_time():
    r = tracing.reduce(TRACE, KERNELS)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps["wait_arrival"] == pytest.approx(GAP_WAIT_ARRIVAL_NS * 1e-9)
    assert gaps[tracing.NO_SPAN] == pytest.approx(GAP_NO_SPAN_NS * 1e-9)
    assert sum(gaps.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    ops = r["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops[0][1] == pytest.approx(408_873e-9)
    assert ops[0][0] == "%copy.3 copy f32[256,128,1024]"
    assert all(len(name) <= 120 for name, _ in ops)


def test_without_the_window_span_the_device_events_bound_the_window():
    planes = [p for p in TRACE["planes"] if p["name"].startswith("/device")]
    r = tracing.reduce({"planes": planes}, KERNELS)
    evs = planes[0]["lines"][0]["events"]
    lo = min(s for _n, s, _d in evs)
    hi = max(s + d for _n, s, d in evs)
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] < r["window_s"]
    assert list(dict(r["breakdown"]["idle_gaps"])) == [tracing.NO_SPAN]


def test_two_chips_average_their_busy_time():
    dev = [p for p in TRACE["planes"] if p["name"].startswith("/device")][0]
    half = dict(dev, name="/device:TPU:1", lines=[
        {"name": "XLA Ops", "events": dev["lines"][0]["events"][::2]}])
    one = tracing.reduce(TRACE, KERNELS)
    two = tracing.reduce({"planes": TRACE["planes"] + [half]}, KERNELS)
    alone = tracing.reduce({"planes": [half] + [
        p for p in TRACE["planes"] if not p["name"].startswith("/device")]},
        KERNELS)
    assert two["chips"] == 2
    assert two["busy_s"] == pytest.approx(
        (one["busy_s"] + alone["busy_s"]) / 2)


def test_short_names_an_instruction_without_its_operands():
    text = ('%jvp__.27 = (bf16[16384,768]{1,0:T(8,128)(2,1)}, f32[16384,1]'
            '{1,0:T(8,128)S(1)}) custom-call(bf16[16384,768]{1,0} %b), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tracing.short(text) == \
        "%jvp__.27 custom-call bf16[16384,768] tpu_custom_call"
    assert tracing.short("bench:step_call") == "bench:step_call"
