"""The small pieces of the yardstick against hand-computed values: the
seeded batch and weights, the per-layer readers (a reader that finds nothing
to read returns nothing, never 0), and the comparison's arithmetic."""
import math

import numpy as np
import pytest

from benchmark import check, loader, weights
from benchmark.drivers import train_job

BENCH = loader.load_benchmark()
TRAIN = "bert_base_mlm.train_1chip"
BIG_SEED = 2**31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_the_batch_is_made_from_the_seed_and_its_rows_all_differ(seed):
    a = train_job.batch_tokens(seed, 30522, 32, 512)
    b = train_job.batch_tokens(seed, 30522, 32, 512)
    assert a.dtype == np.int32 and a.shape == (32, 512)
    assert np.array_equal(a, b)
    assert 0 <= a.min() and a.max() < 30522
    assert len({r.tobytes() for r in a}) == 32
    assert not np.array_equal(a, train_job.batch_tokens(seed + 1, 30522,
                                                        32, 512))


def test_weights_are_made_from_the_seed_in_the_served_type():
    spec = [("w", (4, 3), ("normal", 0.02)), ("b", (3,), ("const", 1.0))]
    a = weights.make_weights(spec, BIG_SEED, "bfloat16")
    b = weights.make_weights(spec, BIG_SEED, "bfloat16")
    c = weights.make_weights(spec, BIG_SEED + 1, "bfloat16")
    assert str(a["w"].dtype) == "bfloat16" and a["w"].shape == (4, 3)
    assert np.array_equal(np.asarray(a["w"], np.float32),
                          np.asarray(b["w"], np.float32))
    assert not np.array_equal(np.asarray(a["w"], np.float32),
                              np.asarray(c["w"], np.float32))
    assert np.all(np.asarray(a["b"], np.float32) == 1.0)


def _obs(**kw):
    cell = loader.Cell(BENCH, TRAIN)
    obs = {"cell": cell, "chips": 1, "seq_len": 512, "window_s": 20.0,
           "items": 80 * 32 * 512, "peaks": {"bf16_flops": 197e12},
           "trace": None, "compiles_in_window": 0}
    obs.update(kw)
    return obs


def test_step_mfu_is_model_flops_over_time_chips_and_peak():
    spec, read = loader.metric_reader("step_mfu.train")
    # 80 steps of 16,384 tokens at 710,415,360 FLOPs a token in 20 s
    want = 100.0 * 80 * 16384 * 710_415_360 / 20.0 / 197e12
    assert read(_obs(), spec["args"]) == pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(23.6327, abs=1e-3)
    assert read(_obs(chips=4), spec["args"]) == pytest.approx(want / 4)


@pytest.mark.parametrize("metric, obs", [
    ("step_mfu.train", {"peaks": None}),
    ("step_mfu.train", {"items": 0}),
    ("device_idle_pct.train", {"trace": None}),
    ("device_idle_pct.train", {"trace": {"busy_s": 0.0, "window_s": 3.0}}),
    ("layer_norm_ms.train", {"trace": None}),
    ("layer_norm_ms.train", {"trace": {"steps": 0, "kernel_seconds": {}}}),
    ("layer_norm_ms.train", {"trace": {"steps": 12, "kernel_seconds": {}}}),
    ("layer_norm_ms.train",
     {"trace": {"steps": 12, "kernel_seconds": {"layer_norm": 0.0}}}),
])
def test_a_reader_that_finds_nothing_to_read_returns_nothing(metric, obs):
    spec, read = loader.metric_reader(metric)
    assert read(_obs(**obs), spec["args"]) is None


def test_trace_readers_take_the_idle_share_and_the_kernel_time_per_step():
    tr = {"busy_s": 2.994, "window_s": 3.0, "steps": 12,
          "kernel_seconds": {"layer_norm": 0.00888}}
    spec, read = loader.metric_reader("device_idle_pct.train")
    assert read(_obs(trace=tr), spec["args"]) == pytest.approx(0.2)
    spec, read = loader.metric_reader("layer_norm_ms.train")
    assert read(_obs(trace=tr), spec["args"]) == pytest.approx(0.74)
    spec, read = loader.metric_reader("compiles_in_window.train")
    assert read(_obs(compiles_in_window=2), spec["args"]) == 2.0


def test_worst_leaf_gap_is_the_gap_of_norms_over_the_larger_of_leaf_and_median():
    ref = {"a": 10.0, "b": 1.0, "c": 1e-4}
    got = {"a": 10.5, "b": 1.2, "c": 2e-4}
    # median 1.0: a 0.5/10, b 0.2/1, c 1e-4/1 (against the median leaf's)
    gap, at = check.worst_leaf_gap(got, ref)
    assert at == "b" and gap == pytest.approx(0.2)
    gap, at = check.worst_leaf_gap(got, ref, ["a", "c"])
    assert at == "a" and gap == pytest.approx(0.05)
    with pytest.raises(ValueError):
        check.worst_leaf_gap({"a": 1.0}, ref)


def test_leaves_whose_reference_gradient_is_nought_are_left_out_of_the_change():
    ref = {"q": 1.0, "k_bias": 5e-4, "v": 2.0, "pooler": 0.0, "w": 1.5}
    assert sorted(check.moving_leaves(ref)) == ["q", "v", "w"]


@pytest.mark.parametrize("numbers, ok", [
    ({"x": (0.5, ""), "y": (0.0, "")}, True),
    ({"x": (1.0, ""), "y": (0.0, "")}, True),        # at the limit
    ({"x": (1.0001, ""), "y": (0.0, "")}, False),
    ({"x": (math.nan, ""), "y": (0.0, "")}, False),
    ({"x": (0.5, "")}, False),                       # a held number missing
    ({"x": (0.5, ""), "y": (1e-9, "")}, False),      # an exact comparison
])
def test_verdict_holds_every_number_a_limit_names(numbers, ok):
    good, rows, lines = check.verdict(numbers, {"x": 1.0, "y": 0})
    assert good is ok
    assert len(lines) == 2 and all(ln.startswith("check ") for ln in lines)


def test_verdict_prints_a_number_with_no_limit_and_does_not_hold_it():
    good, rows, lines = check.verdict({"x": (0.5, "d"), "z": (9.0, "d")},
                                      {"x": 1.0})
    assert good and rows["z"] == {"value": 9.0, "limit": None}
    assert rows["x"] == {"value": 0.5, "limit": 1.0}
    assert any("z" in ln and "not held" in ln for ln in lines)


def test_without_a_tpu_the_run_exits_non_zero_and_prints_no_result(capsys):
    import sys

    sys.path.insert(0, loader.HERE)
    try:
        import run as bench_run
    finally:
        sys.path.remove(loader.HERE)
    rc = bench_run.main(["--workload", TRAIN, "--seed", str(BIG_SEED),
                         "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "no TPU" in out.err
