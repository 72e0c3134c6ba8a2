"""The comparison that decides ``correct`` has been shown to fail: the
control (the plain reference computed in the nearest precision below the
configuration's, put in the program's place) and each fault a cell can have
come out as not correct, at the rehearsal size on the CPU.  The faults drive
the rest of a run (everything after the look for a chip) with the timed path
broken underneath."""
import argparse

import pytest

from benchmark import check, loader

BENCH = loader.load_benchmark()
TRAIN = "bert_base_mlm.train_1chip"


def _run(cell_name, seed, seconds=0.3):
    import jax

    import run as bench_run

    cell = loader.Cell(BENCH, cell_name, rehearse=True)
    args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                              trace=0, rehearse=1)
    result, lines = bench_run.run_cell(cell, args, jax.devices()[:1])
    return result, lines


@pytest.fixture(autouse=True)
def _path():
    import sys

    sys.path.insert(0, loader.HERE)
    yield
    sys.path.remove(loader.HERE)


def test_a_sound_run_is_correct_and_prints_each_number_beside_its_limit():
    result, lines = _run(TRAIN, 2**31 + 5)
    assert result["correct"] is True
    assert list(result)[-1] == "check"
    for name, limit in loader.Cell(BENCH, TRAIN, rehearse=True).limits().items():
        assert result["check"][name]["limit"] == limit
        assert any(line.startswith(f"check {name}:") for line in lines)
    assert result["metrics"] == {}          # a rehearsal carries no metric
    assert "cpu.train_throughput" in result["rehearsal"]


def test_training_control_in_the_precision_below_is_not_correct():
    import jax

    from benchmark.drivers import train_job

    cell = loader.Cell(BENCH, TRAIN, rehearse=True)
    dev = jax.devices()[0]
    ref = train_job.reference_readings(cell, 3, dev)
    ctl = train_job.reference_readings(cell, 3, dev,
                                       quant=cell.checks["control"])
    ok, rows, _ = check.verdict(check.training_numbers(ctl, ref),
                                cell.limits())
    assert not ok
    ok, _, _ = check.verdict(check.training_numbers(ref, ref), cell.limits())
    assert ok


def test_fault_step_returns_its_state_unchanged(monkeypatch):
    from mxnet_tpu.parallel import data_parallel as dp

    monkeypatch.setattr(
        dp, "_adam_tree_update",
        lambda params, grads, state, *a, **k: (params, state))
    result, _ = _run(TRAIN, 4)
    assert result["correct"] is False
    assert result["check"]["grad_norm_gap"]["value"] > 0.9


def test_fault_half_of_the_batch_left_out(monkeypatch):
    from mxnet_tpu.parallel import DataParallelStep

    orig = DataParallelStep.step

    def half(self, data, label):
        n = label.shape[0] // 2
        return orig(self, data[:n] if not isinstance(data, (tuple, list))
                    else tuple(d[:n] for d in data), label[:n])

    monkeypatch.setattr(DataParallelStep, "step", half)
    result, _ = _run(TRAIN, 5)
    assert result["correct"] is False
