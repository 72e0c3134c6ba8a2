"""The configuration and cell PR 27 added, at the rehearsal size on the CPU: a
sound run is correct and reads its counter, the control is not correct, and
the configuration file states the share."""
import argparse

import pytest

from benchmark import check, loader

BENCH = loader.load_benchmark()
NEMOTRON = "nemotron_twotower_30b_a3b.train_2x8k"


@pytest.fixture(autouse=True)
def _path():
    import sys

    sys.path.insert(0, loader.HERE)
    yield
    sys.path.remove(loader.HERE)


def _run(cell_name, seed, seconds=0.3, trace=0):
    import jax

    import run as bench_run

    cell = loader.Cell(BENCH, cell_name, rehearse=True)
    args = argparse.Namespace(workload=cell_name, seed=seed, seconds=seconds,
                              trace=trace, rehearse=1)
    return bench_run.run_cell(cell, args, jax.devices()[:cell.chips])


def test_the_new_configuration_rehearses_correct_and_reads_its_counter():
    result, lines = _run(NEMOTRON, 2**31 + 27, trace=1)
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    tiny = loader.Cell(BENCH, NEMOTRON, rehearse=True)
    assert set(tiny.limits()) <= set(result["check"])
    # the expert layers' load counter reaches the metric at close
    assert result["rehearsal"]["cpu.moe_load_max_over_mean.train"] >= 1.0
    # nothing of the device on a CPU: the trace's readers return nothing
    assert "cpu.ssd_scan_ms.train" not in result["rehearsal"]


def test_the_new_configurations_control_in_the_precision_below_is_not_correct():
    import jax

    from benchmark.drivers import train_job

    cell = loader.Cell(BENCH, NEMOTRON, rehearse=True)
    dev = jax.devices()[0]
    ref = train_job.reference_readings(cell, 5, dev)
    ctl = train_job.reference_readings(cell, 5, dev,
                                       quant=cell.checks["control"])
    ok, _rows, _ = check.verdict(check.training_numbers(ctl, ref),
                                 cell.limits())
    assert not ok


def test_the_configuration_file_states_the_share_and_every_published_width():
    cfg = loader.Cell(BENCH, NEMOTRON).config
    row = [c for c in BENCH["configs"] if c["name"] == cfg["name"]][0]
    assert set(row["reduced"]) == {"hybrid_override_pattern",
                                   "n_routed_experts", "vocab_size"}
    assert (cfg["hybrid_override_pattern"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == ("MEMEM*EME", 8, 16384)
    assert cfg["n_routed_experts_published"] == 128
    assert "one of 16 chips that share each layer" in cfg["deployment"]
    assert list(cfg["assumed"])[0] == "tower"          # the unbuilt tower first
    published = {"hidden_size": 2688, "mamba_num_heads": 64,
                 "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
                 "conv_kernel": 4, "chunk_size": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "moe_intermediate_size": 1856,
                 "moe_shared_expert_intermediate_size": 3712,
                 "num_experts_per_tok": 6, "routed_scaling_factor": 2.5}
    assert {k: cfg[k] for k in published} == published
    kw = cfg["program"]["factory_kwargs"]
    assert kw == {"vocab_size": 16384, "hybrid_override_pattern": "MEMEM*EME",
                  "experts_held": [0, 8],      # every width the model's default
                  "router_lr_mult": 0.0}       # no exchange, no router update


def test_kernel_costs_count_model_work_only():
    from benchmark import flops_nemotron_h, kernel_costs

    cell = loader.Cell(BENCH, NEMOTRON)
    per_token = flops_nemotron_h.nemotron_h_train_flops_per_token(
        cell.config, cell.traffic["seq_len"])
    assert round(per_token / 1e9, 2) == 2.15             # ISSUE 27's count
    tokens = cell.traffic["batch_per_chip"] * cell.traffic["seq_len"]
    for name in ("ssd_scan", "moe_experts"):
        flops, nbytes = getattr(kernel_costs, name)(cell.config, cell.traffic)
        assert 0 < flops < 0.1 * per_token * tokens and nbytes > 0
