"""The configuration and cell PR 31 added (``zaya1_8b.train_1x8k``), at the
rehearsal size on the CPU: a sound run is correct and reads its counter, the
control and a planted fault are not correct, and the configuration file
states the stage with every published width."""
import argparse
import os
import re

import numpy as np
import pytest

from benchmark import check, loader

BENCH = loader.load_benchmark()
ZAYA = "zaya1_8b.train_1x8k"


@pytest.fixture(autouse=True)
def _path():
    import sys

    sys.path.insert(0, loader.HERE)
    yield
    sys.path.remove(loader.HERE)


def test_the_cell_rehearses_correct_and_reads_its_counter():
    import jax

    import run as bench_run

    cell = loader.Cell(BENCH, ZAYA, rehearse=True)
    args = argparse.Namespace(workload=ZAYA, seed=2**31 + 31, seconds=0.3,
                              trace=1, rehearse=1)
    result, lines = bench_run.run_cell(cell, args, jax.devices()[:1])
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(cell.limits()) <= set(result["check"])
    # the expert layers' load counter reaches the metric at close
    assert result["rehearsal"]["cpu.moe_load_max_over_mean.train"] >= 1.0
    # nothing of the device on a CPU: the trace's readers return nothing
    for name in ("cca_mix_ms", "router_mlp_ms", "flash_attention_peak_share",
                 "gated_experts_peak_share"):
        assert f"cpu.{name}.train" not in result["rehearsal"]


@pytest.mark.parametrize("fault", ["fp8", "half_row"])
def test_the_control_and_a_half_row_fault_are_not_correct(fault):
    """The reference in the precision below put in the program's place, and
    the reference with the loss over the row's first half only (the batch is
    one row: there is no half of the rows to leave out)."""
    import jax

    from benchmark import weights
    from benchmark.drivers import train_job

    cell = loader.Cell(BENCH, ZAYA, rehearse=True)
    dev = jax.devices()[0]
    ref = train_job.reference_readings(cell, 5, dev)
    if fault == "fp8":
        assert cell.checks["control"] == "fp8"
        got = train_job.reference_readings(cell, 5, dev, quant="fp8")
    else:
        cfg, traffic = cell.config, cell.traffic
        tokens = train_job.batch_tokens(5, cfg["vocab_size"], 1,
                                        traffic["seq_len"])
        w = weights.make_weights(cell.reference().param_spec(cfg), 5,
                                 cfg["dtype"], dev)
        got = cell.reference().train(
            cfg, w, tokens, 5, traffic["check_steps"], 1,
            positions=traffic["seq_len"] // 2, probe=check.sketch)
    ok, _rows, _ = check.verdict(check.training_numbers(got, ref),
                                 cell.limits())
    assert not ok
    ok, _rows, _ = check.verdict(check.training_numbers(ref, ref),
                                 cell.limits())
    assert ok


def test_the_configuration_file_states_the_stage_and_every_published_width():
    cfg = loader.Cell(BENCH, ZAYA).config
    row = [c for c in BENCH["configs"] if c["name"] == cfg["name"]][0]
    assert row["source"] == cfg["source"] == \
        "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    assert set(row["reduced"]) == {"layer_types", "vocab_size"}
    assert cfg["layer_types"] == ["hybrid"] * 4 and cfg["vocab_size"] == 32784
    assert (cfg["vocab_size_published"], cfg["num_hidden_layers"],
            cfg["num_hidden_layers_held"]) == (262272, 40, 4)
    assert set(cfg["reduced_from"]) == set(row["reduced"])
    published = {"hidden_size": 2048, "num_attention_heads": 8,
                 "num_key_value_heads": 2, "head_dim": 128,
                 "moe_intermediate_size": 2048, "num_experts": 16,
                 "num_experts_per_tok": 1, "router_hidden_size": 256,
                 "cca_time0": 2, "cca_time1": 2,
                 "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-5,
                 "max_position_embeddings": 131072, "hidden_act": "silu",
                 "tie_word_embeddings": True, "model_type": "zaya"}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000,
        "rope_type": "default"}
    assert "4 of the 40 layers" in cfg["deployment"]
    assert "all 16 experts" in cfg["deployment"]
    for key in ("order", "value_shift", "mean_across_heads", "convolutions",
                "qk_norm", "rotary", "router", "balance_bias", "residual",
                "experts", "router_lr_mult", "dtype", "optimizer", "loss"):
        assert cfg["assumed"][key]
    # every width is the factory's default: the program is told its stage
    assert cfg["program"]["factory_kwargs"] == {
        "vocab_size": 32784, "num_layers": 4, "router_lr_mult": 0.0}
    assert cfg["router_lr_mult"] == 0.0        # PERF.md section 6, PR 31


def test_the_stage_holds_897_million_parameters_and_the_model_builds_them():
    cell = loader.Cell(BENCH, ZAYA)
    spec = cell.reference().param_spec(cell.config)
    total = sum(int(np.prod(s)) for _n, s, _i in spec)
    assert round(total / 1e6, 1) == 897.4
    layer = sum(int(np.prod(s)) for n, s, _i in spec
                if n.startswith("layer0_"))
    assert round(layer / 1e6, 1) == 207.6      # 5.57 + 0.66 + 201.3 M
    net = loader.factory(cell.config["program"]["factory"])(
        **cell.config["program"]["factory_kwargs"])
    mine = {k[len(net.prefix):]: tuple(p.shape)
            for k, p in net.collect_params().items()}
    assert mine == {n: tuple(s) for n, s, _i in spec}


def test_flops_and_kernel_costs_count_model_work_only():
    from benchmark import flops_zaya, kernel_costs_zaya

    cell = loader.Cell(BENCH, ZAYA)
    cfg, traffic = cell.config, cell.traffic
    per_token = flops_zaya.zaya_train_flops_per_token(cfg, traffic["seq_len"])
    # a layer's multiply-adds a token, forward: projections 5.24 M, head
    # mixing 0.33 M, the causal half of the scores 8.39 M, router 0.66 M, one
    # gated expert 12.58 M; the head 67.1 M
    layer = 5242880 + 327680 + 8388608 + 659456 + 12582912
    assert per_token == 3 * 2 * (4 * layer + 2048 * 32784)
    assert round(per_token * 8192 / 1e12, 2) == 8.65     # ISSUE 31's 8.6
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    flops, nbytes = kernel_costs_zaya.flash_attention(cfg, traffic)
    assert flops == 3 * 4 * tokens * 2 * 8388608
    assert nbytes == 3 * 4 * tokens * 2 * (8 + 2) * 128 * 2
    flops, nbytes = kernel_costs_zaya.gated_experts(cfg, traffic)
    assert flops == 3 * 4 * tokens * 2 * 12582912
    assert nbytes == 3 * 4 * (tokens * 2 * 2048 + 16 * 12582912) * 2
    assert flops < 0.3 * per_token * tokens


def test_the_reference_is_plain_and_imports_nothing_of_the_program():
    path = os.path.join(loader.HERE, "references", "zaya1_8b.py")
    with open(path) as f:
        src = f.read()
    imports = re.findall(r"^\s*(?:import|from)\s+([\w.]+)", src, re.M)
    assert set(imports) == {"functools", "json", "jax", "jax.numpy", "numpy"}
    assert "Precision.HIGHEST" in src and "pallas" not in src
    for departure in ("norm-then-rotary", "SECOND half", "rep", "grp",
                      "biases", "tanh form", "res", "skips computation"):
        assert departure in src, departure
