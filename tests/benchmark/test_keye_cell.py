"""The Keye configuration and its cell (``keye_vl_2_0_30b_a3b.train_1x16k``),
at the rehearsal size on the CPU: a sound run is correct and reads its
counters, the control and both planted faults (the selection ignored, and a
selection by recency) are not correct, and the configuration file holds
every published number, states the deployment, and its costs count the
arithmetic the deployment states."""
import argparse

import pytest

from benchmark import check, loader

BENCH = loader.load_benchmark()
KEYE = "keye_vl_2_0_30b_a3b.train_1x16k"
CATALOG = {   # the catalog's ``config`` of Keye-VL-2.0-30B-A3B
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


@pytest.fixture(autouse=True)
def _path():
    import sys

    sys.path.insert(0, loader.HERE)
    yield
    sys.path.remove(loader.HERE)


def test_the_cell_rehearses_correct_and_reads_its_counters():
    import jax

    import run as bench_run
    from mxnet_tpu import telemetry

    telemetry.reset()
    cell = loader.Cell(BENCH, KEYE, rehearse=True)
    args = argparse.Namespace(workload=KEYE, seed=2**31 + 40, seconds=0.3,
                              trace=1, rehearse=1)
    result, lines = bench_run.run_cell(cell, args, jax.devices()[:1])
    assert result["correct"] is True, lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(cell.limits()) <= set(result["check"])
    assert result["rehearsal"]["cpu.moe_load_max_over_mean.train"] >= 1.0
    # the indexers' KL and tile counter reach telemetry at drain, a reading
    # a layer; the CPU takes the dense compositions, so no tile of the
    # kernels is counted at trace time
    kl = telemetry.aux_readings("dsa_kl")
    hit = telemetry.aux_readings("dsa_tile_hit")
    assert len(kl) == len(hit) == len(cell.config["layer_types"])
    assert all(0.0 < v[0] < 5.0 for v in kl.values())
    assert all(v == [1.0] for v in hit.values())       # one tile, hit
    assert _reader("dsa_tile_hit_pct.train")[0]({}, {}) == 100.0
    assert telemetry.summary()["dsa_tiles"] == []


@pytest.fixture(scope="module")
def sound():
    import jax

    from benchmark.drivers import train_job

    cell = loader.Cell(BENCH, KEYE, rehearse=True)
    return cell, train_job.reference_readings(cell, 5, jax.devices()[0])


@pytest.mark.parametrize("fault", ["fp8", "dense", "recent"])
def test_the_control_and_both_planted_faults_are_not_correct(fault, sound):
    """The reference in the precision below put in the program's place;
    attention over every earlier key (the selection ignored); and over the
    ``topk`` latest keys (a selection by recency)."""
    import jax

    from benchmark import weights
    from benchmark.drivers import train_job

    cell, ref = sound
    dev = jax.devices()[0]
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
            cfg, w, tokens, 5, traffic["check_steps"], 1, probe=check.sketch,
            fault=fault)
    ok, _rows, _ = check.verdict(check.training_numbers(got, ref),
                                 cell.limits())
    assert not ok
    ok, _rows, _ = check.verdict(check.training_numbers(ref, ref),
                                 cell.limits())
    assert ok


def test_the_configuration_file_holds_every_published_number_and_the_cut():
    cfg = loader.Cell(BENCH, KEYE).config
    row = [c for c in BENCH["configs"] if c["name"] == cfg["name"]][0]
    assert row["source"] == cfg["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json")
    reduced = {"layer_types", "num_experts", "num_local_experts",
               "vocab_size"}
    assert set(row["reduced"]) == reduced == set(cfg["reduced_from"])
    for key, value in CATALOG.items():
        if key in reduced:
            assert cfg["reduced_from"][key] == value or key == "layer_types"
        else:
            assert cfg[key] == value, key
    assert (cfg["num_experts"], cfg["num_local_experts"], cfg["vocab_size"],
            len(cfg["layer_types"])) == (16, 16, 18992, 4)
    assert "ONE OF 8 THAT SHARE EACH LAYER" in cfg["deployment"]
    assert {"vision_tower", "rotary", "indexer_rope", "indexer_precision",
            "selection", "chunks", "indexer_loss"} <= set(cfg["assumed"])
    kw = cfg["program"]["factory_kwargs"]
    assert kw["experts_held"] == [0, 16] and kw["topk"] == 2048
    assert kw["router_lr_mult"] == 0.0 and kw["num_layers"] == 4
    why = [w["why"] for w in BENCH["workloads"] if w["name"] == KEYE][0]
    assert "8x their share" in why


def test_the_costs_count_the_deployments_arithmetic():
    from benchmark import flops_keye, kernel_costs_keye

    cell = loader.Cell(BENCH, KEYE)
    cfg, traffic = cell.config, cell.traffic
    seq = traffic["seq_len"]
    assert flops_keye.pairs_selected(seq, 2048) == 31458304
    per_token = flops_keye.keye_train_flops_per_token(cfg, seq)
    assert round(per_token / 1e9, 3) == 1.439
    assert round(per_token * seq / 1e12, 1) == 23.6
    flops, nbytes = kernel_costs_keye.dsa_attention(cfg, traffic)
    assert flops == 3 * 4 * 31458304 * 32 * 128 * 4 and nbytes > 0
    flops, nbytes = kernel_costs_keye.dsa_scores(cfg, traffic)
    assert 0 < flops < 0.1 * per_token * seq and nbytes > 0
    # the bisection makes no product and reads the causal half once
    assert kernel_costs_keye.dsa_topk(cfg, traffic) == (
        0.0, 4 * 4 * (seq * (seq + 1) // 2 + seq))
    flops, nbytes = kernel_costs_keye.dsa_kl_fwd(cfg, traffic)
    assert flops == 4 * 31458304 * 16 * 64 * 2 and nbytes > 0
    assert kernel_costs_keye.dsa_kl_bwd(cfg, traffic) == (2 * flops,
                                                         2 * nbytes)


# what the indexer's per-layer metrics would read: (reader, args) of each
# (PERF.md section 7 y says why BENCHMARK.json does not list them yet)
PREPARED = {
    "dsa_indexer_ms.train": ("kernel_ms_per_step", {"kernel": "dsa_indexer"}),
    "dsa_attention_peak_share.train": ("kernel_peak_share", {
        "kernel": "flash_attention",
        "cost": "benchmark.kernel_costs_keye:dsa_attention"}),
    "dsa_tile_hit_pct.train": ("dsa_tile_hit_pct", {}),
    **{f"dsa_{k}_peak_share.train": ("kernel_peak_share", {
        "kernel": f"dsa_{k}", "cost": f"benchmark.kernel_costs_keye:dsa_{k}"})
       for k in ("scores", "topk", "kl_fwd", "kl_bwd")}}


def _reader(name):
    import os

    reader, args = PREPARED[name]
    mod = loader.load_module(os.path.join(loader.HERE, "metrics",
                                          reader + ".py"), "keye_" + reader)
    return mod.read, args


@pytest.mark.parametrize("name", sorted(PREPARED))
def test_the_indexers_readers_find_their_kernel_and_read_nothing_elsewhere(
        name):
    from mxnet_tpu import telemetry

    read, args = _reader(name)
    cell = loader.Cell(BENCH, KEYE)
    assert args.get("kernel", "dsa_indexer") in cell.checks["kernels"]
    telemetry.reset()
    trace = {"steps": 2, "kernel_seconds": {}}
    # a trace with no event of the kernel, or a program with no reading
    assert read({"trace": trace, "peaks": None, "cell": cell}, args) is None


def test_the_tile_hit_share_is_the_layers_mean():
    from mxnet_tpu import telemetry

    read, args = _reader("dsa_tile_hit_pct.train")
    telemetry.reset()
    telemetry.record_aux_reading("dsa_tile_hit", "layer0_tile_hit", [0.5])
    telemetry.record_aux_reading("dsa_tile_hit", "layer1_tile_hit", [1.0])
    assert read({}, args) == pytest.approx(75.0)
    telemetry.reset()


@pytest.mark.parametrize("name, kernel, ms", [
    ("dsa_scores_peak_share.train", "dsa_scores", 24.59),
    ("dsa_topk_peak_share.train", "dsa_topk", 48.64),
    ("dsa_kl_fwd_peak_share.train", "dsa_kl_fwd", 38.83),
    ("dsa_kl_bwd_peak_share.train", "dsa_kl_bwd", 67.86),
    ("dsa_attention_peak_share.train", "flash_attention", 273.67)])
def test_a_share_at_a_v5e_steps_kernel_time_is_a_share(name, kernel, ms):
    """The kernel's ms a step in a traced run of the cell on a v5e (PERF.md
    section 5): the cost counts no more than the chip could do in it."""
    from benchmark import peaks

    read, args = _reader(name)
    obs = {"trace": {"steps": 3, "kernel_seconds": {kernel: 3 * ms / 1e3}},
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "cell": loader.Cell(BENCH, KEYE)}
    assert 1.0 < read(obs, args) < 50.0
