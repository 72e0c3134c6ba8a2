"""The benchmark's files agree with each other and with the contract's
limits that can be checked without a chip."""
import os
import re

import pytest

from benchmark import loader

ROOT = loader.ROOT
BENCH = loader.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmark/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_units_and_keys(kind):
    allowed = {
        "configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source",
                       "workloads"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}}[kind]
    names = [e["name"] for e in BENCH[kind]]
    assert len(names) == len(set(names))
    for e in BENCH[kind]:
        assert set(e) <= allowed, e
        assert loader.NAME_RE.match(e["name"]), e["name"]
        if "unit" in e:
            assert loader.UNIT_RE.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
            assert e["source"] in loader.SOURCES
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k]


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        for w in m.get("workloads", []):
            assert w in CELLS


def test_chips_and_pairs():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(CELLS) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("cell_name", CELLS)
def test_loader_finds_every_file_of_a_cell(cell_name):
    cell = loader.Cell(BENCH, cell_name)
    assert callable(cell.driver().run)
    assert cell.config["name"] == cell.config_name
    assert hasattr(cell.reference(), "param_spec")
    assert isinstance(cell.limits(), dict) and cell.limits()
    e2e = {m["name"] for m in cell.end_to_end()}
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert m["moves"] in e2e
        spec, read = loader.metric_reader(m["name"])
        assert callable(read)
    # the rehearsal preset lays over the same files
    tiny = loader.Cell(BENCH, cell_name, rehearse=True)
    assert tiny.limits() and tiny.limits() != cell.limits()


def test_limits_are_a_file_of_the_cell_not_of_its_configuration_or_kind():
    have = {f[:-5] for f in os.listdir(os.path.join(loader.HERE, "checks"))}
    assert have == set(CELLS)


def test_a_mix_of_an_unknown_kind_has_no_driver():
    cell = loader.Cell(BENCH, CELLS[0])
    cell.traffic = dict(cell.traffic, kind="no_such_kind")
    with pytest.raises(loader.BenchmarkError):
        cell.driver()
    cell.traffic["kind"] = "../run"
    with pytest.raises(loader.BenchmarkError):
        cell.driver()


def test_a_per_layer_metric_without_its_list_of_cells_is_refused():
    bench = dict(BENCH, per_layer=[
        {k: v for k, v in BENCH["per_layer"][0].items() if k != "workloads"}])
    with pytest.raises(loader.BenchmarkError):
        loader.Cell(bench, CELLS[0]).per_layer()
    with pytest.raises(loader.BenchmarkError):
        loader.Cell(BENCH, "no_such_cell")


def test_config_files_are_their_own_and_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = loader.read_json(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"]
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert loader.NAME_RE.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head|"
                                 r"d_model|d_ff)", key), key


def test_every_metric_file_is_named_by_the_benchmark():
    named = {m["name"] for m in BENCH["per_layer"]}
    have = {f[:-5] for f in os.listdir(os.path.join(loader.HERE, "metrics"))
            if f.endswith(".json")}
    assert named == have


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric():
    for name in CELLS:
        cell = loader.Cell(BENCH, name)
        e2e = [m["name"] for m in cell.end_to_end()]
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in cell.per_layer():
            assert name in m["workloads"]
            assert m["moves"] in e2e and m["moves"] != "setup_s"


def test_shares_of_a_peak_carry_mfu_in_their_names_and_no_roofline_is_claimed():
    per = {m["name"]: m for m in BENCH["per_layer"]}
    assert not [n for n in per if "roofline" in n]      # PERF.md says why
    for moved in {m["moves"] for m in per.values()}:
        assert any("mfu" in n.split(".")[0].split("_") and m["unit"] == "%"
                   and m["moves"] == moved for n, m in per.items())


def test_traffic_files_are_data_with_a_rehearsal_preset():
    for w in BENCH["workloads"]:
        mix = loader.read_json(os.path.join(
            loader.HERE, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(
            loader.HERE, "drivers", mix["kind"] + ".py"))
        assert mix["trace_seconds"] < BENCH["run_seconds"]
        assert set(mix["rehearsal"]) <= set(mix)


def test_peaks_table_has_no_default():
    from benchmark import peaks

    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
