"""The eight ``model blocks`` metrics PR 35 added, on a small recorded trace
with its scope map beside it (``data/scope_trace.json``, ``scope_map.json``:
two steps, each with a ``while`` whose body's ops are events too): a loop's
body counts once, a reader without a map returns nothing, the share that no
scope places is the planted one, and ``tools/scope_table.py`` prints the
same table."""
import io
import json
import os
import re

import pytest

from benchmark import loader, tracing

BENCH = loader.load_benchmark()
DATA = os.path.join(os.path.dirname(__file__), "data")
CELLS = [w["name"] for w in BENCH["workloads"]]
NEW = {"attn_sublayer_ms.train": CELLS, "ssm_sublayer_ms.train": CELLS[1:2],
       "moe_sublayer_ms.train": CELLS[1:], "ffn_sublayer_ms.train":
       [CELLS[0], CELLS[3]], "head_loss_ms.train": CELLS,
       "update_ms.train": CELLS, "recompute_ms.train": CELLS[1:],
       "unscoped_pct.train": CELLS}
# ms a step in the recorded trace, by hand (the module docstring)
PLANTED = {"attn_sublayer_ms.train": 4.0 + 6.0,
           "moe_sublayer_ms.train": 9.0 + 3.0,      # the while ONCE, and the
           "ffn_sublayer_ms.train": None,           # shared GatedMLP in it
           "ssm_sublayer_ms.train": None,
           "head_loss_ms.train": 5.0 + 2.0, "update_ms.train": 7.0,
           "recompute_ms.train": 3.0}
ENTRY_MS = 40.0


def _read(path):
    with open(os.path.join(DATA, path)) as f:
        return json.load(f)


@pytest.fixture
def obs():
    from mxnet_tpu import telemetry

    telemetry.reset()
    tr = tracing.reduce(_read("scope_trace.json"))
    tr["steps"] = 2
    yield {"trace": tr}
    telemetry.reset()


@pytest.fixture
def mapped(obs):
    from mxnet_tpu import telemetry

    for executor, scopes in _read("scope_map.json").items():
        if executor != "recorded":
            telemetry.record_scope_map(executor, scopes)
    return obs


@pytest.mark.parametrize("name", sorted(PLANTED))
def test_a_kind_sums_its_entry_events_and_a_loops_body_once(mapped, name):
    spec, read = loader.metric_reader(name)
    got = read(mapped, spec["args"])
    if PLANTED[name] is None:
        assert got is None
    else:
        assert got == pytest.approx(PLANTED[name], rel=1e-9)


def test_the_trace_really_holds_the_body_twice_over():
    ops = tracing.reduce(_read("scope_trace.json"))["op_seconds"]
    body = sum(t for k, t in ops.items() if k.startswith(
        ("%fusion.20 ", "%copy.21 ")))
    loop, = (t for k, t in ops.items() if k.startswith("%while.3 "))
    assert body == pytest.approx(2 * 3 * 2.8e-3) and body < loop
    # what tracing.reduce's op_seconds sums (PERF.md section 7 f) against
    # what the scope readers count
    assert sum(ops.values()) == pytest.approx(2 * (ENTRY_MS + 8.4) * 1e-3)


def test_unscoped_pct_reads_the_planted_share(mapped):
    spec, read = loader.metric_reader("unscoped_pct.train")
    # XLA's own copy (1 ms, no scope) and the fusion the map lacks (0.5)
    assert read(mapped, spec["args"]) == pytest.approx(
        100.0 * 1.5 / ENTRY_MS)


@pytest.mark.parametrize("name", sorted(NEW))
def test_without_a_map_a_reader_returns_nothing(obs, name, monkeypatch):
    from mxnet_tpu import telemetry

    spec, read = loader.metric_reader(name)
    assert read(dict(obs), spec["args"]) is None        # nobody handed one
    monkeypatch.delattr(telemetry, "scope_map")         # a parent commit
    assert read(dict(obs), spec["args"]) is None
    assert read({"trace": None}, spec["args"]) is None


def test_a_map_handed_as_a_function_is_made_on_the_ask(obs):
    from mxnet_tpu import telemetry

    made = []
    scopes = _read("scope_map.json")["DataParallelStep:XingModel#1"]
    telemetry.record_scope_map("step", lambda: (made.append(1), scopes)[1])
    assert made == []
    spec, read = loader.metric_reader("update_ms.train")
    assert read(obs, spec["args"]) == pytest.approx(7.0)
    spec, read = loader.metric_reader("head_loss_ms.train")
    assert read(obs, spec["args"]) == pytest.approx(7.0)
    assert made == [1] and telemetry.scope_map()["step"] is scopes


@pytest.mark.parametrize("scope, kind", [
    ("M.m_/XingLayer.l1_/XingMoE.ffn_/mx_moe_shared/GatedMLP.shared_", "moe"),
    ("M.m_/XingLayer.l0_/GatedMLP.ffn_/Dense.up_proj_", "ffn"),
    ("M.m_/XingLayer.l0_/HyperConnection.attn_hc_/mx_mhc_mix", "mhc"),
    ("M.m_/XingLayer.l0_/mx_mhc_mix", "mhc"),
    ("M.m_/XingLayer.l0_/RMSNorm.attn_norm_", "other"),
    ("M.m_/mx_head/Dense.decoder_", "head_loss"), ("mx_loss", "head_loss"),
    ("mx_update", "update"), ("M.m_/mx_embed", "embed"),
    ("M.m_/BERTModel.bert_/Embedding.word_embed_", "embed"),
    ("M.m_/NemotronHLayer.layer0_/Mamba2Mixer.mixer_/mx_ssd_scan", "ssm"),
    ("M.m_/ZayaLayer.layer0_/ZayaRouter.router_/mx_zaya_router", "moe"),
    ("M.m_/L.l_/MultiHeadAttention.attn_/Dropout.dropout0_", "attention"),
    ("", "unscoped")])
def test_a_scopes_kind_is_its_outermost_listed_elements(scope, kind):
    scope_mod = loader.load_module(os.path.join(
        loader.HERE, "metrics", "scope_ms_per_step.py"), "scope_reader_test")
    assert scope_mod.kind_of(scope, scope_mod.kinds()) == kind


def test_the_kinds_name_classes_and_scopes_the_program_has():
    scope_mod = loader.load_module(os.path.join(
        loader.HERE, "metrics", "scope_ms_per_step.py"), "scope_reader_test")
    kinds = scope_mod.kinds()
    assert set(kinds) == {"attention", "ssm", "moe", "ffn", "head_loss",
                          "update", "mhc", "embed"}
    source = ""
    pkg = os.path.join(loader.ROOT, "mxnet_tpu")
    for sub in ("models", "gluon/nn", "ops", "parallel"):
        for name in sorted(os.listdir(os.path.join(pkg, sub))):
            if name.endswith(".py"):
                with open(os.path.join(pkg, sub, name)) as f:
                    source += f.read()
    for row in kinds.values():
        for cls in row["classes"]:
            assert re.search(rf"^class {cls}\(", source, re.M), cls
        for scope in row["scopes"]:
            assert f'"{scope}"' in source, scope


def test_benchmark_json_lists_the_new_metrics_for_their_cells():
    rows = {m["name"]: m for m in BENCH["per_layer"]}
    assert list(rows)[-8:] == list(NEW)
    for name, cells in NEW.items():
        m = rows[name]
        assert m["workloads"] == cells and m["layer"] == "model blocks"
        assert (m["source"], m["moves"], m["better"]) == (
            "device_trace", "train_throughput", "lower")
        assert m["unit"] == ("%" if name.startswith("unscoped") else "ms")
        spec, _read_fn = loader.metric_reader(name)
        assert spec["reader"] in ("scope_ms_per_step", "scope_share_pct")


def test_scope_table_prints_the_table_the_readers_sum(mapped):
    from mxnet_tpu import telemetry

    tool = loader.load_module(os.path.join(
        loader.HERE, "tools", "scope_table.py"), "scope_table_test")
    tr = mapped["trace"]
    merged = {k: v for m in telemetry.scope_map().values()
              for k, v in m.items()}
    record = {"workload": "xing4_0_29b_a4b.train_1x4k", "seed": 1,
              "steps": 2, "busy_s": tr["busy_s"], "window_s": tr["window_s"],
              "map_cost": {"ask_s": 0.0},
              "events": tool.events_of(tr["op_seconds"], merged)}
    out = io.StringIO()
    tool.render(json.loads(json.dumps(record)), by_instance=True, ops=3,
                out=out)
    text = out.getvalue()
    assert "busy 40.00 ms a step, ENTRY events 40.00 (100.00% of busy)" in text
    assert "known to the map 98.750%" in text
    rows = {ln.split()[0]: [float(x.rstrip("%")) for x in ln.split()[1:]]
            for ln in text.split("\n\n")[0].splitlines()[2:]
            if not ln.startswith("mixed:")}
    assert len(rows) == 7
    assert rows["moe"] == [9.0, 3.0, 0.0, 12.0, 30.0]
    assert rows["attention"] == [10.0, 0.0, 0.0, 10.0, 25.0]
    assert rows["head_loss"] == [5.0, 0.0, 2.0, 7.0, 17.5]
    assert rows["other"] == [0.0, 0.0, 2.5, 2.5, 6.2]
    assert rows["unscoped"][3] == 1.0 and rows["unknown"][3] == 0.5
    assert re.search(r"mixed:\s+7\.00 ms of update holds a product of "
                     r"attention", text)
    assert "XingModel.xing0_/XingLayer.layer1_/XingMoE.ffn_" in text
    assert "%while.3 while" in text and "mx_moe_experts" in text
