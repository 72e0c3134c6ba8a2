"""Memory & compile observability (ISSUE 8, docs/OBSERVABILITY.md
§Memory): the memwatch sampler (on/off/no-op, category attribution,
sliding-window leak detector), per-executable compile events at every
jit construction site with restart-stable fingerprints, the
RESOURCE_EXHAUSTED post-mortem path (in-process + the launch.py
supervisor echo, no-jax and real-gang shapes), the tools/mem_report.py
CLI contract, and the observe-don't-perturb parity guarantee."""
import importlib.util
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, memwatch, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.context import normalize_memory_stats

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MEM_REPORT = os.path.join(_REPO, "tools", "mem_report.py")


@pytest.fixture
def tele():
    telemetry.reset()
    memwatch.reset()
    yield telemetry
    telemetry.reset()
    memwatch.reset()


def _events(tmp_path, rank=0):
    telemetry.flush()
    return [json.loads(line)
            for line in open(telemetry.event_path(str(tmp_path), rank))]


def _toy_step(lr=0.05):
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    mx.random.seed(0)
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    return DataParallelStep(net, gluon.loss.L2Loss(), mesh=local_mesh(),
                            optimizer="sgd",
                            optimizer_params={"learning_rate": lr})


def _run_steps(step, n, seed=0, dim=4):
    rng = np.random.RandomState(seed)
    losses = []
    for _ in range(n):
        x = nd.array(rng.rand(8, dim).astype(np.float32))
        y = nd.array(rng.rand(8, dim).astype(np.float32))
        losses.append(float(step.step(x, y)))
    step.drain()
    return losses


# ---------------------------------------------------------------------------
# sampler: on / off / no-op
# ---------------------------------------------------------------------------
def test_disabled_without_recorder(tele):
    assert not memwatch.enabled()
    assert memwatch.sample("test") is None
    memwatch.on_step(1)  # must not raise or record
    assert memwatch.summary()["samples"] == 0


def test_kill_switch(tele, tmp_path, monkeypatch):
    """MX_MEMWATCH=0 kills the WHOLE subsystem: no mem samples, no
    compile events (and no analysis retrace behind them), no OOM census
    — with the telemetry recorder itself still on."""
    monkeypatch.setenv("MX_MEMWATCH", "0")
    tele.enable(str(tmp_path))
    assert not memwatch.enabled()
    step = _toy_step()
    _run_steps(step, 2)
    assert memwatch.note_compile("X", ("parts",), 0.1) is None
    monkeypatch.setenv("MX_FAULT_SPEC", "oom:step=3")
    with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED"):
        _run_steps(step, 1)
    kinds = {e["kind"] for e in _events(tmp_path)}
    assert not kinds & {"mem", "compile", "oom_report"}, kinds
    assert kinds & {"step"}  # the recorder itself kept running
    assert memwatch.summary()["samples"] == 0


def test_sampler_emits_categorized_mem_events(tele, tmp_path, monkeypatch):
    monkeypatch.setenv("MX_MEMWATCH_EVERY", "1")
    tele.enable(str(tmp_path))
    step = _toy_step()
    _run_steps(step, 3)
    mems = [e for e in _events(tmp_path) if e["kind"] == "mem"]
    assert len(mems) == 3
    last = mems[-1]
    assert last["site"] == "step"
    cats = last["categories"]
    # the registered providers attributed the step's buffers
    assert cats["params"]["nbytes"] > 0
    assert cats["optimizer"]["nbytes"] > 0
    assert last["live_bytes"] >= cats["params"]["nbytes"]
    assert last["watermark_bytes"] >= last["live_bytes"] or \
        last["watermark_bytes"] >= mems[0]["live_bytes"]
    s = memwatch.summary()
    assert s["samples"] == 3 and s["watermark_bytes"] > 0


def test_category_attribution_exact(tele, tmp_path, monkeypatch):
    """Registered param arrays land in 'params', byte-exact; unclaimed
    arrays fall into 'other'."""
    tele.enable(str(tmp_path))
    step = _toy_step()
    _run_steps(step, 1)
    c = memwatch.census()
    want = sum(int(a.nbytes) for a in step.params.values())
    assert c["categories"]["params"]["nbytes"] == want
    assert c["categories"]["params"]["count"] == len(step.params)
    assert "other" in c["categories"]  # RNG key etc. are unclaimed


def test_sampling_cadence(tele, tmp_path, monkeypatch):
    monkeypatch.setenv("MX_MEMWATCH_EVERY", "3")
    tele.enable(str(tmp_path))
    step = _toy_step()
    _run_steps(step, 6)
    mems = [e for e in _events(tmp_path) if e["kind"] == "mem"]
    # DataParallelStep.step + AsyncCheckpointer-free loop: exactly one
    # on_step observation per step -> samples at steps 3 and 6
    assert len(mems) == 2


def test_checkpoint_boundary_always_samples(tele, tmp_path, monkeypatch):
    from mxnet_tpu.checkpoint import AsyncCheckpointer

    monkeypatch.setenv("MX_MEMWATCH_EVERY", "1000")  # step cadence: never
    tele.enable(str(tmp_path / "t"))
    net = gluon.nn.Dense(1)
    net.initialize(mx.init.Normal(0.5))
    net(nd.array(np.ones((2, 4), np.float32)))  # resolve deferred init
    ckpt = AsyncCheckpointer(str(tmp_path / "ckpt"), save_every=2)
    ckpt.step(net)
    ckpt.step(net)  # enqueues a save
    ckpt.close()
    mems = [e for e in _events(tmp_path / "t") if e["kind"] == "mem"]
    assert any(e["site"] == "checkpoint_save" for e in mems)


# ---------------------------------------------------------------------------
# leak detector
# ---------------------------------------------------------------------------
class _Bucket:
    def __init__(self):
        self.arrs = []


def test_leak_detector_fires_and_names_category(tele, tmp_path,
                                                monkeypatch, caplog):
    import gc

    import jax.numpy as jnp

    gc.collect()  # stale arrays from earlier tests must not free mid-run
    monkeypatch.setenv("MX_MEMWATCH_LEAK_WINDOW", "4")
    tele.enable(str(tmp_path))
    bucket = _Bucket()
    memwatch.register("inflight", bucket, lambda b: b.arrs)
    for _i in range(6):
        bucket.arrs.append(jnp.ones((64 * 1024,), jnp.float32))  # 256KB
        with caplog.at_level("WARNING", logger="mxnet_tpu.memwatch"):
            memwatch.sample("test")
    leaks = [e for e in _events(tmp_path) if e["kind"] == "mem_leak"]
    assert len(leaks) == 1  # rate-limited: one warning while growing
    assert leaks[0]["category"] == "inflight"
    assert leaks[0]["growth_bytes"] > 3 * 256 * 1024 - 1
    assert any("top-growing category: inflight" in r.message
               for r in caplog.records)
    s = memwatch.summary()
    assert s["leak"]["active"] and s["leak"]["category"] == "inflight"
    # growth stops -> detector re-arms (active flag drops)
    for _i in range(4):
        memwatch.sample("test")
    assert not memwatch.summary()["leak"]["active"]


def test_leak_detector_silent_on_steady_state(tele, tmp_path, monkeypatch):
    import gc

    import jax.numpy as jnp

    gc.collect()
    monkeypatch.setenv("MX_MEMWATCH_LEAK_WINDOW", "4")
    tele.enable(str(tmp_path))
    bucket = _Bucket()
    bucket.arrs.append(jnp.ones((64 * 1024,), jnp.float32))
    memwatch.register("inflight", bucket, lambda b: b.arrs)
    for _i in range(8):  # steady: same arrays every sample
        memwatch.sample("test")
    assert not [e for e in _events(tmp_path) if e["kind"] == "mem_leak"]
    assert not memwatch.summary()["leak"]["active"]


# ---------------------------------------------------------------------------
# compile events: one per cache entry at every jit site
# ---------------------------------------------------------------------------
def _compiles(tmp_path, site=None):
    evs = [e for e in _events(tmp_path) if e["kind"] == "compile"]
    return [e for e in evs if site is None or e["site"] == site]


def test_data_parallel_compile_event_once(tele, tmp_path):
    tele.enable(str(tmp_path))
    step = _toy_step()
    _run_steps(step, 3)
    comps = _compiles(tmp_path, "data_parallel")
    assert len(comps) == 1, comps
    ev = comps[0]
    assert ev["executor"] == step._tele_name
    assert len(ev["fingerprint"]) == 16
    int(ev["fingerprint"], 16)  # hex
    assert ev["wall_ms"] > 0
    # cost analysis captured on this jax (soft: presence asserted because
    # this environment exposes it; fields are best-effort by contract)
    assert ev.get("arg_bytes", 0) > 0
    _run_steps(step, 2)  # steady state: NO re-emission
    assert len(_compiles(tmp_path, "data_parallel")) == 1


def test_fused_updater_compile_event_once(tele, tmp_path):
    from mxnet_tpu import optimizer as opt_mod
    from mxnet_tpu.optimizer.fused import FusedUpdater

    tele.enable(str(tmp_path))
    opt = opt_mod.SGD(learning_rate=0.1, momentum=0.9)
    upd = FusedUpdater(opt)
    w = nd.array(np.ones((8,), np.float32))
    g = nd.array(np.ones((8,), np.float32))
    upd.apply([(0, g, w)])
    upd.apply([(0, g, w)])
    comps = _compiles(tmp_path, "fused")
    assert len(comps) == 1, comps
    assert comps[0]["executor"] == "FusedUpdater:SGD"
    assert comps[0]["n_params"] == 1


def test_kvstore_psum_compile_event_once(tele, tmp_path):
    from mxnet_tpu import kvstore

    tele.enable(str(tmp_path))
    kv = kvstore.create("device")
    kv.init(3, nd.zeros((16,)))
    for _ in range(2):
        vals = [nd.array(np.ones((16,), np.float32), ctx=mx.cpu(i))
                for i in range(2)]
        kv.push(3, vals)
    comps = _compiles(tmp_path, "kvstore")
    assert len(comps) == 1, comps
    assert comps[0]["executor"] == "KVStore.device_allreduce"
    assert comps[0]["ndev"] == 2


def test_cached_op_compile_event_per_signature(tele, tmp_path):
    tele.enable(str(tmp_path))
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    net(nd.array(np.ones((2, 8), np.float32)))
    net(nd.array(np.ones((2, 8), np.float32)))  # cached: no re-emission
    assert len(_compiles(tmp_path, "cached_op")) == 1
    # a new input signature is a new executable -> second compile event
    net(nd.array(np.ones((5, 8), np.float32)))
    comps = _compiles(tmp_path, "cached_op")
    assert len(comps) == 2
    assert comps[0]["fingerprint"] != comps[1]["fingerprint"]


_FP_SCRIPT = r"""
import json, os, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry
from mxnet_tpu.parallel import DataParallelStep, local_mesh
d = tempfile.mkdtemp()
telemetry.enable(d)
mx.random.seed(0)
net = gluon.nn.Dense(4)
net.initialize(mx.init.Xavier())
step = DataParallelStep(net, gluon.loss.L2Loss(), mesh=local_mesh(),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.05})
x = nd.array(np.ones((8, 4), np.float32))
y = nd.array(np.ones((8, 4), np.float32))
float(step.step(x, y))
step.drain(); telemetry.flush()
evs = [json.loads(l) for l in open(telemetry.event_path(d, 0))]
print([e["fingerprint"] for e in evs if e["kind"] == "compile"][0])
"""


def test_fingerprint_stable_across_process_restart():
    """Acceptance: the same program in two separate processes maps to the
    SAME fingerprint — structural identity only, no object ids.  The two
    restarts run concurrently: the test pays one jax-import wall, not two
    (tier-1 budget)."""
    env = dict(os.environ)
    env.pop("MX_TELEMETRY_DIR", None)
    procs = [subprocess.Popen([sys.executable, "-c", _FP_SCRIPT],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True,
                              env=env, cwd=_REPO) for _ in range(2)]
    fps = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, (out, err)
        fps.append(out.strip().splitlines()[-1])
    assert fps[0] == fps[1] and len(fps[0]) == 16


# ---------------------------------------------------------------------------
# OOM post-mortem
# ---------------------------------------------------------------------------
def test_oom_injection_emits_report_and_reraises(tele, tmp_path,
                                                 monkeypatch):
    monkeypatch.setenv("MX_FAULT_SPEC", "oom:step=2")
    tele.enable(str(tmp_path))
    step = _toy_step()
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(8, 4).astype(np.float32))
    y = nd.array(rng.rand(8, 4).astype(np.float32))
    float(step.step(x, y))  # step 1: clean
    with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED"):
        step.step(x, y)  # step 2: injected OOM at dispatch
    evs = _events(tmp_path)
    ooms = [e for e in evs if e["kind"] == "oom_report"]
    assert len(ooms) == 1
    ev = ooms[0]
    assert ev["step"] == 2
    assert ev["executor"] == step._tele_name
    assert ev["largest_category"] in ev["categories"]
    assert ev["inflight_depth"] >= 0
    assert ev["watermark_bytes"] > 0
    # top-executables ranking drawn from the compile registry
    assert any(t["executor"] == step._tele_name
               for t in ev["top_executables"])


def test_oom_report_emitted_once(tele, tmp_path, monkeypatch):
    monkeypatch.setenv("MX_FAULT_SPEC", "oom:step=1; oom:step=2")
    tele.enable(str(tmp_path))
    step = _toy_step()
    x = nd.array(np.ones((8, 4), np.float32))
    y = nd.array(np.ones((8, 4), np.float32))
    for _ in range(2):
        with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED"):
            step.step(x, y)
    assert len([e for e in _events(tmp_path)
                if e["kind"] == "oom_report"]) == 1


def _load_launch():
    spec = importlib.util.spec_from_file_location(
        "launch_for_memwatch_test", os.path.join(_REPO, "tools",
                                                 "launch.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_supervisor_echoes_oom_post_mortem_no_jax(tmp_path, capsys):
    """The launch.py death diagnosis echoes a rank's oom_report (largest
    category, watermark, inflight depth) next to the flight tail —
    covered here with a synthetic stream so the supervisor's reader needs
    no jax."""
    launch = _load_launch()
    lines = [
        {"t": 1.0, "kind": "step", "rank": 0, "step": 3, "wall_ms": 5.0},
        {"t": 1.1, "kind": "oom_report", "rank": 0, "executor": "X",
         "step": 3, "watermark_bytes": 512 * 1024 * 1024,
         "live_bytes": 200 * 1024 * 1024,
         "categories": {"params": 120 * 1024 * 1024,
                        "other": 80 * 1024 * 1024},
         "largest_category": "params", "inflight_depth": 2,
         "top_executables": [{"executor": "DataParallelStep:Dense#1",
                              "fingerprint": "ab12cd34ef56ab12",
                              "temp_bytes": 64 * 1024 * 1024}]},
    ]
    with open(tmp_path / "rank-0.jsonl", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    monitor = launch._HeartbeatMonitor(
        1, {"MX_TELEMETRY_DIR": str(tmp_path)})
    monitor.diagnose()
    err = capsys.readouterr().err
    assert "rank 0 OOM post-mortem (step 3)" in err
    assert "largest live-array category params" in err
    assert "watermark 536.9MB" in err
    assert "inflight depth 2" in err
    assert "DataParallelStep:Dense#1[ab12cd34ef56ab12]" in err


@pytest.mark.dist
@pytest.mark.slow
@pytest.mark.chaos
def test_gang_oom_post_mortem_in_supervisor_diagnosis(tmp_path):
    """Acceptance: injected oom:step=N in a 2-rank gang yields an
    oom_report in the supervisor's death diagnosis naming the largest
    live-array category."""
    tdir = tmp_path / "telemetry"
    env = dict(os.environ, MX_TELEMETRY_DIR=str(tdir),
               MX_TELEMETRY_FLUSH_SEC="0.2", MX_HEARTBEAT_SEC="0.5",
               MX_MEMWATCH_EVERY="1",
               MX_FAULT_SPEC="oom:step=3:rank=1")
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", "2", "--force-cpu", "--",
           sys.executable,
           os.path.join(_REPO, "tests", "dist", "oom_worker.py")]
    res = subprocess.run(cmd, cwd=_REPO, timeout=240, capture_output=True,
                         text=True, env=env)
    assert res.returncode != 0  # the injected rank died
    # the worker's own traceback names the synthetic OOM
    assert "RESOURCE_EXHAUSTED" in res.stderr
    # supervisor echo: the post-mortem with the largest category named
    assert "rank 1 OOM post-mortem (step 3)" in res.stderr, \
        res.stderr[-3000:]
    assert "largest live-array category" in res.stderr
    # and the stream itself carries the machine-readable report
    events = [json.loads(line) for line in open(tdir / "rank-1.jsonl")]
    ooms = [e for e in events if e["kind"] == "oom_report"]
    assert len(ooms) == 1 and ooms[0]["step"] == 3
    assert ooms[0]["largest_category"] in ooms[0]["categories"]
    # the healthy rank recorded mem samples (watchdog at every-step)
    mems = [json.loads(line) for line in open(tdir / "rank-0.jsonl")
            if '"mem"' in line]
    assert any(e.get("kind") == "mem" for e in mems)
    # mem_report flags the OOM from the same streams
    rep = subprocess.run(
        [sys.executable, _MEM_REPORT, str(tdir), "--json"],
        capture_output=True, text=True, timeout=60)
    assert rep.returncode == 3
    obj = json.loads(rep.stdout)
    assert any(a.startswith("oom: rank 1") for a in obj["anomalies"])


# ---------------------------------------------------------------------------
# tools/mem_report.py CLI
# ---------------------------------------------------------------------------
def _write_mem_stream(directory, rank, totals, leak_events=0,
                      compile_events=(), oom=False):
    lines = []
    t = 1000.0
    for i, total in enumerate(totals):
        lines.append({
            "t": t + i, "kind": "mem", "rank": rank, "site": "step",
            "step": i + 1, "live_bytes": total, "live_count": 4,
            "watermark_bytes": max(totals[:i + 1]),
            "categories": {"params": {"count": 2, "nbytes": total // 2},
                           "other": {"count": 2,
                                     "nbytes": total - total // 2}}})
    for _ in range(leak_events):
        lines.append({"t": t + 99, "kind": "mem_leak", "rank": rank,
                      "category": "other", "growth_bytes": 1 << 20,
                      "window": 4, "total_bytes": totals[-1]})
    for c in compile_events:
        lines.append(dict({"t": t, "kind": "compile", "rank": rank}, **c))
    if oom:
        lines.append({"t": t + 100, "kind": "oom_report", "rank": rank,
                      "step": 7, "largest_category": "params",
                      "categories": {"params": 100}, "watermark_bytes": 200,
                      "live_bytes": 150, "inflight_depth": 1})
    with open(os.path.join(str(directory), f"rank-{rank}.jsonl"), "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


def _report(directory, *args):
    return subprocess.run(
        [sys.executable, _MEM_REPORT, str(directory), *args],
        capture_output=True, text=True, timeout=60)


def test_mem_report_clean_run_exits_zero(tmp_path):
    _write_mem_stream(tmp_path, 0, [1000] * 8, compile_events=[
        {"executor": "DataParallelStep:Dense#1",
         "fingerprint": "ab12cd34ef56ab12", "site": "data_parallel",
         "wall_ms": 900.0, "flops": 924.0, "arg_bytes": 428,
         "out_bytes": 164}])
    _write_mem_stream(tmp_path, 1, [990] * 8)
    res = _report(tmp_path, "--window", "4")
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "no anomalies detected" in res.stdout
    assert "executable cost table" in res.stdout
    assert "ab12cd34ef56ab12" in res.stdout


def test_mem_report_exits_three_on_seeded_leak(tmp_path):
    # strictly monotonic growth above the 64KB floor across the window
    _write_mem_stream(tmp_path, 0,
                      [1 << 20, 2 << 20, 3 << 20, 4 << 20, 5 << 20])
    res = _report(tmp_path, "--window", "4", "--json")
    assert res.returncode == 3, (res.stdout, res.stderr)
    rep = json.loads(res.stdout)
    assert rep["per_rank"]["0"]["leak"]["verdict"] == "leak"
    assert rep["per_rank"]["0"]["leak"]["category"] in ("params", "other")
    assert any(a.startswith("leak: rank 0") for a in rep["anomalies"])
    # human rendering names the verdict too
    txt = _report(tmp_path, "--window", "4")
    assert txt.returncode == 3
    assert "ANOMALIES" in txt.stdout and "leak" in txt.stdout


def test_mem_report_recorded_leak_event_counts(tmp_path):
    # flat trailing window, but the run recorded a mem_leak live (the
    # leak crashed/flattened before the end): still a leak verdict
    _write_mem_stream(tmp_path, 0, [1000] * 6, leak_events=1)
    res = _report(tmp_path, "--window", "4", "--json")
    assert res.returncode == 3
    rep = json.loads(res.stdout)
    assert rep["per_rank"]["0"]["leak"]["verdict"] == "leak"
    assert rep["per_rank"]["0"]["recorded_leak_events"] == 1


def test_mem_report_json_schema_and_watermarks(tmp_path):
    _write_mem_stream(tmp_path, 0, [500, 900, 700], oom=True)
    res = _report(tmp_path, "--json")
    rep = json.loads(res.stdout)
    assert rep["num_ranks"] == 1
    r0 = rep["per_rank"]["0"]
    assert r0["samples"] == 3
    assert r0["watermark_bytes"] == 900
    assert r0["categories_last"]["params"] == 350
    assert r0["peak_category_bytes"]["params"] == 450
    assert rep["ooms"][0]["largest_category"] == "params"
    assert res.returncode == 3  # the OOM is an anomaly


def test_mem_report_empty_dir_exits_two(tmp_path):
    res = _report(tmp_path)
    assert res.returncode == 2


# ---------------------------------------------------------------------------
# satellites: normalized memory_stats + profiler plumb
# ---------------------------------------------------------------------------
def test_context_memory_stats_normalized_cpu_fallback():
    stats = mx.cpu(0).memory_stats()
    assert set(stats) == {"bytes_in_use", "peak_bytes_in_use",
                          "bytes_limit", "available"}
    assert stats["available"] is False  # XLA:CPU: no allocator stats
    assert normalize_memory_stats(None)["available"] is False
    norm = normalize_memory_stats({"bytes_in_use": 5, "bytes_limit": 10})
    assert norm == {"bytes_in_use": 5, "peak_bytes_in_use": 5,
                    "bytes_limit": 10, "available": True}
    # util.get_gpu_memory reads an accelerator; this process has none, and
    # an accelerator context never stands in for the host
    with pytest.raises(mx.MXNetError, match="no accelerator"):
        mx.util.get_gpu_memory()


def test_profiler_memory_plumb(tele):
    """Satellite: record_op's memory field is no longer dead —
    profile_memory plumbs memwatch.peak_bytes() through timed_call and
    dumps() surfaces it."""
    from mxnet_tpu import profiler

    import jax.numpy as jnp

    profiler.reset_stats()
    profiler.set_config(profile_memory=True)
    try:
        keep = profiler.timed_call("AllocOp",
                                   lambda: jnp.ones((1024,), jnp.float32))
        rows = json.loads(profiler.dumps(format="json"))
        assert rows[0]["name"] == "AllocOp"
        assert rows[0]["peak_mem_bytes"] > 0
        table = profiler.dumps()
        assert "Peak(MB)" in table
        del keep
    finally:
        profiler.set_config(profile_memory=False)
        profiler.reset_stats()
    # without the flag the column stays absent (back-compat)
    profiler.record_op("X", 0.001)
    assert "Peak(MB)" not in profiler.dumps()
    assert "peak_mem_bytes" not in json.loads(
        profiler.dumps(format="json"))[0]
    profiler.reset_stats()


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_prometheus_gains_mem_gauges(tele, tmp_path, monkeypatch):
    monkeypatch.setenv("MX_MEMWATCH_EVERY", "1")
    tele.enable(str(tmp_path))
    step = _toy_step()
    _run_steps(step, 2)
    path = telemetry.export_prometheus(str(tmp_path / "m.prom"))
    text = open(path).read()
    assert "mx_mem_samples_total" in text
    assert "mx_mem_watermark_bytes" in text
    assert 'mx_mem_category_bytes{rank="0",category="params"}' in text
    assert "mx_mem_compile_total" in text
    assert text.rstrip().endswith("# EOF")


def test_compile_accounting_counts_compiles_and_nothing_else(tele, tmp_path):
    """No second cache stands behind the jit sites since PR 29: the
    compile rollup and its gauges carry a count and a wall, and a compile
    event says nothing of where its executable came from."""
    tele.enable(str(tmp_path))
    _run_steps(_toy_step(), 2)
    assert set(memwatch.summary()["compiles"]) == {"count", "wall_ms"}
    text = open(telemetry.export_prometheus(str(tmp_path / "m.prom"))).read()
    assert sorted(set(re.findall(r"mx_mem_compile_\w+", text))) == [
        "mx_mem_compile_ms_total", "mx_mem_compile_total"]
    compiles = [e for e in telemetry.flight_tail(256)
                if e["kind"] == "compile"]
    assert compiles and not any(
        k.startswith(("cache_", "deserialize")) for e in compiles for k in e)


def test_chrome_trace_gains_memory_counter_track(tele, tmp_path,
                                                 monkeypatch):
    monkeypatch.setenv("MX_MEMWATCH_EVERY", "1")
    tele.enable(str(tmp_path))
    step = _toy_step()
    _run_steps(step, 2)
    out = telemetry.export_chrome_trace(str(tmp_path))
    evs = json.load(open(out))["traceEvents"]
    counters = [e for e in evs if e["ph"] == "C" and e["name"] == "memory"]
    assert counters, "mem events must render as ph-C counter tracks"
    assert "params" in counters[-1]["args"]


# ---------------------------------------------------------------------------
# observe, don't perturb
# ---------------------------------------------------------------------------
def _train_losses_and_weights(tmp_path, tag):
    telemetry.reset()
    memwatch.reset()
    telemetry.enable(str(tmp_path / tag))
    step = _toy_step()
    losses = _run_steps(step, 5)
    step.sync_to_block()
    weights = [p.data().asnumpy().copy()
               for p in step.block.collect_params().values()]
    return losses, weights


def test_memwatch_does_not_perturb_training(tele, tmp_path, monkeypatch):
    """Acceptance: losses/weights bitwise unchanged with memwatch
    sampling every step vs MX_MEMWATCH=0."""
    monkeypatch.setenv("MX_MEMWATCH", "1")
    monkeypatch.setenv("MX_MEMWATCH_EVERY", "1")
    on_losses, on_weights = _train_losses_and_weights(tmp_path, "on")
    assert memwatch.summary()["samples"] >= 5
    monkeypatch.setenv("MX_MEMWATCH", "0")
    off_losses, off_weights = _train_losses_and_weights(tmp_path, "off")
    assert memwatch.summary()["samples"] == 0
    assert on_losses == off_losses
    for a, b in zip(on_weights, off_weights):
        assert np.array_equal(a, b)
