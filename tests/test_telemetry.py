"""Runtime telemetry (docs/OBSERVABILITY.md): recorder no-op guarantee,
JSONL sink + flight recorder, retrace detection, step/checkpoint events,
heartbeats, the launch.py supervisor's stale-rank diagnosis, and the
[rank N] log prefixes."""
import json
import logging
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd, telemetry

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tele():
    """Fresh recorder state per test; leaves the recorder disabled after."""
    telemetry.reset()
    yield telemetry
    telemetry.reset()


# ---------------------------------------------------------------------------
# recorder core
# ---------------------------------------------------------------------------
def test_recorder_noops_without_sink(tele):
    assert not tele.enabled()
    tele.record("step", executor="x", step=1)  # must not raise or buffer
    tele.record_step("x", step=1, wall_s=0.1, samples=8)
    tele.heartbeat(1)
    s = tele.summary()
    assert s["enabled"] is False
    assert s["events"] == {}
    assert tele.flight_tail() == []


def test_jsonl_sink_ring_and_summary(tele, tmp_path):
    tele.enable(str(tmp_path))
    assert tele.enabled()
    tele.record_step("ExecA", step=1, wall_s=0.5, samples=0, traced=True)
    tele.record_step("ExecA", step=2, wall_s=0.1, samples=16)
    tele.record_collective("device_allreduce", nbytes=1024, wall_s=0.002)
    tele.record_checkpoint("save", step=2, wall_s=0.05, nbytes=4096)
    tele.flush()
    path = tele.event_path(str(tmp_path), tele.rank())
    events = [json.loads(line) for line in open(path)]
    kinds = [e["kind"] for e in events]
    assert kinds[0] == "start"
    assert kinds.count("step") == 2 and "collective" in kinds
    assert "checkpoint_save" in kinds
    for e in events:  # schema: every event carries t/kind/rank
        assert {"t", "kind", "rank"} <= set(e)
    s = tele.summary()
    assert s["steps"]["ExecA"]["count"] == 2
    assert s["steps"]["ExecA"]["compile_count"] == 1
    assert s["steps"]["ExecA"]["compile_ms"] == pytest.approx(500, rel=0.01)
    assert s["steps"]["ExecA"]["samples_per_sec"] == pytest.approx(160, rel=0.01)
    assert s["collectives"] == {"count": 1, "bytes": 1024,
                                "total_ms": pytest.approx(2, rel=0.01),
                                "compile_ms": 0.0}
    assert s["checkpoints"]["saves"] == 1
    # flight recorder: newest last, bounded
    tail = tele.flight_tail(3)
    assert [e["kind"] for e in tail] == ["step", "collective",
                                        "checkpoint_save"]
    json.dumps(s)  # summary must stay JSON-serializable


def test_heartbeat_atomic_and_rate_limited(tele, tmp_path, monkeypatch):
    monkeypatch.setenv("MX_HEARTBEAT_SEC", "9999")  # rate limit ~forever
    tele.enable(str(tmp_path))
    tele.heartbeat(5)
    path = tele.heartbeat_path(str(tmp_path), tele.rank())
    first = json.load(open(path))
    assert first["step"] == 5 and first["pid"] == os.getpid()
    tele.heartbeat(6)  # rate-limited: no write
    assert json.load(open(path))["step"] == 5
    tele.heartbeat(7, force=True)
    assert json.load(open(path))["step"] == 7
    # no torn tmp files left behind
    assert not [f for f in os.listdir(tmp_path) if ".tmp-" in f]


# ---------------------------------------------------------------------------
# retrace detection
# ---------------------------------------------------------------------------
def test_retrace_warning_fires_and_rate_limits(tele, monkeypatch, caplog):
    monkeypatch.setenv("MX_TELEMETRY_RETRACE_LIMIT", "3")
    caplog.set_level(logging.WARNING, logger="mxnet_tpu.telemetry")
    for i in range(4):
        assert tele.note_signature("ExecB", ("shape", i)) is True
    warns = [r for r in caplog.records if "ExecB" in r.getMessage()]
    assert len(warns) == 1, [r.getMessage() for r in warns]
    assert "4 distinct signatures" in warns[0].getMessage()
    assert "('shape', 3)" in warns[0].getMessage()  # names the offender
    # rate-limited: the next warning only once the count doubles
    for i in range(4, 8):
        tele.note_signature("ExecB", ("shape", i))
    warns = [r for r in caplog.records if "ExecB" in r.getMessage()]
    assert len(warns) == 2, [r.getMessage() for r in warns]
    assert tele.summary()["retraces"]["ExecB"]["traces"] == 8


def test_collective_compile_split(tele, tmp_path):
    tele.enable(str(tmp_path))
    tele.record_collective("device_allreduce", nbytes=64, wall_s=0.5,
                           traced=True)   # first use: jit trace + compile
    tele.record_collective("device_allreduce", nbytes=64, wall_s=0.001)
    c = tele.summary()["collectives"]
    assert c["count"] == 2
    assert c["compile_ms"] == pytest.approx(500, rel=0.01)
    assert c["total_ms"] == pytest.approx(1, rel=0.01)


def test_retrace_limit_zero_disables_detection(tele, monkeypatch, caplog):
    monkeypatch.setenv("MX_TELEMETRY_RETRACE_LIMIT", "0")
    caplog.set_level(logging.WARNING, logger="mxnet_tpu.telemetry")
    assert not tele.retrace_enabled()
    for i in range(20):
        assert tele.note_signature("ExecZ", ("shape", i)) is False
    assert not caplog.records
    assert "ExecZ" not in tele.summary()["retraces"]


def test_stable_signatures_never_warn(tele, monkeypatch, caplog):
    monkeypatch.setenv("MX_TELEMETRY_RETRACE_LIMIT", "3")
    caplog.set_level(logging.WARNING, logger="mxnet_tpu.telemetry")
    assert tele.note_signature("ExecC", ("stable",)) is True
    for _ in range(50):
        assert tele.note_signature("ExecC", ("stable",)) is False
    assert not [r for r in caplog.records if "ExecC" in r.getMessage()]


def test_cached_op_shape_churn_warns(tele, monkeypatch, caplog):
    """The integration path: a hybridized block fed a new batch shape every
    call recompiles every call — the warning must fire; a stable-shape loop
    must stay silent."""
    monkeypatch.setenv("MX_TELEMETRY_RETRACE_LIMIT", "4")
    caplog.set_level(logging.WARNING, logger="mxnet_tpu.telemetry")
    net = gluon.nn.Dense(2)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    for b in range(1, 7):  # 6 distinct batch shapes > limit of 4
        net(nd.array(np.random.rand(b, 3).astype(np.float32)))
    warns = [r for r in caplog.records if "CachedOp:Dense" in r.getMessage()]
    assert warns, "shape churn through a CachedOp did not warn"
    assert "recompile" in warns[0].getMessage()

    caplog.clear()
    stable = gluon.nn.Dense(2)
    stable.initialize(mx.init.Xavier())
    stable.hybridize()
    x = nd.array(np.random.rand(4, 3).astype(np.float32))
    for _ in range(20):
        stable(x)
    assert not [r for r in caplog.records
                if "CachedOp:Dense" in r.getMessage()]


def test_many_same_class_blocks_do_not_false_storm(tele, monkeypatch, caplog):
    """Retrace tracking is per CachedOp instance: a model holding many
    same-class blocks of different widths (one stable signature each) must
    not pool into a phantom retrace storm."""
    monkeypatch.setenv("MX_TELEMETRY_RETRACE_LIMIT", "3")
    caplog.set_level(logging.WARNING, logger="mxnet_tpu.telemetry")
    x = nd.array(np.random.rand(2, 3).astype(np.float32))
    for width in range(1, 7):  # 6 instances > limit of 3
        b = gluon.nn.Dense(width)
        b.initialize(mx.init.Xavier())
        b.hybridize()
        b(x)
    assert not [r for r in caplog.records if "CachedOp" in r.getMessage()]


# ---------------------------------------------------------------------------
# executor step events
# ---------------------------------------------------------------------------
def test_data_parallel_step_events_and_heartbeat(tele, tmp_path, monkeypatch):
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    monkeypatch.setenv("MX_HEARTBEAT_SEC", "0")
    tele.enable(str(tmp_path))
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    step = DataParallelStep(net, gluon.loss.L2Loss(), mesh=local_mesh(),
                            optimizer="sgd")
    x = nd.array(np.random.rand(8, 4).astype(np.float32))
    y = nd.array(np.random.rand(8, 4).astype(np.float32))
    for _ in range(3):
        step.step(x, y)
    tele.flush()
    events = [json.loads(line)
              for line in open(tele.event_path(str(tmp_path), 0))]
    steps = [e for e in events if e["kind"] == "step"]
    assert [e["step"] for e in steps] == [1, 2, 3]
    assert steps[0]["traced"] is True  # first call = trace + compile
    assert steps[1]["traced"] is False and steps[2]["traced"] is False
    assert all(e["samples"] == 8 for e in steps)
    assert all(e["transfer_bytes"] > 0 for e in steps)
    step_keys = [k for k in tele.summary()["steps"]
                 if k.startswith("DataParallelStep:Dense#")]
    assert len(step_keys) == 1, tele.summary()["steps"]
    ex = tele.summary()["steps"][step_keys[0]]
    assert ex["compile_count"] == 1 and ex["count"] == 3
    # compile (trace+build XLA program) dominates a steady-state tiny step
    assert ex["compile_ms"] > ex["mean_exec_ms"]
    hb = json.load(open(tele.heartbeat_path(str(tmp_path), 0)))
    assert hb["step"] == 3


def test_checkpoint_events(tele, tmp_path, monkeypatch):
    from mxnet_tpu import checkpoint

    monkeypatch.setenv("MX_HEARTBEAT_SEC", "0")
    tele.enable(str(tmp_path / "t"))
    net = gluon.nn.Dense(1)
    net.initialize(mx.init.Normal(0.5))
    net(nd.array(np.random.rand(2, 3).astype(np.float32)))
    ckdir = str(tmp_path / "ck")
    ckpt = checkpoint.AsyncCheckpointer(ckdir, save_every=2, keep=2)
    for _ in range(4):
        ckpt.step(net)
    ckpt.close()
    assert checkpoint.restore(ckdir, net) == 4
    tele.flush()
    events = [json.loads(line)
              for line in open(tele.event_path(str(tmp_path / "t"), 0))]
    saves = [e for e in events if e["kind"] == "checkpoint_save"]
    assert [e["step"] for e in saves] == [2, 4]
    assert all(e["nbytes"] > 0 and e["wall_ms"] > 0 for e in saves)
    loads = [e for e in events if e["kind"] == "checkpoint_load"]
    assert loads and loads[-1]["step"] == 4
    s = tele.summary()["checkpoints"]
    assert s["saves"] == 2 and s["loads"] == 1
    # heartbeats advanced with the step counter
    hb = json.load(open(tele.heartbeat_path(str(tmp_path / "t"), 0)))
    assert hb["step"] == 4


# ---------------------------------------------------------------------------
# satellites: Speedometer clock, profiler segments
# ---------------------------------------------------------------------------
def test_speedometer_survives_wallclock_jump(monkeypatch, caplog):
    """Speed math must use the monotonic perf counter: a backwards
    wall-clock step (NTP) used to yield negative samples/sec."""
    from mxnet_tpu import callback

    walltimes = [1000.0, 500.0, 100.0]  # time.time() jumping BACKWARDS
    monkeypatch.setattr(callback.time, "time",
                        lambda: walltimes.pop(0) if walltimes else 100.0)
    caplog.set_level(logging.INFO)
    sm = callback.Speedometer(batch_size=4, frequent=1)

    class Param:
        epoch, eval_metric = 0, None

    p = Param()
    p.nbatch = 0
    sm(p)
    time.sleep(0.01)
    p.nbatch = 1
    sm(p)
    msgs = [r.getMessage() for r in caplog.records
            if "samples/sec" in r.getMessage()]
    assert msgs, caplog.records
    speed = float(re.search(r"Speed: (-?[\d.]+)", msgs[-1]).group(1))
    assert speed > 0, msgs[-1]


def test_profiler_resume_writes_fresh_segments(tmp_path, monkeypatch):
    """resume() must not clobber the prior trace: every start()/resume()
    opens a fresh numbered segment dir, and dump() lists them all.  The
    jax profiler itself is stubbed (real capture costs ~7s per segment and
    test_profiler.py already exercises it through the same start/stop
    path); this pins OUR segment bookkeeping."""
    import jax

    from mxnet_tpu import profiler

    started = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: (started.append(d), os.makedirs(d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    profiler.set_config(filename=str(tmp_path / "prof.json"))
    before = len(profiler.dump())
    profiler.start()
    profiler.pause()
    profiler.resume()
    profiler.stop()
    segments = profiler.dump()
    new = segments[before:]
    assert len(new) == 2, segments
    assert new[0] != new[1] and started == new
    assert [os.path.basename(s) for s in new] == \
        [f"segment-{before:03d}", f"segment-{before + 1:03d}"]
    for seg in new:
        assert os.path.isdir(seg), f"trace segment {seg} not created"
    assert all(s.startswith(str(tmp_path)) for s in new)


def test_dumps_includes_telemetry_rollup(tele):
    from mxnet_tpu import profiler

    tele.note_signature("ExecD", ("a",))
    out = profiler.dumps()
    assert "Telemetry rollup:" in out
    assert "ExecD" in out


# ---------------------------------------------------------------------------
# launch.py supervisor (no-jax workers: fast)
# ---------------------------------------------------------------------------
def _launch(n, worker, env=None, timeout=90, args=()):
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", str(n), *args, "--", sys.executable, str(worker)]
    return subprocess.run(cmd, timeout=timeout, capture_output=True,
                          text=True, env=env)


def test_supervisor_stale_heartbeat_diagnosis_and_flight_tail(tmp_path):
    """One supervised gang covers three supervisor features: worker
    stdout/stderr lines arrive `[rank N]`-prefixed; a rank whose heartbeat
    stops advancing is called out while the gang is still alive; and after
    the gang dies the supervisor echoes each rank's flight-recorder tail.
    Workers write the telemetry files directly (same schema as
    mxnet_tpu.telemetry) so this covers the supervisor's reader without
    paying jax imports."""
    tdir = tmp_path / "telemetry"
    tdir.mkdir()
    worker = tmp_path / "w.py"
    worker.write_text(
        "import json, os, sys, time\n"
        "rank = os.environ['MX_PROC_ID']\n"
        "td = os.environ['MX_TELEMETRY_DIR']\n"
        "print('hello from worker')\n"
        "print('oops line', file=sys.stderr)\n"
        "with open(os.path.join(td, f'heartbeat-{rank}.json'), 'w') as f:\n"
        "    json.dump({'rank': int(rank), 'step': 130 + int(rank),\n"
        "               'time': time.time(), 'pid': os.getpid()}, f)\n"
        "with open(os.path.join(td, f'rank-{rank}.jsonl'), 'a') as f:\n"
        "    for i in range(3):\n"
        "        f.write(json.dumps({'t': time.time(), 'kind': 'step',\n"
        "                            'rank': int(rank), 'step': i}) + '\\n')\n"
        "if rank == '0':\n"
        "    time.sleep(5)\n"
        "    sys.exit(9)\n"
        "time.sleep(60)\n")
    env = dict(os.environ, MX_TELEMETRY_DIR=str(tdir),
               MX_HEARTBEAT_SEC="0.2")  # stale threshold = 2s floor
    res = _launch(2, worker, env=env, timeout=60)
    assert res.returncode == 9, (res.stdout, res.stderr)
    # interleaved gang output stays attributable
    for r in (0, 1):
        assert f"[rank {r}] hello from worker" in res.stdout, res.stdout
        assert f"[rank {r}] oops line" in res.stderr, res.stderr
    # diagnosed BEFORE the gang died (rank 1 never advanced its heartbeat)
    stale = re.search(r"rank 1 last heartbeat ([\d.]+)s ago at step 131 — "
                      "suspect hung/slow rank", res.stderr)
    assert stale, res.stderr
    assert float(stale.group(1)) >= 2.0
    # post-mortem: per-rank flight-recorder tail with parseable events
    for r in (0, 1):
        assert f"flight recorder tail (rank {r}" in res.stderr, res.stderr
    tail_events = [json.loads(line.strip()) for line in res.stderr.splitlines()
                   if line.strip().startswith('{"t"')]
    assert len(tail_events) >= 6  # 3 events x 2 ranks echoed
    assert {e["kind"] for e in tail_events} == {"step"}


# ---------------------------------------------------------------------------
# the full acceptance shape: 2-rank gang with real training telemetry
# ---------------------------------------------------------------------------
@pytest.mark.dist
def test_two_rank_gang_emits_jsonl_and_advancing_heartbeats(tmp_path):
    """2-rank launch_local with MX_TELEMETRY_DIR: one parseable JSONL
    stream per rank containing step, collective, and checkpoint events,
    plus heartbeat files that ADVANCED during the run (the worker verifies
    advancement in-process; we verify the final files)."""
    tdir = tmp_path / "telemetry"
    env = dict(os.environ, MX_TELEMETRY_DIR=str(tdir),
               MX_HEARTBEAT_SEC="0.05", MX_TELEMETRY_FLUSH_SEC="0.2")
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", "2", "--force-cpu", "--",
           sys.executable,
           os.path.join(_REPO, "tests", "dist", "telemetry_worker.py")]
    res = subprocess.run(cmd, cwd=_REPO, timeout=240, capture_output=True,
                         text=True, env=env)
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
    assert res.stdout.count("telemetry OK") == 2, res.stdout
    assert res.stdout.count("heartbeat advanced") == 2, res.stdout
    for rank in (0, 1):
        path = tdir / f"rank-{rank}.jsonl"
        events = [json.loads(line) for line in open(path)]
        kinds = {e["kind"] for e in events}
        assert {"start", "step", "collective",
                "checkpoint_save"} <= kinds, (rank, kinds)
        assert all(e["rank"] == rank for e in events)
        trainer_steps = [e["step"] for e in events
                         if e["kind"] == "step" and e["executor"] == "Trainer"]
        assert trainer_steps == sorted(trainer_steps) and \
            len(trainer_steps) == 30
        colls = [e for e in events if e["kind"] == "collective"]
        assert all(e["nbytes"] > 0 and e["wall_ms"] >= 0 for e in colls)
        hb = json.load(open(tdir / f"heartbeat-{rank}.json"))
        assert hb["rank"] == rank and hb["step"] >= 26
