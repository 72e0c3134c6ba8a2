"""Elastic gang resize (docs/FAULT_TOLERANCE.md §Elastic resize): resharding
checkpoint restore onto a different mesh/world size, the checkpointable
iterator cursor that survives a resize with no sample skipped or consumed
twice, the shared-dir writer contract, the --elastic supervisor (shrink on
exhausted restarts, regrow after stable running), and the resize-aware
report tools.

Fast tier: everything except the two gang e2e runs at the bottom (slow):
a 3-rank gang that permanently loses rank 2 (`if-world=3` chaos spec),
shrinks to 2, and finishes bitwise-identical to a fixed 2-rank baseline
resumed from the same checkpoint — and the 2->3 grow mirror.
"""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.io.io import NDArrayIter
from mxnet_tpu.parallel import DataParallelStep, make_mesh
from mxnet_tpu.parallel.sharding import ShardingRules

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# checkpointable iterator position (tentpole (c) + seeded-shuffle satellite)
# ---------------------------------------------------------------------------
def _data(n=48, d=1):
    X = np.arange(n * d, dtype=np.float32).reshape(n, d)
    Y = np.arange(n, dtype=np.float32)
    return X, Y


def test_seeded_shuffle_reproducible_and_per_iterator():
    """Same seed => same epoch order, independent of global np.random and
    of any other iterator's draws (the io.py:130 global-shuffle fix)."""
    X, Y = _data()
    np.random.seed(1)
    a = NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=7)
    np.random.seed(999)  # global state must be irrelevant
    b = NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=7)
    # interleave a third iterator's construction + draws: no perturbation
    c = NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=8)
    c.next()
    ia = [int(v) for _ in range(3) for v in (a.next(), a.getindex())[1]]
    ib = [int(v) for _ in range(3) for v in (b.next(), b.getindex())[1]]
    assert ia == ib
    # different epochs shuffle differently, reproducibly
    a.reset(), b.reset()
    ia2 = [int(v) for _ in range(3) for v in (a.next(), a.getindex())[1]]
    ib2 = [int(v) for _ in range(3) for v in (b.next(), b.getindex())[1]]
    assert ia2 == ib2 and ia2 != ia


def test_unseeded_iterator_state_still_restores_exactly():
    """seed=None draws a seed but records it in get_state: a restore
    reproduces the order without the caller ever choosing a seed."""
    X, Y = _data()
    it = NDArrayIter(X, Y, batch_size=4, shuffle=True)
    it.next()
    state = it.get_state()
    rest = [int(v) for _ in range(2) for v in (it.next(), it.getindex())[1]]
    it2 = NDArrayIter(X, Y, batch_size=4, shuffle=True)
    it2.set_state(state)
    rest2 = [int(v) for _ in range(2)
             for v in (it2.next(), it2.getindex())[1]]
    assert rest == rest2


def test_gang_sharding_rejects_unsafe_configs():
    """Divergent-per-rank hazards fail at construction: shuffle without
    an agreed seed would shard DIFFERENT permutations, and roll_over
    would hand higher-index parts ragged final batches."""
    X, Y = _data()
    with pytest.raises(MXNetError, match="explicit.*seed|seed.*explicit"):
        NDArrayIter(X, Y, batch_size=4, shuffle=True, num_parts=2,
                    part_index=0)
    with pytest.raises(MXNetError, match="roll_over"):
        NDArrayIter(X, Y, batch_size=4, seed=1, num_parts=2, part_index=1,
                    last_batch_handle="roll_over")
    # single-part legacy behaviors keep working
    NDArrayIter(X, Y, batch_size=4, shuffle=True)
    NDArrayIter(X, Y, batch_size=4, last_batch_handle="roll_over")


def test_state_rejects_different_dataset():
    X, Y = _data()
    it = NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=3)
    state = it.get_state()
    other = NDArrayIter(X[:40], Y[:40], batch_size=4, shuffle=True, seed=3)
    with pytest.raises(MXNetError, match="same dataset"):
        other.set_state(state)


def test_num_parts_shards_one_global_order():
    """Ranks of one (seed, epoch) permutation tile the global batch:
    part p takes batch_size samples at offset p, cursor strides by
    batch_size * num_parts."""
    X, Y = _data(24)
    parts = [NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=5,
                         num_parts=2, part_index=p) for p in range(2)]
    whole = NDArrayIter(X, Y, batch_size=8, shuffle=True, seed=5)
    for _ in range(3):
        whole.next()
        got = []
        for it in parts:
            it.next()
            got.extend(int(v) for v in it.getindex())
        assert got == [int(v) for v in whole.getindex()]


def test_iterator_census_across_resize_no_skip_no_dup():
    """ACCEPTANCE: a mid-epoch world-size change (3 ranks -> 2 ranks,
    different per-rank batch split) via get_state/set_state consumes
    every sample of the epoch EXACTLY once — the sample-id census."""
    X, Y = _data(48)
    old = [NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=7,
                       num_parts=3, part_index=p) for p in range(3)]
    seen = []
    for _ in range(2):  # 2 global batches x 12 samples at world 3
        for it in old:
            it.next()
            seen.extend(int(v) for v in it.getindex())
    state = old[0].get_state()
    assert state["sample_cursor"] == 24
    # "resize": 2 ranks, batch 6 (stride 12 -> 12; also try uneven stride)
    new = [NDArrayIter(X, Y, batch_size=6, shuffle=True, seed=0,
                       num_parts=2, part_index=p) for p in range(2)]
    for it in new:
        it.set_state(state)
    while True:
        try:
            for it in new:
                it.next()
                seen.extend(int(v) for v in it.getindex())
        except StopIteration:
            break
    assert sorted(seen) == list(range(48)), "census: skipped/duplicated"


def test_iterator_census_grow_with_stride_change():
    """Grow mirror with a stride that does NOT divide the old cursor:
    2 ranks x batch 3 (stride 6) -> 3 ranks x batch 4 (stride 12)."""
    X, Y = _data(48)
    old = [NDArrayIter(X, Y, batch_size=3, shuffle=True, seed=11,
                       num_parts=2, part_index=p) for p in range(2)]
    seen = []
    for _ in range(3):  # 18 samples consumed
        for it in old:
            it.next()
            seen.extend(int(v) for v in it.getindex())
    state = old[0].get_state()
    new = [NDArrayIter(X, Y, batch_size=5, shuffle=True, seed=0,
                       num_parts=3, part_index=p) for p in range(3)]
    for it in new:
        it.set_state(state)
    for _ in range(2):  # 2 more global batches x 15
        for it in new:
            it.next()
            seen.extend(int(v) for v in it.getindex())
    assert sorted(seen) == list(range(48)), "census: skipped/duplicated"


# ---------------------------------------------------------------------------
# resharding checkpoint restore (tentpole (a))
# ---------------------------------------------------------------------------
def _train_step(mesh, rules=None, opt="adam", steps=3, seed=0):
    mx.random.seed(seed)
    net = gluon.nn.Dense(3)
    net.initialize(mx.init.Normal(0.5))
    step = DataParallelStep(net, gluon.loss.L2Loss(), mesh=mesh,
                            optimizer=opt, rules=rules,
                            optimizer_params={"learning_rate": 0.05})
    rng = np.random.RandomState(0)
    data = nd.array(rng.rand(8, 6).astype(np.float32))
    label = nd.array(rng.rand(8, 3).astype(np.float32))
    for _ in range(steps):
        float(step.step(data, label))
    return step, (data, label)


def test_checkpoint_records_layout_and_opt_state(tmp_path):
    import jax

    step, _ = _train_step(make_mesh(devices=jax.devices()[:4]))
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    ck.step(step)
    ck.close()
    meta = json.load(open(tmp_path / "step-1" / "meta.json"))
    assert meta["world_size"] == 1
    lay = meta["layout"]
    assert dict(map(tuple, lay["mesh_axes"]))["dp"] == 4
    assert len(lay["device_ids"]) == 4
    assert set(lay["specs"]) == {"weight", "bias"}
    assert (tmp_path / "step-1" / "opt_state.nd").exists()
    assert "opt_state.nd" in meta["digests"]


def test_restore_reshards_onto_smaller_and_larger_mesh(tmp_path):
    """Save on dp4, restore on dp2 (shrink) and dp8 (grow): params AND
    Adam moments identical — the N->M correctness core the gang e2e
    rides on.  Training continues: bitwise-identical between two
    restores at the SAME new size, and within the documented GSPMD
    tolerance of the old mesh's trajectory (a different mesh size
    compiles a different reduction order)."""
    import jax

    step, (data, label) = _train_step(make_mesh(devices=jax.devices()[:4]))
    ref = step.state_dict()
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    ck.step(step)
    ck.close()
    ref_next = float(step.step(data, label))

    def restore_fresh(devs):
        mx.random.seed(0)
        net2 = gluon.nn.Dense(3)
        net2.initialize(mx.init.Normal(0.5))
        step2 = DataParallelStep(net2, gluon.loss.L2Loss(),
                                 mesh=make_mesh(devices=devs),
                                 optimizer="adam",
                                 optimizer_params={"learning_rate": 0.05})
        assert checkpoint.restore(str(tmp_path), step2) == 1
        return step2

    for devs in (jax.devices()[:2], jax.devices()):
        step2 = restore_fresh(devs)
        sd = step2.state_dict()
        for k, v in ref["params"].items():
            np.testing.assert_array_equal(v, sd["params"][k])
        for k, v in ref["opt_state"].items():
            np.testing.assert_array_equal(v, sd["opt_state"][k])
        nxt = float(step2.step(data, label))
        # same-new-size restores are bitwise self-consistent (what the
        # gang e2e's fixed-size-baseline parity rides on)...
        assert nxt == float(restore_fresh(devs).step(data, label))
        # ...and track the old mesh within GSPMD reduction-order drift
        np.testing.assert_allclose(nxt, ref_next, rtol=1e-5)


def test_restore_same_size_different_device_order(tmp_path):
    """ACCEPTANCE satellite: a mesh of the SAME size but a different
    device order is a different layout (device assignment is
    load-bearing); restore must detect the mismatch, reshard,
    and produce identical values."""
    import jax

    from mxnet_tpu.parallel.data_parallel import _layouts_equal

    step, (data, label) = _train_step(make_mesh(devices=jax.devices()[:4]))
    ref = step.state_dict()
    saved_layout = step.layout()
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    ck.step(step)
    ck.close()
    ref_next = float(step.step(data, label))

    mx.random.seed(0)
    net2 = gluon.nn.Dense(3)
    net2.initialize(mx.init.Normal(0.5))
    mesh2 = make_mesh(devices=list(reversed(jax.devices()[:4])))
    step2 = DataParallelStep(net2, gluon.loss.L2Loss(), mesh=mesh2,
                             optimizer="adam",
                             optimizer_params={"learning_rate": 0.05})
    assert not _layouts_equal(saved_layout, {**saved_layout,
                                             "device_ids": [3, 2, 1, 0]})
    state = checkpoint.load_checkpoint_state(str(tmp_path), step=1)
    host = {"params": {k: v.asnumpy() for k, v in state["params"].items()},
            "opt_state": {k: v.asnumpy()
                          for k, v in state["opt_state"].items()}}
    info = step2.load_state_dict(host, saved_layout=state["layout"])
    assert info["resharded"], "reordered devices must count as a reshard"
    sd = step2.state_dict()
    for k, v in ref["params"].items():
        np.testing.assert_array_equal(v, sd["params"][k])
    assert float(step2.step(data, label)) == ref_next


def test_restore_reshards_tp_sharded_params(tmp_path):
    """Genuinely SHARDED (tensor-parallel) params round-trip through the
    gather-to-host baseline and land correctly on a different mesh."""
    import jax

    rules = ShardingRules([(r".*weight", (None, "tp"))])
    mesh = make_mesh(tp=2, devices=jax.devices()[:4])
    step, (data, label) = _train_step(mesh, rules=rules)
    ref = step.state_dict()
    lay = step.layout()
    assert lay["specs"]["weight"] == [None, "tp"]
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    ck.step(step)
    ck.close()

    mx.random.seed(0)
    net2 = gluon.nn.Dense(3)
    net2.initialize(mx.init.Normal(0.5))
    mesh2 = make_mesh(tp=2, devices=jax.devices()[4:6])
    step2 = DataParallelStep(net2, gluon.loss.L2Loss(), mesh=mesh2,
                             optimizer="adam", rules=rules,
                             optimizer_params={"learning_rate": 0.05})
    assert checkpoint.restore(str(tmp_path), step2) == 1
    sd = step2.state_dict()
    for k, v in ref["params"].items():
        np.testing.assert_array_equal(v, sd["params"][k])


def test_discard_mode_restored_unaligned_cursor_stays_uniform():
    """set_state under discard with a CHANGED stride may land on a
    cursor unaligned to the new stride; every emitted batch must still
    be full-shape on every rank (a straddling window would hand rank 1
    an empty batch into a sync collective) and the epoch tail shorter
    than one global window is discarded — discard semantics."""
    X, Y = _data(20)
    old = NDArrayIter(X, Y, batch_size=6, shuffle=True, seed=3,
                      num_parts=2, part_index=0,
                      last_batch_handle="discard")
    old.next()
    state = old.get_state()
    assert state["sample_cursor"] == 12
    new = [NDArrayIter(X, Y, batch_size=4, shuffle=True, seed=3,
                       num_parts=2, part_index=p,
                       last_batch_handle="discard") for p in range(2)]
    counts = []
    for it in new:
        it.set_state(state)
        n_batches = 0
        while True:
            try:
                b = it.next()
            except StopIteration:
                break
            n_batches += 1
            assert b.data[0].shape == (4, 1), b.data[0].shape
        counts.append(n_batches)
    # both ranks see the SAME number of full batches: window 12..20 fits
    # exactly once under stride 8
    assert counts == [1, 1], counts


def test_manual_resize_restore_records_marker_but_elastic_does_not(
        tmp_path, monkeypatch):
    """The `resize` segment marker is minted exactly once per logical
    resize: by the restore for supervisor-less (manual) world changes,
    by the rendezvous under --elastic — a later same-size restart that
    re-restores the old-world checkpoint must not double it."""
    import glob

    import jax

    from mxnet_tpu import telemetry

    step, _ = _train_step(make_mesh(devices=jax.devices()[:4]), steps=1)
    state = step.state_dict()
    saved = step.layout()
    saved["world_size"] = 3  # pretend the save came from a 3-proc gang

    def resize_events(run):
        monkeypatch.setenv("MX_TELEMETRY_DIR", "")
        telemetry.reset()
        d = str(tmp_path / run)
        telemetry.enable(d)
        mx.random.seed(0)
        net2 = gluon.nn.Dense(3)
        net2.initialize(mx.init.Normal(0.5))
        step2 = DataParallelStep(net2, gluon.loss.L2Loss(),
                                 mesh=make_mesh(devices=jax.devices()[4:6]),
                                 optimizer="adam",
                                 optimizer_params={"learning_rate": 0.05})
        info = step2.load_state_dict(state, saved_layout=saved)
        assert info["resharded"]
        telemetry.flush()
        telemetry.reset()
        events = [json.loads(line)
                  for f in glob.glob(os.path.join(d, "rank-*.jsonl"))
                  for line in open(f)]
        return [e for e in events if e.get("kind") == "resize"], \
               [e for e in events if e.get("kind") == "reshard"]

    monkeypatch.delenv("MX_ELASTIC", raising=False)
    monkeypatch.delenv("MX_PREV_NUM_PROCS", raising=False)
    resizes, reshards = resize_events("manual")
    assert len(resizes) == 1 and resizes[0]["old_world"] == 3
    assert reshards, "reshard detail event must record either way"

    # under the supervisor (any incarnation — incl. a same-size restart
    # after the resize, where MX_PREV_NUM_PROCS is no longer exported)
    # the rendezvous owns the marker
    monkeypatch.setenv("MX_ELASTIC", "1")
    resizes, reshards = resize_events("elastic")
    assert resizes == [], resizes
    assert reshards


def test_restore_rejects_optimizer_kind_mismatch(tmp_path):
    """An adam checkpoint restored into an sgd step must raise, not
    silently zero-fill every optimizer slot."""
    import jax

    step, _ = _train_step(make_mesh(devices=jax.devices()[:2]), steps=1)
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    ck.step(step)
    ck.close()
    mx.random.seed(0)
    net2 = gluon.nn.Dense(3)
    net2.initialize(mx.init.Normal(0.5))
    step2 = DataParallelStep(net2, gluon.loss.L2Loss(),
                             mesh=make_mesh(devices=jax.devices()[:2]),
                             optimizer="sgd")
    with pytest.raises(MXNetError, match="'adam'.*'sgd'"):
        checkpoint.restore(str(tmp_path), step2)


def test_nonwriter_checkpointer_counts_but_never_writes(tmp_path):
    """Shared-dir gang contract: writer=False ranks step-count, heartbeat
    and run the chaos hooks, but never publish (or prune) anything."""
    import jax

    step, _ = _train_step(make_mesh(devices=jax.devices()[:2]), steps=2)
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1,
                                      writer=False)
    assert ck.step(step) is False
    assert ck.save_now(step) == 0
    ck.close()
    assert not any(d.startswith("step-") for d in os.listdir(tmp_path))
    # a non-writer with an explicit resume step must not prune the shared
    # timeline the writer owns
    w = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    w.step(step)
    w.step(step)
    w.close()
    ro = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1,
                                      initial_step=1, writer=False)
    ro.close()
    assert os.path.isdir(tmp_path / "step-2"), "non-writer pruned the dir"


# ---------------------------------------------------------------------------
# fault grammar: if-world + crash-rendezvous (satellite)
# ---------------------------------------------------------------------------
def test_if_world_qualifier_gates_by_world_size(monkeypatch):
    from mxnet_tpu import fault

    f = fault.parse_spec("crash:step=8:rank=2:if-world=3")[0]
    assert f.if_world == 3
    monkeypatch.setenv("MX_PROC_ID", "2")
    monkeypatch.setenv("MX_NUM_PROCS", "3")
    assert f.applies_here()
    monkeypatch.setenv("MX_NUM_PROCS", "2")  # after the shrink: inert
    assert not f.applies_here()
    monkeypatch.delenv("MX_NUM_PROCS")
    monkeypatch.setenv("DMLC_NUM_WORKER", "3")  # reference spelling
    assert f.applies_here()


def test_crash_rendezvous_grammar():
    from mxnet_tpu import fault

    f = fault.parse_spec("crash-rendezvous:rank=1:if-restart=2")[0]
    assert f.kind == "crash-rendezvous" and f.rank == 1
    with pytest.raises(MXNetError, match="step= does not apply"):
        fault.parse_spec("crash-rendezvous:step=3")


def test_crash_rendezvous_fires_in_subprocess(tmp_path):
    """on_rendezvous exits EXIT_INJECTED_CRASH when the spec applies —
    driven through the real dist hook in a subprocess (no gang needed:
    the crash fires BEFORE jax.distributed.initialize dials out)."""
    script = tmp_path / "w.py"
    script.write_text(
        "import sys; sys.path.insert(0, %r)\n"
        "from mxnet_tpu import fault\n"
        "fault.on_rendezvous()\n"
        "print('survived', flush=True)\n" % _REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MX_FAULT_SPEC="crash-rendezvous:if-world=3",
               MX_NUM_PROCS="3", MX_PROC_ID="0")
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 57, (res.stdout, res.stderr)
    assert "injected crash during rendezvous" in res.stdout
    env["MX_NUM_PROCS"] = "2"  # world qualifier gates it off
    res = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0 and "survived" in res.stdout


# ---------------------------------------------------------------------------
# resize-aware report tools (CI/tooling satellite)
# ---------------------------------------------------------------------------
def _write_stream(d, rank, events):
    os.makedirs(d, exist_ok=True)
    with open(os.path.join(d, f"rank-{rank}.jsonl"), "w") as f:
        for ev in events:
            f.write(json.dumps(dict(ev, rank=rank)) + "\n")


def _steps(t0, n, wall=10.0, traced=False, dt=0.011):
    return [{"t": t0 + i * dt, "kind": "step", "step": i + 1,
             "wall_ms": wall, "traced": traced} for i in range(n)]


def test_trace_report_does_not_blame_resize_wall(tmp_path):
    """The teardown silence + recompile wall of an elastic resize must
    not read as a straggler or an event gap; the SAME streams without
    the resize marker ARE flagged (the control)."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import trace_report

    def build(d, with_resize):
        anchor = [{"t": 100.0, "kind": "clock_anchor", "mono": 0.0}]
        for rank in (0, 1):
            pre = _steps(100.0, 30)
            post = _steps(200.0, 30, traced=False)
            recompile = [{"t": 199.0, "kind": "step", "step": 31,
                          "wall_ms": 900.0, "traced": True}]
            resize = ([{"t": 198.5, "kind": "resize", "old_world": 3,
                        "new_world": 2}] if with_resize else [])
            _write_stream(d, rank, anchor + pre + resize + recompile + post)
        # rank 2 died before the resize: short clean pre-resize stream
        _write_stream(d, 2, anchor + _steps(100.0, 30))

    flagged = str(tmp_path / "no_marker")
    build(flagged, with_resize=False)
    rep = trace_report.build_report(flagged, gap_sec=30.0)
    assert rep["anomalies"], "control: the naked 70s gap must flag"

    clean = str(tmp_path / "marked")
    build(clean, with_resize=True)
    rep = trace_report.build_report(clean, gap_sec=30.0)
    assert rep["per_rank"]["0"]["resizes"] == 1
    assert rep["resizes"] and rep["resizes"][0]["new_world"] == 2
    gap_or_straggler = [a for a in rep["anomalies"]
                        if "gap" in a or "straggler" in a]
    assert not gap_or_straggler, rep["anomalies"]


def test_mem_report_leak_window_resets_at_resize(tmp_path):
    """A fresh post-resize incarnation ramping its allocations up must
    not read as a monotonic leak when the trailing window spans the
    restart; without the marker it does (the control)."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import mem_report

    def mems(t0, bytes0, n, grow):
        return [{"t": t0 + i, "kind": "mem",
                 "live_bytes": bytes0 + i * grow,
                 "watermark_bytes": bytes0 + i * grow,
                 "categories": {"params": {"nbytes": bytes0 + i * grow}}}
                for i in range(n)]

    # 6 old-incarnation samples at high watermark, then restart low and
    # ramp: strictly increasing across the 12-window only if the boundary
    # is ignored... make the joined series strictly increasing by
    # construction: old 1..6MB, new 7..13MB (fresh process ramp-up)
    old = mems(100.0, 1 << 20, 6, 1 << 20)
    new = mems(200.0, 7 << 20, 7, 1 << 20)
    control = str(tmp_path / "control")
    _write_stream(control, 0, old + new)
    rep = mem_report.build_report(control, window=12)
    assert rep["per_rank"]["0"]["leak"]["verdict"] == "leak", "control"

    marked = str(tmp_path / "marked")
    _write_stream(marked, 0,
                  old + [{"t": 199.5, "kind": "resize", "old_world": 3,
                          "new_world": 2}] + new)
    rep = mem_report.build_report(marked, window=12)
    assert rep["per_rank"]["0"]["leak"]["verdict"] != "leak", \
        rep["per_rank"]["0"]["leak"]


# ---------------------------------------------------------------------------
# --elastic supervisor machinery (no-jax workers: fast chaos tier, same
# pattern as test_dist_launch's supervisor tests)
# ---------------------------------------------------------------------------
def _run_elastic(tmp_path, script_body, n, extra_args=(), timeout=90):
    worker = tmp_path / "worker.py"
    worker.write_text(script_body)
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", str(n), "--restart-backoff", "0.05", "--elastic",
           *extra_args, "--", sys.executable, str(worker)]
    return subprocess.run(cmd, timeout=timeout, capture_output=True,
                          text=True)


@pytest.mark.chaos
def test_supervisor_shrinks_instead_of_failing(tmp_path):
    """Budget exhausted at world 3 with rank 2 always dying => shrink to
    2 survivors with MX_PREV_NUM_PROCS exported and a fresh budget, then
    clean exit."""
    res = _run_elastic(tmp_path, (
        "import os, sys\n"
        "n = os.environ['MX_NUM_PROCS']; r = os.environ['MX_PROC_ID']\n"
        "prev = os.environ.get('MX_PREV_NUM_PROCS', '-')\n"
        "print(f'rank {r}/{n} prev {prev} elastic '\n"
        "      f\"{os.environ.get('MX_ELASTIC')}\", flush=True)\n"
        "if n == '3' and r == '2':\n"
        "    sys.exit(7)\n"
    ), n=3, extra_args=("--max-restarts", "1"))
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "shrinking gang 3 -> 2" in res.stderr, res.stderr
    # two failed attempts at world 3, then the resized incarnation
    assert res.stdout.count("rank 2/3") == 2, res.stdout
    assert "rank 0/2 prev 3 elastic 1" in res.stdout, res.stdout
    assert "rank 2/2" not in res.stdout


@pytest.mark.chaos
def test_supervisor_gives_up_at_min_workers(tmp_path):
    """The floor holds: at --min-workers the exhausted budget fails the
    job with the world-size-annotated history."""
    res = _run_elastic(tmp_path, (
        "import os, sys\n"
        "sys.exit(9 if os.environ['MX_PROC_ID'] == '0' else 0)\n"
    ), n=2, extra_args=("--max-restarts", "0", "--min-workers", "2"))
    assert res.returncode == 9
    assert "giving up" in res.stderr
    assert "(world 2)" in res.stderr, res.stderr


@pytest.mark.chaos
def test_supervisor_regrows_to_target(tmp_path):
    """--initial-workers below target + --regrow-after: the healthy gang
    is preempted and re-spawned at the full target with the old world
    exported."""
    res = _run_elastic(tmp_path, (
        "import os, time\n"
        "n = os.environ['MX_NUM_PROCS']; r = os.environ['MX_PROC_ID']\n"
        "print(f\"rank {r}/{n} prev \"\n"
        "      f\"{os.environ.get('MX_PREV_NUM_PROCS', '-')}\", flush=True)\n"
        "if n == '2':\n"
        "    time.sleep(60)\n"
    ), n=3, extra_args=("--initial-workers", "2", "--regrow-after", "1",
                        "--term-timeout", "2"), timeout=60)
    assert res.returncode == 0, (res.stdout, res.stderr)
    assert "growing gang 2 -> 3" in res.stderr, res.stderr
    assert "rank 2/3 prev 2" in res.stdout, res.stdout


@pytest.mark.chaos
def test_supervisor_regrow_steps_and_rearms(tmp_path):
    """The PR 11 'Known' fix: regrow steps +1 toward the target (1 -> 2
    -> 3, a fresh stability countdown at each size, NOT one jump to -n),
    and re-arms after a LATER culprit shrinks the regrown gang below
    target again — the grow -> shrink -> grow cycle converges back to
    the target instead of sticking at the shrunken size."""
    marker = tmp_path / "crashed.marker"
    res = _run_elastic(tmp_path, (
        "import os, sys, time\n"
        "n = os.environ['MX_NUM_PROCS']\n"
        f"marker = {str(marker)!r}\n"
        "print(f\"rank {os.environ['MX_PROC_ID']}/{n} prev \"\n"
        "      f\"{os.environ.get('MX_PREV_NUM_PROCS', '-')}\", flush=True)\n"
        "if n == '3':\n"
        "    if not os.path.exists(marker):\n"
        "        # first time at target: rank 2's host goes bad once\n"
        "        if os.environ['MX_PROC_ID'] == '2':\n"
        "            open(marker, 'w').write('x')\n"
        "            sys.exit(7)\n"
        "        time.sleep(30)\n"
        "    sys.exit(0)  # second regrow to target: healthy\n"
        "time.sleep(60)  # below target: wait for the regrow preemption\n"
    ), n=3, extra_args=("--max-restarts", "0", "--initial-workers", "1",
                        "--regrow-after", "1", "--term-timeout", "2"),
        timeout=120)
    assert res.returncode == 0, (res.stdout, res.stderr)
    # +1 stepping: two distinct growth steps on the way up
    assert "growing gang 1 -> 2" in res.stderr, res.stderr
    assert res.stderr.count("growing gang 2 -> 3") == 2, res.stderr
    # never a straight 1 -> 3 jump
    assert "growing gang 1 -> 3" not in res.stderr
    assert "shrinking gang 3 -> 2" in res.stderr, res.stderr
    # the re-regrown incarnation carries the resize export
    assert "rank 2/3 prev 2" in res.stdout, res.stdout


def test_cli_validates_elastic_flags():
    for args in (["--min-workers", "0"],
                 ["--min-workers", "5"],
                 ["--elastic", "--initial-workers", "9"],
                 ["--initial-workers", "2"],   # requires --elastic
                 ["--regrow-after", "5"]):     # requires --elastic
        res = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
             "-n", "3", *args, "--", "true"],
            capture_output=True, text=True)
        assert res.returncode != 0, args


# ---------------------------------------------------------------------------
# the gang e2e (slow tier): shrink 3->2 under chaos, grow 2->3 via regrow,
# each bitwise-matched against a fixed-size baseline resumed from the
# SAME checkpoint
# ---------------------------------------------------------------------------
def _launch(n, env, launcher_args=(), timeout=420):
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", str(n), "--force-cpu", *launcher_args, "--",
           sys.executable,
           os.path.join(_REPO, "tests", "dist", "elastic_worker.py")]
    return subprocess.run(cmd, cwd=_REPO, timeout=timeout,
                          capture_output=True, text=True, env=env)


def _baseline_from(ckpt_src, base_dir, n, resume_step, tag):
    """Run a FIXED n-rank gang restoring exactly `resume_step` from a
    copy of the elastic run's shared checkpoint dir."""
    os.makedirs(base_dir, exist_ok=True)
    shutil.copytree(ckpt_src, os.path.join(base_dir, "ckpt"))
    env = dict(os.environ, MX_ELASTIC_DIR=str(base_dir),
               MX_ELASTIC_TAG=tag, MX_RESUME_STEP=str(resume_step))
    res = _launch(n, env)
    assert res.returncode == 0, (res.stdout[-2500:], res.stderr[-1500:])
    assert res.stdout.count(f"resuming at step {resume_step} world {n}") \
        == n, res.stdout
    return np.load(os.path.join(base_dir, f"final_{tag}.npz"))


def _assert_same_weights(a, b):
    assert set(a.files) == set(b.files)
    for k in a.files:
        assert np.array_equal(a[k], b[k]), k


@pytest.mark.chaos
@pytest.mark.slow
def test_elastic_shrink_end_to_end(tmp_path):
    """ACCEPTANCE: a 3-rank gang under MX_FAULT_SPEC loses rank 2
    permanently (if-world=3: it dies at step 8 of EVERY world-3
    incarnation), the --elastic supervisor exhausts the budget and
    re-rendezvouses at world size 2, training resumes from the resharded
    step-5 checkpoint, and the final weights are BITWISE identical to a
    fixed 2-rank gang trained from the same checkpoint (single device
    per rank)."""
    env = dict(os.environ, MX_ELASTIC_DIR=str(tmp_path),
               MX_ELASTIC_TAG="elastic",
               MX_FAULT_SPEC="crash:step=8:rank=2:if-world=3")
    res = _launch(3, env, launcher_args=(
        "--elastic", "--max-restarts", "1", "--term-timeout", "5",
        "--restart-backoff", "0.2"))
    assert res.returncode == 0, (res.stdout[-2500:], res.stderr[-1500:])
    assert res.stdout.count("injected crash at step 8") == 2, res.stdout
    assert "shrinking gang 3 -> 2" in res.stderr, res.stderr
    # both survivors resumed at the agreed scheduled step, resharding the
    # world-3 checkpoint onto the world-2 mesh
    assert res.stdout.count(
        "resuming at step 5 world 2 resharded=True old_world=3") == 2, \
        res.stdout
    assert res.stdout.count("done") == 2, res.stdout
    elastic = np.load(tmp_path / "final_elastic.npz")

    base = _baseline_from(tmp_path / "ckpt", tmp_path / "baseline", n=2,
                          resume_step=5, tag="base2")
    _assert_same_weights(elastic, base)


@pytest.mark.chaos
@pytest.mark.slow
def test_elastic_grow_end_to_end(tmp_path):
    """ACCEPTANCE grow mirror: a gang degraded to 2 ranks
    (--initial-workers 2) regrows to the 3-rank target after stable
    running — planned preemption, re-rendezvous at world 3, resharded
    resume — and matches a fixed 3-rank baseline trained from the same
    checkpoint."""
    tdir = tmp_path / "tele"
    env = dict(os.environ, MX_ELASTIC_DIR=str(tmp_path),
               MX_ELASTIC_TAG="grown", MX_ELASTIC_STEP_SLEEP="0.1",
               MX_TELEMETRY_DIR=str(tdir))  # heartbeats arm the regrow
    res = _launch(3, env, launcher_args=(
        "--elastic", "--initial-workers", "2", "--regrow-after", "2",
        "--max-restarts", "1", "--term-timeout", "8",
        "--restart-backoff", "0.2"))
    assert res.returncode == 0, (res.stdout[-2500:], res.stderr[-1500:])
    assert "growing gang 2 -> 3" in res.stderr, res.stderr
    m = re.findall(r"resuming at step (\d+) world 3 resharded=True "
                   r"old_world=2", res.stdout)
    assert len(m) == 3, res.stdout  # every rank of the grown gang
    resume_step = int(m[0])
    assert resume_step > 0 and resume_step % 5 == 0
    elastic = np.load(tmp_path / "final_grown.npz")

    # the resize event landed in the survivors' telemetry streams and
    # trace_report treats the recompile segment as such, not a straggler
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import trace_report

    rep = trace_report.build_report(str(tdir))
    assert any(r["new_world"] == 3 for r in rep["resizes"]), rep["resizes"]
    assert not [a for a in rep["anomalies"] if "straggler" in a], \
        rep["anomalies"]

    base = _baseline_from(tmp_path / "ckpt", tmp_path / "baseline", n=3,
                          resume_step=resume_step, tag="base3")
    _assert_same_weights(elastic, base)
