"""Async step pipeline (docs/PERFORMANCE.md §Async pipeline): lazy
AsyncLoss handles, the bounded MX_ASYNC_INFLIGHT window, the device
prefetcher/step handshake, epoch/preemption drains, and deferred-error
delivery naming the dispatching step."""
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.parallel import AsyncLoss, DataParallelStep, local_mesh
from mxnet_tpu.parallel import async_loss as al
from mxnet_tpu.parallel import data_parallel as dp_mod

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# PR 9's scan-dispatch options, gone since PR 29.  Spelled in pieces: a
# grep of the tree for the old names stays empty
_SCAN_K = "MX_" + "SUPER" + "STEP"
_SCAN_FORCE_CPU = _SCAN_K + "_FORCE_CPU"


@pytest.fixture
def tele(tmp_path):
    from mxnet_tpu import telemetry

    telemetry.reset()
    telemetry.enable(str(tmp_path / "tele"))
    yield telemetry
    telemetry.flush()
    telemetry.reset()


def _build(optimizer="sgd", **kwargs):
    mx.random.seed(0)
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    kwargs.setdefault("mesh", local_mesh())
    return DataParallelStep(net, gluon.loss.L2Loss(), optimizer=optimizer,
                            **kwargs)


def _batches(n, b=8, d=4):
    rng = np.random.RandomState(0)
    return [(nd.array(rng.rand(b, d).astype(np.float32)),
             nd.array(rng.rand(b, 4).astype(np.float32)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# parity: async changes WHEN the host observes results, never what is
# computed
# ---------------------------------------------------------------------------
def test_losses_and_weights_bitwise_identical_across_window_sizes(
        monkeypatch):
    batches = _batches(6)

    def run(limit):
        import jax

        monkeypatch.setenv("MX_ASYNC_INFLIGHT", str(limit))
        step = _build()
        handles = [step.step(x, y) for x, y in batches]
        step.drain()
        losses = [h.asnumpy() for h in handles]
        # gluon's global name counter gives each _build() a fresh block
        # prefix (dense0_, dense1_, ...) — strip it so runs compare
        weights = {n.split("_", 1)[-1]: np.asarray(jax.device_get(a))
                   for n, a in step.params.items()}
        return losses, weights

    sync_l, sync_w = run(0)
    for limit in (1, 2, 4):
        async_l, async_w = run(limit)
        for a, b in zip(sync_l, async_l):
            assert np.array_equal(a, b), (limit, sync_l, async_l)
        assert sync_w.keys() == async_w.keys()
        for name in sync_w:
            assert np.array_equal(sync_w[name], async_w[name]), (limit, name)


def test_step_returns_lazy_handle_and_sync_mode_forces(monkeypatch):
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    step = _build()
    (x, y), = _batches(1)
    h = step.step(x, y)
    assert isinstance(h, AsyncLoss)
    assert not h.forced and step.inflight_depth == 1
    v = float(h)  # __float__ forces
    assert h.forced and np.isfinite(v)
    assert step.inflight_depth == 0  # forcing removed it from the ring
    # np.asarray / asnumpy / asscalar / item agree after forcing
    assert float(np.asarray(h)) == v == h.asscalar() == h.item()
    # MX_ASYNC_INFLIGHT=0: today's synchronous behavior, forced at dispatch
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "0")
    h2 = step.step(x, y)
    assert isinstance(h2, AsyncLoss) and h2.forced
    assert step.inflight_depth == 0


def test_window_never_exceeds_limit(tele, monkeypatch):
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    step = _build()
    for x, y in _batches(8):
        step.step(x, y)  # never forced by the caller
        assert step.inflight_depth <= 2
    depths = [e["inflight_depth"] for e in tele.flight_tail(50)
              if e["kind"] == "step"]
    assert len(depths) == 8
    assert max(depths) == 2 and all(d <= 2 for d in depths), depths
    step.drain()
    assert step.inflight_depth == 0
    # the ring-full dispatches blocked on the oldest step: the rollup saw it
    row = [v for k, v in tele.summary()["steps"].items()
           if k.startswith("DataParallelStep")][0]
    assert row["block_wait_ms"] >= 0.0


def _slow_handle(i, ring):
    """A step whose end the host has to wait for."""
    import time

    return AsyncLoss(np.float32(i), step=i, executor="t", ring=ring,
                     host_fn=lambda v: (time.sleep(0.004), v)[1])


@pytest.mark.parametrize("seconds,most", [(60.0, 5), (0.0, 2)])
def test_deep_window_grows_by_what_a_waited_step_took(seconds, most):
    """The ring by itself: a handle that was waited for and went from
    dispatch to its end in under ``seconds`` buys the next call one more
    slot, up to ``most``; one that took half as much again gives it back;
    a call never waits for more than the handles over its window."""
    ring = al.InflightRing("t")
    seen = []
    for i in range(30):
        before = ring.depth
        ring.make_room(2, deep=(5, seconds))
        assert before - ring.depth <= 1
        seen.append(ring.admit(_slow_handle(i, ring)))
    assert max(seen) == most and seen[-1] == most, seen
    ring.make_room(2)                   # no deepening: the limit alone
    assert ring.depth == 1
    ring.drain()


@pytest.mark.parametrize("env", [None, "3", "0", "junk"])
def test_unset_window_is_the_compiled_steps_to_deepen(monkeypatch, env):
    """Unset, the compiled step (a handle pins one scalar) may deepen its
    window to eight; the variable, where set, is the count, for it and
    for the Trainer/Module fences (a fence pins a generation of buffers),
    which never deepen."""
    if env is None:
        monkeypatch.delenv("MX_ASYNC_INFLIGHT", raising=False)
    else:
        monkeypatch.setenv("MX_ASYNC_INFLIGHT", env)
    limit, deep = al.compiled_step_window()
    assert limit == al.inflight_limit() == (
        2 if env in (None, "junk") else int(env))
    assert deep == (None if env is not None else (8, 1.25))
    step = _build()
    seen = []
    for x, y in _batches(11):
        step.step(x, y)
        seen.append(step.inflight_depth)
    assert max(seen) <= (8 if env is None else limit), seen
    if env is not None and limit:
        assert max(seen) == limit
    step.drain()
    assert step.inflight_depth == 0


def test_drain_on_epoch_end_via_device_prefetcher(monkeypatch):
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "4")
    step = _build()
    rng = np.random.RandomState(0)
    it = mx.io.NDArrayIter(rng.rand(32, 4).astype(np.float32),
                           rng.rand(32, 4).astype(np.float32), batch_size=8)
    dit = mx.io.DevicePrefetchIter(it, step)
    n = 0
    for b in dit:
        step.step(b.data[0], b.label[0])
        n += 1
        assert step.inflight_depth <= 4
    assert n == 4
    # StopIteration drained the ring: every dispatched step has landed
    assert step.inflight_depth == 0
    # and the iterator resets cleanly for another epoch
    dit.reset()
    assert sum(1 for _ in dit) == 4 and step.inflight_depth == 0


def test_prefetcher_step_handshake_no_double_transfer(tele, monkeypatch):
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    step = _build()
    (x, y), = _batches(1)
    float(step.step(x, y))  # init params/state so puts below are inputs only
    calls = {"n": 0}
    orig = dp_mod._global_put

    def counting(arr, sharding):
        calls["n"] += 1
        return orig(arr, sharding)

    monkeypatch.setattr(dp_mod, "_global_put", counting)
    staged_d, staged_l = step.stage((x,), y)
    assert calls["n"] == 2  # one put per input, in the staging thread's stead
    step.step(staged_d[0], staged_l)
    assert calls["n"] == 2, "step re-transferred a pre-placed input"
    step.drain()
    ev = [e for e in tele.flight_tail(20) if e["kind"] == "step"][-1]
    assert ev["h2d_overlapped"] == ev["transfer_bytes"] > 0
    # an un-staged batch reports zero overlap
    float(step.step(x, y))
    ev = [e for e in tele.flight_tail(20) if e["kind"] == "step"][-1]
    assert "h2d_overlapped" not in ev and ev["transfer_bytes"] > 0
    row = tele.summary()["steps"][ev["executor"]]
    assert 0 < row["h2d_overlapped_bytes"] < row["transfer_bytes"]


def test_dataloader_prefetch_to_hook(monkeypatch):
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    step = _build()
    rng = np.random.RandomState(0)
    ds = gluon.data.ArrayDataset(rng.rand(32, 4).astype(np.float32),
                                 rng.rand(32, 4).astype(np.float32))
    loader = gluon.data.DataLoader(ds, batch_size=8, prefetch_to=step)
    n = 0
    for data, label in loader:
        h = step.step(data, label)
        n += 1
    assert n == 4
    assert step.inflight_depth == 0  # loader exhaustion drained the ring
    assert np.isfinite(float(h))


def test_stage_batches_abandoned_consumer_retires_worker(monkeypatch):
    import threading
    import time as _time

    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    step = _build()
    rng = np.random.RandomState(0)
    ds = gluon.data.ArrayDataset(rng.rand(64, 4).astype(np.float32),
                                 rng.rand(64, 4).astype(np.float32))
    loader = gluon.data.DataLoader(ds, batch_size=8, prefetch_to=step)
    before = threading.active_count()
    # the common fixed-steps loop: abandons the generator mid-epoch
    for _i, (data, label) in zip(range(2), loader):
        step.step(data, label)
    # generator close must retire the staging worker (no leaked thread
    # parked forever in q.put) and drain the in-flight ring
    deadline = _time.monotonic() + 5.0
    while threading.active_count() > before and _time.monotonic() < deadline:
        _time.sleep(0.05)
    assert threading.active_count() <= before
    assert step.inflight_depth == 0


# ---------------------------------------------------------------------------
# the one dispatch path: what only a test of PR 9's scan dispatch covered
# ---------------------------------------------------------------------------
def _host_params(step):
    import jax

    return {n.split("_", 1)[-1]: np.asarray(jax.device_get(a))
            for n, a in step.params.items()}


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_ragged_final_batch_books_one_retrace_and_the_eager_loss(
        tele, optimizer):
    import jax

    # one device: five rows need not divide over a dp axis
    step = _build(optimizer,
                  mesh=local_mesh(devices=[jax.devices("cpu")[0]]))
    (x, y), = _batches(1)
    for _ in range(2):
        step.step(x, y)
    ragged_x, ragged_y = x[:5], y[:5]
    # the eager loss under the weights the ragged step starts from
    step.sync_to_block()
    eager = gluon.loss.L2Loss()(step.block(ragged_x), ragged_y).mean()
    loss = step.step(ragged_x, ragged_y)
    np.testing.assert_allclose(float(loss), float(eager.asscalar()),
                               rtol=1e-6)
    step.step(ragged_x, ragged_y)     # seen: no second retrace
    step.drain()
    traced = [bool(e.get("traced")) for e in tele.flight_tail(50)
              if e["kind"] == "step"]
    assert traced == [True, False, True, False]
    assert step._jitted._cache_size() == 2


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
def test_loss_scaled_overflow_step_holds_weights_at_any_window(
        monkeypatch, optimizer):
    from mxnet_tpu.precision import LossScaleConfig, PrecisionConfig

    (x, y), = _batches(1)
    bad = x.asnumpy().copy()
    bad[0, 0] = np.inf
    batches = [x, nd.array(bad), x]

    def run(limit):
        monkeypatch.setenv("MX_ASYNC_INFLIGHT", str(limit))
        step = _build(optimizer, precision=PrecisionConfig(
            loss_scale=LossScaleConfig(init_scale=16.0, growth_interval=4)))
        handles, weights = [], []
        for b in batches:
            handles.append(step.step(b, y))
            weights.append(_host_params(step))
        step.drain()
        scaler = {k: np.asarray(v) for k, v in step.scaler_state.items()}
        return [h.asnumpy() for h in handles], weights, scaler

    sync_l, sync_w, sync_s = run(0)
    async_l, async_w, async_s = run(2)
    for i in (0, 2):
        assert np.array_equal(sync_l[i], async_l[i]), (i, sync_l, async_l)
    assert not np.isfinite(sync_l[1]) and not np.isfinite(async_l[1])
    for weights in (sync_w, async_w):
        for name in weights[0]:
            # the overflow step is a no-op update; the next one moves
            assert np.array_equal(weights[0][name], weights[1][name]), name
        assert any(not np.array_equal(weights[1][n], weights[2][n])
                   for n in weights[0])
    for name in sync_w[2]:
        assert np.array_equal(sync_w[2][name], async_w[2][name]), name
    assert sync_s.keys() == async_s.keys()
    for k in sync_s:
        assert np.array_equal(sync_s[k], async_s[k]), k
    assert int(sync_s["skipped"]) == 1 and float(sync_s["scale"]) == 8.0


@pytest.mark.parametrize("wrap", ["DevicePrefetchIter", "stage_batches"])
def test_staging_queues_one_batch_unless_told(monkeypatch, wrap):
    """Batches staged ahead of a consumer that took one and stopped: the
    one it took, ``depth`` in the queue, one in the worker's hand."""
    import time as _time

    monkeypatch.setenv(_SCAN_K, "4")   # sized the queue once; inert
    step = _build()
    rng = np.random.RandomState(0)
    data = rng.rand(96, 4).astype(np.float32)
    staged = []
    orig = step.stage
    monkeypatch.setattr(step, "stage",
                        lambda d, l: staged.append(1) or orig(d, l))

    def staged_ahead(**kw):
        del staged[:]
        if wrap == "DevicePrefetchIter":
            it = iter(mx.io.DevicePrefetchIter(
                mx.io.NDArrayIter(data, data, batch_size=8), step, **kw))
        else:
            it = mx.io.stage_batches(
                [(nd.array(data[i:i + 8]), nd.array(data[i:i + 8]))
                 for i in range(0, 96, 8)], step, **kw)
        next(it)
        n = -1
        deadline = _time.monotonic() + 5.0
        while n != len(staged) and _time.monotonic() < deadline:
            n = len(staged)
            _time.sleep(0.2)
        if wrap == "stage_batches":
            it.close()
        return n

    assert staged_ahead() == 1 + 1 + 1
    assert staged_ahead(depth=4) == 1 + 4 + 1


def test_dispatched_step_keeps_no_reference_to_its_inputs():
    import gc
    import weakref

    step = _build()
    (x, y), = _batches(1)
    float(step.step(x, y))
    staged_d, staged_l = step.stage((x,), y)
    refs = [weakref.ref(staged_d[0]._data), weakref.ref(staged_l._data)]
    handle = step.step(staged_d[0], staged_l)
    del staged_d, staged_l
    step.drain()
    gc.collect()
    assert [r() for r in refs] == [None, None]
    assert np.isfinite(float(handle))


def test_the_scan_dispatch_options_are_inert(tele, monkeypatch):
    monkeypatch.setenv(_SCAN_K, "4")
    monkeypatch.setenv(_SCAN_FORCE_CPU, "1")
    step = _build()
    handles = [step.step(x, y) for x, y in _batches(4)]
    assert [type(h) for h in handles] == [AsyncLoss] * 4
    assert [h.step for h in handles] == [1, 2, 3, 4]
    step.drain()
    dispatched = [s for s in tele.spans_between(0.0, float("inf"))
                  if s.name == "dispatch"]
    assert len(dispatched) == 4


# ---------------------------------------------------------------------------
# deferred failures
# ---------------------------------------------------------------------------
def test_deferred_error_names_dispatching_step():
    def boom(_value):
        raise RuntimeError("kaboom")

    ring = al.InflightRing("X")
    h = AsyncLoss(object(), step=7, executor="DataParallelStep:Net#9",
                  ring=ring, host_fn=boom)
    ring.admit(h)
    with pytest.raises(mx.base.MXNetError) as ei:
        h.wait()
    msg = str(ei.value)
    assert "step 7" in msg and "DataParallelStep:Net#9" in msg
    assert "kaboom" in msg
    # exactly the same (wrapped) error again on re-force; the ring is clean
    with pytest.raises(mx.base.MXNetError):
        float(h)
    assert ring.depth == 0

    # a poisoned handle inside the window surfaces when dispatch makes
    # room, and the ring never wedges
    ring2 = al.InflightRing("Y")
    bad = AsyncLoss(object(), step=1, executor="Y", ring=ring2, host_fn=boom)
    ring2.admit(bad)
    with pytest.raises(mx.base.MXNetError):
        ring2.make_room(1)
    assert ring2.depth == 0 and ring2.make_room(1) == 0.0


def test_one_dispatch_path_is_the_whole_surface():
    """The scan dispatch left no shim behind: the handle classes, the
    package's exports and the step's public methods are the sequential
    path's."""
    assert set(al.__all__) == {"AsyncLoss", "AsyncResult", "StepFence",
                               "InflightRing", "inflight_limit", "drain_all"}
    assert set(dp_mod.__all__) == {"DataParallelStep", "make_train_step",
                                   "compile_step_with_plan", "dp_plan"}
    handles = {c.__name__ for c in al._PendingHandle.__subclasses__()}
    assert handles == {"AsyncLoss", "StepFence"}
    public = {n for n in vars(DataParallelStep) if not n.startswith("_")}
    assert public == {
        "stage", "step", "drain", "inflight_depth", "learning_rate",
        "set_learning_rate", "sync_to_block", "layout", "state_dict",
        "shard_state_dict", "load_state_dict",
        "snapshot_requires_collective",
        "scope_map"}        # PR 35: read-only, built on ask, never by step()


def test_drain_all_preemption_path(monkeypatch):
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "4")
    step = _build()
    for x, y in _batches(3):
        step.step(x, y)
    assert step.inflight_depth > 0
    assert al.drain_all() == []  # what the SIGTERM handler runs pre-snapshot
    assert step.inflight_depth == 0

    # best-effort contract: failures are returned, not raised
    ring = al.InflightRing("Z")
    ring.admit(AsyncLoss(object(), step=3, executor="Z", ring=ring,
                         host_fn=lambda v: (_ for _ in ()).throw(
                             RuntimeError("dead"))))
    errs = al.drain_all()
    assert len(errs) == 1 and "step 3" in str(errs[0])
    assert ring.depth == 0


# ---------------------------------------------------------------------------
# Trainer / Module ride the same window
# ---------------------------------------------------------------------------
def test_trainer_window_bounded_and_drains(tele, monkeypatch):
    from mxnet_tpu import autograd

    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    mx.random.seed(0)
    net = gluon.nn.Dense(2)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.L2Loss()
    x = nd.array(np.random.rand(4, 3).astype(np.float32))
    y = nd.array(np.random.rand(4, 2).astype(np.float32))
    for _ in range(5):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)
    depths = [e["inflight_depth"] for e in tele.flight_tail(50)
              if e["kind"] == "step" and e["executor"] == "Trainer"]
    assert len(depths) == 5 and all(0 < d <= 2 for d in depths), depths
    trainer.drain()
    assert trainer._inflight.depth == 0


def test_trainer_sync_mode_adds_no_fences(monkeypatch):
    from mxnet_tpu import autograd

    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "0")
    mx.random.seed(0)
    net = gluon.nn.Dense(2)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    with autograd.record():
        loss = gluon.loss.L2Loss()(
            net(nd.array(np.random.rand(4, 3).astype(np.float32))),
            nd.array(np.random.rand(4, 2).astype(np.float32)))
    loss.backward()
    trainer.step(4)
    assert trainer._inflight is None
    trainer.drain()  # no-op, must not raise


# ---------------------------------------------------------------------------
# 2-rank gang: deferred readback across a real Gloo mesh (slow tier per
# the tier-1 wall budget; the in-process tests above cover the default
# tier)
# ---------------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.dist
def test_two_rank_gang_deferred_readback_parity():
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", "2", "--force-cpu", "--",
           sys.executable,
           os.path.join(_REPO, "tests", "dist", "async_step_worker.py")]
    res = subprocess.run(cmd, cwd=_REPO, timeout=240, capture_output=True,
                         text=True, env=dict(os.environ))
    assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-2000:])
    assert res.stdout.count("async dist OK") == 2, res.stdout
