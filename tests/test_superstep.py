"""Superstep compiled training + persistent AOT executable cache
(docs/PERFORMANCE.md §Superstep & AOT executable cache): K steps per
compiled lax.scan dispatch with bitwise parity across modes, the
transparent MX_SUPERSTEP step() routing with its CPU-mesh gate, stacked
loss semantics, and the MX_EXECUTABLE_CACHE_DIR restart cache
(round-trip, corruption fallback, kill switch, supervised gang
restart)."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import aot_cache, gluon, nd
from mxnet_tpu.parallel import (AsyncLoss, DataParallelStep,
                                StackedAsyncLoss, SuperstepLossView,
                                local_mesh, superstep_k)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def tele(tmp_path):
    from mxnet_tpu import memwatch, telemetry

    telemetry.reset()
    memwatch.reset()
    telemetry.enable(str(tmp_path / "tele"))
    yield telemetry
    telemetry.flush()
    telemetry.reset()
    memwatch.reset()


def _build(opt="sgd", one_dev=True, prefix=None):
    """prefix: pass a FIXED block prefix when the test needs two builds
    to share one executable fingerprint (param names are part of the
    restart-stable identity; gluon's global name counter would otherwise
    make every in-process rebuild a distinct program)."""
    import jax

    mx.random.seed(0)
    net = gluon.nn.Dense(4, prefix=prefix)
    net.initialize(mx.init.Xavier())
    mesh = (local_mesh(devices=[jax.devices()[0]]) if one_dev
            else local_mesh())
    return DataParallelStep(net, gluon.loss.L2Loss(), mesh=mesh,
                            optimizer=opt)


def _events(tele):
    tele.flush()
    return [json.loads(line)
            for f in glob.glob(os.path.join(tele.summary()["dir"],
                                            "rank-*.jsonl"))
            for line in open(f)]


def _batches(n, b=8, d=4):
    rng = np.random.RandomState(0)
    return [(nd.array(rng.rand(b, d).astype(np.float32)),
             nd.array(rng.rand(b, 4).astype(np.float32)))
            for _ in range(n)]


def _weights(step):
    import jax

    # gluon's global name counter gives each _build() a fresh block
    # prefix — strip it so runs compare
    return {n.split("_", 1)[-1]: np.asarray(jax.device_get(a))
            for n, a in step.params.items()}


def _run_mode(monkeypatch, batches, k, opt="sgd", one_dev=True):
    """Train len(batches) steps with MX_SUPERSTEP=k (0 = off) ->
    (per-step losses, final weights)."""
    monkeypatch.setenv("MX_SUPERSTEP", str(k))
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    step = _build(opt=opt, one_dev=one_dev)
    handles = [step.step(x, y) for x, y in batches]
    step.drain()
    losses = [np.asarray(h.asnumpy()) for h in handles]
    return losses, _weights(step)


# ---------------------------------------------------------------------------
# parity: superstep changes HOW MANY steps one dispatch carries, never
# what is computed
# ---------------------------------------------------------------------------
def test_losses_and_weights_bitwise_identical_across_superstep_modes(
        monkeypatch):
    """Acceptance: MX_SUPERSTEP=0, 1 and 4 produce bitwise-identical
    per-step losses AND final weights on the same model/data (CPU
    force-on, single-device mesh)."""
    batches = _batches(8)
    base_l, base_w = _run_mode(monkeypatch, batches, 0)
    for k in (1, 4):
        l, w = _run_mode(monkeypatch, batches, k)
        for i, (a, b) in enumerate(zip(base_l, l)):
            assert np.array_equal(a, b), (k, i, a, b)
        assert base_w.keys() == w.keys()
        for name in base_w:
            assert np.array_equal(base_w[name], w[name]), (k, name)


def test_adam_parity_and_lr_schedule_scans(monkeypatch):
    """Stateful optimizer (Adam's t counter rides the scan carry) and a
    per-step lr schedule (lr becomes a scanned array) both stay bitwise
    faithful to sequential dispatch."""
    import jax

    batches = _batches(8)

    def run(k):
        monkeypatch.setenv("MX_SUPERSTEP", str(k))
        monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
        from mxnet_tpu.optimizer.lr_scheduler import FactorScheduler

        mx.random.seed(0)
        net = gluon.nn.Dense(4)
        net.initialize(mx.init.Xavier())
        step = DataParallelStep(
            net, gluon.loss.L2Loss(),
            mesh=local_mesh(devices=[jax.devices()[0]]), optimizer="adam",
            optimizer_params={
                "learning_rate": 0.01,
                "lr_scheduler": FactorScheduler(step=2, factor=0.5)})
        handles = [step.step(x, y) for x, y in batches]
        step.drain()
        return ([np.asarray(h.asnumpy()) for h in handles],
                _weights(step))

    l0, w0 = run(0)
    l4, w4 = run(4)
    for a, b in zip(l0, l4):
        assert np.array_equal(a, b)
    for name in w0:
        assert np.array_equal(w0[name], w4[name]), name


def test_scan_family_self_consistent_across_lengths_multi_device(
        monkeypatch):
    """On a multi-device mesh the scan executable family (K=1, 2, 4 —
    incl. partial-group lengths) is bitwise self-consistent: chunking
    never changes the trajectory.  (The plain non-scan path may differ
    from the scan family at ~1 ulp on multi-device meshes — XLA fuses
    the inlined body differently — which is why the 0-vs-K acceptance
    parity is asserted on a single-device mesh above.)"""
    batches = _batches(8)
    l1, w1 = _run_mode(monkeypatch, batches, 1, one_dev=False)
    for k in (2, 4):
        l, w = _run_mode(monkeypatch, batches, k, one_dev=False)
        for a, b in zip(l1, l):
            assert np.array_equal(a, b), k
        for name in w1:
            assert np.array_equal(w1[name], w[name]), (k, name)


def test_explicit_superstep_matches_sequential(monkeypatch):
    batches = _batches(8)
    base_l, base_w = _run_mode(monkeypatch, batches, 0)
    monkeypatch.setenv("MX_SUPERSTEP", "0")
    step = _build()
    h1 = step.superstep(batches[:4])
    h2 = step.superstep(batches[4:])
    step.drain()
    got = list(h1.asnumpy()) + list(h2.asnumpy())
    for a, b in zip(base_l, got):
        assert np.array_equal(np.asarray(a).ravel(), np.asarray(b).ravel())
    w = _weights(step)
    for name in base_w:
        assert np.array_equal(base_w[name], w[name]), name


# ---------------------------------------------------------------------------
# transparent-mode semantics
# ---------------------------------------------------------------------------
def test_superstep_defaults_off_on_cpu_mesh(monkeypatch):
    """Acceptance: MX_SUPERSTEP=4 WITHOUT the force override is inert on
    a CPU mesh — step() stays on the plain path and returns a plain
    AsyncLoss, not a superstep view."""
    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.delenv("MX_SUPERSTEP_FORCE_CPU", raising=False)
    step = _build()
    assert superstep_k(step.mesh) == 0
    h = step.step(*_batches(1)[0])
    assert isinstance(h, AsyncLoss)
    assert not isinstance(h, SuperstepLossView)
    assert step._open_group is None
    step.drain()
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    assert superstep_k(step.mesh) == 4


def test_stacked_loss_semantics_and_views(monkeypatch):
    """StackedAsyncLoss: len/vector/scalar contracts; views resolve to
    their own step's loss; forcing a view mid-group dispatches the
    partial group as a shorter scan (no deadlock, order preserved)."""
    batches = _batches(8)
    base_l, _ = _run_mode(monkeypatch, batches, 0)
    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    step = _build()
    v0 = step.step(*batches[0])
    v1 = step.step(*batches[1])
    assert isinstance(v0, SuperstepLossView)
    assert len(step._open_group.entries) == 2
    # forcing v0 dispatches the partial (K'=2) group
    assert np.array_equal(np.asarray(v0.asnumpy()), np.asarray(base_l[0]))
    assert step._open_group is None
    # remaining steps open a fresh group; explicit superstep returns the
    # stacked handle with vector + scalar semantics
    h = step.superstep(batches[2:6])
    assert isinstance(h, StackedAsyncLoss)
    assert len(h) == 4
    vec = h.asnumpy()
    assert vec.shape == (4,)
    assert float(h) == vec[-1]
    assert h.steps == (3, 4, 5, 6)
    for i, v in enumerate(vec):
        assert np.array_equal(np.float32(v),
                              np.float32(np.asarray(base_l[2 + i]))), i
    assert np.array_equal(np.asarray(v1.asnumpy()), base_l[1])
    step.drain()


def test_superstep_one_step_event_one_compile_per_group(monkeypatch, tele):
    """One telemetry step event (superstep=K, samples summed over the
    group) and ONE compile event per superstep executable — not one per
    covered step."""
    from mxnet_tpu import memwatch

    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    batches = _batches(8)
    step = _build()
    for x, y in batches:
        step.step(x, y)
    step.drain()
    evs = _events(tele)
    steps = [e for e in evs if e.get("kind") == "step"
             and e.get("executor", "").startswith("DataParallelStep")]
    assert len(steps) == 2, steps
    assert all(e["superstep"] == 4 for e in steps)
    assert all(e["samples"] == 4 * 8 for e in steps)
    assert [e["step"] for e in steps] == [4, 8]
    comps = [e for e in evs if e.get("kind") == "compile"
             and e.get("site") == "superstep"]
    assert len(comps) == 1, comps
    assert memwatch.summary()["compiles"]["count"] == 1


def test_superstep_rides_inflight_ring(monkeypatch, tele):
    """The in-flight window bounds dispatched SUPERSTEPS: one ring
    admission per group, depth never exceeds MX_ASYNC_INFLIGHT."""
    monkeypatch.setenv("MX_SUPERSTEP", "2")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    monkeypatch.setenv("MX_ASYNC_INFLIGHT", "2")
    step = _build()
    for x, y in _batches(12):
        step.step(x, y)
    step.drain()
    evs = _events(tele)
    depths = [e["inflight_depth"] for e in evs if e.get("kind") == "step"]
    assert depths and max(depths) <= 2, depths


def test_superstep_with_device_prefetcher(monkeypatch, tele):
    """DevicePrefetchIter auto-sizes its queue to K and its staged
    batches are consumed without a second H2D (h2d_overlapped > 0 on
    superstep records); losses match the unprefetched run bitwise."""
    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    batches = _batches(8)

    class _Iter(mx.io.DataIter):
        def __init__(self):
            super().__init__(batch_size=8)
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self):
            if self.i >= len(batches):
                raise StopIteration
            x, y = batches[self.i]
            self.i += 1
            return mx.io.DataBatch([x], [y])

    base_l, base_w = _run_mode(monkeypatch, batches, 4)
    monkeypatch.setenv("MX_SUPERSTEP", "4")
    step = _build()
    it = mx.io.DevicePrefetchIter(_Iter(), step)
    assert it._QUEUE_DEPTH == 4
    views = [step.step(b.data[0], b.label[0]) for b in it]
    step.drain()
    for a, b in zip(base_l, [np.asarray(v.asnumpy()) for v in views]):
        assert np.array_equal(a, b)
    w = _weights(step)
    for name in base_w:
        assert np.array_equal(base_w[name], w[name])
    evs = _events(tele)
    sups = [e for e in evs if e.get("kind") == "step" and e.get("superstep")]
    assert sups and any(e.get("h2d_overlapped", 0) > 0 for e in sups)


def test_ragged_final_batch_closes_group_instead_of_crashing(monkeypatch):
    """A shape change mid-group (the classic no-drop-last final batch)
    flushes the open group as a shorter scan and starts a fresh one —
    the buffered full steps land instead of dying in jnp.stack."""
    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    rng = np.random.RandomState(0)
    full = [(nd.array(rng.rand(8, 4).astype(np.float32)),
             nd.array(rng.rand(8, 4).astype(np.float32)))
            for _ in range(3)]
    tail = (nd.array(rng.rand(5, 4).astype(np.float32)),
            nd.array(rng.rand(5, 4).astype(np.float32)))

    def run(k):
        monkeypatch.setenv("MX_SUPERSTEP", str(k))
        step = _build()
        views = [step.step(x, y) for x, y in full + [tail]]
        step.drain()
        return ([np.asarray(v.asnumpy()) for v in views], _weights(step))

    base_l, base_w = run(0)
    l, w = run(4)
    for a, b in zip(base_l, l):
        assert np.array_equal(a, b)
    for name in base_w:
        assert np.array_equal(base_w[name], w[name]), name


def test_dispatched_group_releases_its_input_buffers(monkeypatch):
    """Loss views outlive their group; the group's K placed input
    buffers must not ride along (an epoch of retained views would pin
    every batch on device)."""
    monkeypatch.setenv("MX_SUPERSTEP", "2")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    step = _build()
    views = [step.step(x, y) for x, y in _batches(4)]
    step.drain()
    for v in views:
        group = v._dispatch_fn.__defaults__[0]
        assert group.handle is not None
        assert group.entries == []
    # and the views still resolve after the release
    assert all(np.isfinite(float(np.asarray(v.asnumpy()))) for v in views)


def test_aot_alternating_signatures_reuse_in_memory(tmp_path, tele,
                                                    monkeypatch):
    """Two interleaved input shapes each deserialize/compile at most
    once — subsequent steps reuse the per-signature executable in
    memory instead of re-reading the disk entry every step."""
    cache = tmp_path / "aot"
    monkeypatch.setenv("MX_EXECUTABLE_CACHE_DIR", str(cache))
    loads = []
    real_load = aot_cache.load
    monkeypatch.setattr(aot_cache, "load",
                        lambda key, *a: loads.append(key) or real_load(key, *a))
    rng = np.random.RandomState(0)
    a = (nd.array(rng.rand(8, 4).astype(np.float32)),
         nd.array(rng.rand(8, 4).astype(np.float32)))
    b = (nd.array(rng.rand(4, 4).astype(np.float32)),
         nd.array(rng.rand(4, 4).astype(np.float32)))
    step = _build()
    for _ in range(5):
        step.step(*a)
        step.step(*b)
    step.drain()
    assert len(step._aot_execs) == 2
    assert len(loads) == 2, loads


def test_superstep_deferred_error_names_step(monkeypatch):
    """A chaos fault injected mid-group surfaces at the group dispatch
    wrapped with the failing step's number; the ring never wedges."""
    from mxnet_tpu.base import MXNetError

    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    monkeypatch.setenv("MX_FAULT_SPEC", "oom:step=3")
    step = _build()
    batches = _batches(4)
    step.step(*batches[0])
    step.step(*batches[1])
    with pytest.raises(MXNetError, match="RESOURCE_EXHAUSTED"):
        for x, y in batches[2:]:
            step.step(x, y)
        step.drain()
    monkeypatch.delenv("MX_FAULT_SPEC")
    # the step object keeps working after the poisoned group
    h = step.step(*batches[0])
    step.drain()
    assert np.isfinite(float(h.asnumpy().ravel()[-1]))


# ---------------------------------------------------------------------------
# AOT executable cache
# ---------------------------------------------------------------------------
_CACHE_SCRIPT = r"""
import os, sys, json, time
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, sys.argv[1])
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, memwatch, nd, telemetry
from mxnet_tpu.parallel import DataParallelStep, local_mesh
import jax

telemetry.enable(sys.argv[2])
mx.random.seed(0)
net = gluon.nn.Dense(4)
net.initialize(mx.init.Xavier())
step = DataParallelStep(net, gluon.loss.L2Loss(),
                        mesh=local_mesh(devices=[jax.devices()[0]]),
                        optimizer="adam")
rng = np.random.RandomState(0)
x = nd.array(rng.rand(8, 4).astype(np.float32))
y = nd.array(rng.rand(8, 4).astype(np.float32))
t0 = time.perf_counter()
losses = [float(step.step(x, y)) for _ in range(2)]
ttfs = time.perf_counter() - t0
h = step.superstep([(x, y)] * 3)  # superstep executable cached too
losses += [float(v) for v in np.asarray(h.asnumpy())]
step.drain()
# fused-updater site via a toy Trainer
net2 = gluon.nn.Dense(3)
net2.initialize(mx.init.Xavier())
tr = gluon.Trainer(net2.collect_params(), "sgd",
                   {"learning_rate": 1e-3, "momentum": 0.9})
with autograd.record():
    l2 = (net2(x) ** 2).sum()
l2.backward()
tr.step(8)
tr.drain()
telemetry.flush()
print(json.dumps({"losses": losses,
                  "compiles": memwatch.summary()["compiles"]}))
"""


def _run_cache_proc(tele_dir, cache_dir, extra_env=None):
    env = dict(os.environ, MX_EXECUTABLE_CACHE_DIR=str(cache_dir))
    env.pop("MX_SUPERSTEP", None)
    env.update(extra_env or {})
    res = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT, _REPO, str(tele_dir)],
        capture_output=True, text=True, timeout=240, env=env, cwd=_REPO)
    assert res.returncode == 0, (res.stdout, res.stderr)
    return json.loads(res.stdout.strip().splitlines()[-1])


def _compile_events(tele_dir):
    evs = [json.loads(line)
           for f in glob.glob(os.path.join(str(tele_dir), "rank-*.jsonl"))
           for line in open(f)]
    return [e for e in evs if e.get("kind") == "compile"]


@pytest.mark.slow
def test_aot_cache_restart_round_trip_two_processes(tmp_path):
    """Acceptance: the second process books ZERO fresh compiles at the
    DataParallelStep (single-step + superstep) and FusedUpdater jit
    sites — every compile event carries cache_hit + deserialize_ms —
    and computes bitwise-identical losses.  (Sequential by necessity:
    process B needs process A's cache on disk.)"""
    cache = tmp_path / "aot"
    a = _run_cache_proc(tmp_path / "tele_a", cache)
    assert a["compiles"]["cache_hits"] == 0
    assert len(glob.glob(str(cache / "*.jexec"))) >= 3
    b = _run_cache_proc(tmp_path / "tele_b", cache)
    assert b["losses"] == a["losses"]
    evs = _compile_events(tmp_path / "tele_b")
    assert evs, "second process booked no compile events at all"
    fresh = [e for e in evs if not e.get("cache_hit")]
    assert not fresh, f"second process compiled fresh: {fresh}"
    assert all(e.get("deserialize_ms", 0) > 0 for e in evs)
    assert b["compiles"]["cache_hits"] == len(evs)


def test_aot_corrupt_entry_falls_back_cleanly(tmp_path, tele, monkeypatch):
    """Truncated and garbage cache entries are a MISS, never a crash:
    the site recompiles fresh (cache_corrupt marked) and overwrites the
    bad entry with a good one."""
    cache = tmp_path / "aot"
    monkeypatch.setenv("MX_EXECUTABLE_CACHE_DIR", str(cache))
    batches = _batches(2)
    # fixed prefix: rebuilds must share the executable fingerprint, as a
    # restarted process would (gluon's name counter resets per process)
    s1 = _build(prefix="sstep_")
    l1 = [np.asarray(s1.step(x, y).asnumpy()) for x, y in batches]
    s1.drain()
    files = glob.glob(str(cache / "*.jexec"))
    assert len(files) == 1
    good = open(files[0], "rb").read()
    key = os.path.basename(files[0])[:-len(".jexec")]

    for blob in (good[: len(good) // 2], b"not a pickle at all"):
        with open(files[0], "wb") as f:
            f.write(blob)
        loaded, info = aot_cache.load(key)
        assert loaded is None and info.get("cache_corrupt")
        s2 = _build(prefix="sstep_")
        l2 = [np.asarray(s2.step(x, y).asnumpy()) for x, y in batches]
        s2.drain()
        for x, y_ in zip(l1, l2):
            assert np.array_equal(x, y_)
        # the fresh compile overwrote the corrupt entry with a loadable one
        loaded, info = aot_cache.load(key)
        assert loaded is not None and info.get("cache_hit"), info


def test_aot_kill_switch_disables_all_persistence(tmp_path, tele,
                                                  monkeypatch):
    """Acceptance: MX_EXECUTABLE_CACHE=0 disables AOT persistence even
    with a cache dir set — nothing written, nothing loaded, compile
    events carry no cache fields."""
    cache = tmp_path / "aot"
    cache.mkdir()
    monkeypatch.setenv("MX_EXECUTABLE_CACHE_DIR", str(cache))
    monkeypatch.setenv("MX_EXECUTABLE_CACHE", "0")
    assert not aot_cache.enabled()
    step = _build()
    for x, y in _batches(2):
        step.step(x, y)
    step.drain()
    assert glob.glob(str(cache / "*")) == []
    tele.flush()
    evs = _compile_events(str(tele._state.dir))
    assert evs and all("cache_hit" not in e for e in evs)
    # and without a dir at all the cache is simply off
    monkeypatch.delenv("MX_EXECUTABLE_CACHE")
    monkeypatch.delenv("MX_EXECUTABLE_CACHE_DIR")
    assert not aot_cache.enabled()


def test_mem_report_marks_cached_executables(tmp_path):
    """tools/mem_report.py's executable table distinguishes "loaded in
    0.2s" (aot column: hit) from "compiled in 40s" (aot column: -)."""
    lines = [
        {"t": 1.0, "kind": "compile", "rank": 0,
         "executor": "DataParallelStep:Dense#1",
         "fingerprint": "ab12cd34ef56ab12", "site": "superstep",
         "wall_ms": 40000.0},
        {"t": 2.0, "kind": "compile", "rank": 0,
         "executor": "DataParallelStep:Dense#2",
         "fingerprint": "ab12cd34ef56ab13", "site": "superstep",
         "wall_ms": 210.0, "cache_hit": True, "deserialize_ms": 180.0},
    ]
    with open(tmp_path / "rank-0.jsonl", "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")
    res = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "mem_report.py"),
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, (res.stdout, res.stderr)
    rep = json.loads(res.stdout)
    by_fp = {r["fingerprint"]: r for r in rep["executables"]}
    assert by_fp["ab12cd34ef56ab12"]["cache_hit"] is False
    assert by_fp["ab12cd34ef56ab13"]["cache_hit"] is True
    assert by_fp["ab12cd34ef56ab13"]["deserialize_ms"] == 180.0
    txt = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "mem_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60)
    assert "hit(0.2s)" in txt.stdout, txt.stdout


# ---------------------------------------------------------------------------
# supervised gang kill-and-restart with a warm cache (slow e2e)
# ---------------------------------------------------------------------------
def _launch_ssr(tmp_path, phase, extra_env=None, launcher_args=(),
                timeout=300):
    env = dict(os.environ,
               MX_SSR_PHASE=phase, MX_SSR_DIR=str(tmp_path),
               MX_SUPERSTEP="4", MX_SUPERSTEP_FORCE_CPU="1",
               MX_EXECUTABLE_CACHE_DIR=str(tmp_path / "aot"),
               MX_TELEMETRY_FLUSH_SEC="0.2")
    env.update(extra_env or {})
    cmd = [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
           "-n", "2", "--force-cpu", "--restart-backoff", "0.2",
           *launcher_args, "--",
           sys.executable,
           os.path.join(_REPO, "tests", "dist",
                        "superstep_restart_worker.py")]
    return subprocess.run(cmd, timeout=timeout, capture_output=True,
                          text=True, env=env, cwd=_REPO)


@pytest.mark.dist
@pytest.mark.slow
@pytest.mark.chaos
def test_supervised_restart_with_warm_cache_resumes_bitwise(tmp_path):
    """Acceptance (slow gang e2e): rank 1 dies mid-run at step 24,
    tools/launch.py --max-restarts re-spawns the gang, the restarted
    incarnation resumes from the step-20 checkpoint with a WARM AOT
    cache (zero fresh scan compiles) and finishes bitwise-identical to
    the uninterrupted baseline."""
    res0 = _launch_ssr(tmp_path, "baseline")
    assert res0.returncode == 0, (res0.stdout[-2000:], res0.stderr[-1000:])
    assert res0.stdout.count("baseline OK") == 2, res0.stdout

    res = _launch_ssr(tmp_path, "supervised",
                      launcher_args=("--max-restarts", "1",
                                     "--term-timeout", "5"))
    assert res.returncode == 0, (res.stdout[-2500:], res.stderr[-1500:])
    assert "self-kill at step 24" in res.stdout
    assert "restarting gang (1/1)" in res.stderr
    assert "rank 1: incarnation 1 resuming at step 20" in res.stdout
    assert "warm-cache restart OK" in res.stdout
    # rank 1's final incarnation must match; rank 0 matches in whichever
    # incarnation(s) it completed (it may finish before the gang dies,
    # then re-verify at resume — two prints are legitimate)
    assert "rank 1: matches uninterrupted baseline" in res.stdout
    assert "rank 0: matches uninterrupted baseline" in res.stdout


# ---------------------------------------------------------------------------
# preemption-path flush of buffered groups (ISSUE 12 satellite: the PR 9
# known issue — drain_all() used to skip buffered-but-undispatched
# _SuperstepGroup entries, silently dropping up to K-1 steps from a
# SIGTERM's final sync checkpoint)
# ---------------------------------------------------------------------------
def test_drain_all_flushes_buffered_superstep_groups(monkeypatch):
    """drain_all DISPATCHES an open partial group (as a shorter scan)
    before draining the rings — the buffered steps land in the params
    instead of vanishing."""
    from mxnet_tpu.parallel import async_loss

    batches = _batches(6)
    base_l, base_w = _run_mode(monkeypatch, batches, 0)
    monkeypatch.setenv("MX_SUPERSTEP", "4")
    monkeypatch.setenv("MX_SUPERSTEP_FORCE_CPU", "1")
    step = _build()
    for x, y in batches:  # 4 dispatch as one group, 2 stay buffered
        step.step(x, y)
    assert step._open_group is not None \
        and len(step._open_group.entries) == 2
    errors = async_loss.drain_all()
    assert errors == []
    assert step._open_group is None or not step._open_group.entries
    w = _weights(step)
    for name in base_w:
        assert np.array_equal(base_w[name], w[name]), name


_PREEMPT_SUPERSTEP_WORKER = """\
import os, sys, time
sys.path.insert(0, {repo!r})
os.environ["MX_SUPERSTEP"] = "4"
os.environ["MX_SUPERSTEP_FORCE_CPU"] = "1"
import numpy as np
import jax
import mxnet_tpu as mx
from mxnet_tpu import checkpoint, fault, gluon, nd
from mxnet_tpu.parallel import DataParallelStep, local_mesh

ckdir = sys.argv[1]
mx.random.seed(0)
net = gluon.nn.Dense(4)
net.initialize(mx.init.Xavier())
step = DataParallelStep(net, gluon.loss.L2Loss(),
                        mesh=local_mesh(devices=[jax.devices()[0]]),
                        optimizer="sgd")
ckpt = checkpoint.AsyncCheckpointer(ckdir, save_every=1000)
fault.install_preemption_handler(ckpt, step)
rng = np.random.RandomState(0)
batches = [(nd.array(rng.rand(8, 4).astype(np.float32)),
            nd.array(rng.rand(8, 4).astype(np.float32)))
           for _ in range(6)]
for x, y in batches:
    step.step(x, y)
    ckpt.step(step)
# 4 steps dispatched as one scan; steps 5-6 still buffered when SIGTERM hits
assert step._open_group is not None and len(step._open_group.entries) == 2
open(os.path.join(ckdir, "ready"), "w").close()
while True:
    time.sleep(0.05)
"""


@pytest.mark.chaos
def test_preemption_checkpoint_includes_buffered_superstep_steps(tmp_path):
    """CHAOS acceptance for the satellite: SIGTERM lands with 2 of 6
    steps still buffered in an open K=4 group; the final preemption
    checkpoint must carry ALL 6 steps' updates (bitwise vs the 6-step
    sequential oracle), not silently drop the buffered two."""
    import signal
    import subprocess as sp
    import time as _time

    from mxnet_tpu import checkpoint

    # sequential oracle in-process
    mp = pytest.MonkeyPatch()
    try:
        batches = _batches(6)
        _l, oracle = _run_mode(mp, batches, 0)
    finally:
        mp.undo()

    ckdir = tmp_path / "ck"
    os.makedirs(ckdir)
    script = tmp_path / "worker.py"
    script.write_text(_PREEMPT_SUPERSTEP_WORKER.format(repo=_REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = sp.Popen([sys.executable, str(script), str(ckdir)], env=env,
                    stdout=sp.PIPE, stderr=sp.PIPE, text=True)
    ready = ckdir / "ready"
    deadline = _time.monotonic() + 240
    while not ready.exists():
        assert proc.poll() is None, proc.communicate()
        assert _time.monotonic() < deadline, "worker never became ready"
        _time.sleep(0.1)
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    assert proc.returncode == 83, (out, err[-2000:])
    assert "final checkpoint at step 6" in out, (out, err[-1000:])
    state = checkpoint.load_checkpoint_state(str(ckdir))
    assert state["step"] == 6
    for name in oracle:
        got = state["params"][name].asnumpy()
        assert np.array_equal(oracle[name], got), name
