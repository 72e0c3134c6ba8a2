"""Unified parallelism Plan + analytic auto-sharding planner
(docs/PERFORMANCE.md §Plan & planner).

Four invariants:
  1. the five legacy strategy entry points (dp kwargs, ShardingRules tp,
     pipeline, ring, ulysses) produce Plans whose compiled step is
     BITWISE identical to the pre-refactor kwargs path on the same mesh
     (same mesh => same program; cross-mesh comparisons keep the
     documented ~1e-3 GSPMD tolerance of test_parallel);
  2. the planner's cost model is hand-checkable: on the three synthetic
     fixtures (dp-wins, tp-wins, memory-forces-sharding) it ranks the
     known-optimal layout first, with every cost term matching the
     closed-form formulas;
  3. every enumerated Plan is LEGAL (axes exist, specs divide shapes,
     stages divide layers, batch divides over dp) and serializes
     losslessly;
  4. elastic reshard works THROUGH the Plan path.
"""
import glob
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.parallel import (DataParallelStep, Plan,
                                compile_step_with_plan, dp_plan, local_mesh,
                                make_mesh, pipeline_plan, ring_plan,
                                tensor_parallel_plan, ulysses_plan)
from mxnet_tpu.parallel import planner
from mxnet_tpu.parallel.planner import Hardware, ModelSignature
from mxnet_tpu.parallel.sharding import ShardingRules


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


def _events(tele):
    tele.flush()
    return [json.loads(line)
            for f in glob.glob(os.path.join(tele.summary()["dir"],
                                            "rank-*.jsonl"))
            for line in open(f)]


# ---------------------------------------------------------------------------
# Plan dataclass: validation, serialization, factories
# ---------------------------------------------------------------------------
def test_plan_validation():
    with pytest.raises(MXNetError):   # duplicate axis
        Plan(mesh_axes=(("dp", 2), ("dp", 2)))
    with pytest.raises(MXNetError):   # axis size < 1
        Plan(mesh_axes=(("dp", 0),))
    with pytest.raises(MXNetError):   # unknown batch axis
        Plan(mesh_axes=(("dp", 2),), batch_axes=("nope",))
    with pytest.raises(MXNetError):   # bad seq_axis
        Plan(mesh_axes=(("dp", 2),), batch_axes=("dp",), seq_axis=2)
    with pytest.raises(MXNetError):   # bad sp mode
        Plan(mesh_axes=(("dp", 2),), batch_axes=("dp",),
             sp_attention="bogus")
    with pytest.raises(MXNetError):   # ring without an sp axis
        Plan(mesh_axes=(("dp", 2),), batch_axes=("dp",),
             sp_attention="ring")
    with pytest.raises(MXNetError):
        Plan(mesh_axes=(("dp", 2),), batch_axes=("dp",), accum_steps=0)
    with pytest.raises(MXNetError):
        Plan(mesh_axes=(("dp", 2),), batch_axes=("dp",),
             pp_microbatches=0)


def test_plan_factories_and_roundtrip():
    from mxnet_tpu.models.bert import bert_sharding_rules

    plans = {
        "dp": dp_plan(n_devices=8),
        "tp": tensor_parallel_plan(bert_sharding_rules(), tp=2,
                                   n_devices=8),
        "pp": pipeline_plan(2, microbatches=2, n_devices=8),
        "ring": ring_plan(2, n_devices=8),
        "ulysses": ulysses_plan(2, n_devices=8),
    }
    assert plans["dp"].strategy == "dp"
    assert plans["tp"].strategy == "dp+tp"
    assert plans["pp"].strategy == "dp+pp"
    assert plans["ring"].strategy == "dp+ring"
    assert plans["ulysses"].strategy == "dp+ulysses"
    for name, p in plans.items():
        assert p.n_devices == 8, name
        rt = Plan.from_json(json.loads(json.dumps(p.to_json())))
        assert rt == p, name   # lossless through REAL json text
    # the sharding rules survive the round trip functionally
    rt = Plan.from_json(plans["tp"].to_json())
    spec = rt.rules.spec_for("encoder0_qkv_weight", 2)
    assert spec == plans["tp"].rules.spec_for("encoder0_qkv_weight", 2)
    # predicted never participates in identity
    assert plans["dp"].with_predicted({"step_s": 1.0}) == plans["dp"]
    # an explicitly-empty batch_axes (a mesh with no dp/sp axes) must
    # round-trip as empty, not regrow the default (review finding)
    empty = Plan(mesh_axes=(("batch", 2),), batch_axes=())
    assert Plan.from_json(empty.to_json()).batch_axes == ()
    # rules hash follows rules equality through the to_json
    # normalization (list vs tuple spec entries; review finding)
    a = ShardingRules([(r"w", (None, ["dp", "tp"]))])
    b = ShardingRules([(r"w", (None, ("dp", "tp")))])
    assert a == b and hash(a) == hash(b)
    hash(plans["tp"])  # frozen Plans embedding rules stay hashable


def test_plan_and_kwargs_clash_rejected():
    net = nn.Dense(2)
    net.initialize(mx.init.Xavier())
    with pytest.raises(MXNetError):
        DataParallelStep(net, gluon.loss.L2Loss(), plan=dp_plan(n_devices=8),
                         accum_steps=2)
    with pytest.raises(MXNetError):   # plan/mesh mismatch
        import jax

        compile_step_with_plan(
            net, gluon.loss.L2Loss(), dp_plan(n_devices=8),
            mesh=local_mesh(devices=jax.devices("cpu")[:4]))


# ---------------------------------------------------------------------------
# shim parity: each legacy entry point vs its Plan on the SAME mesh
# ---------------------------------------------------------------------------
def _dense_net():
    mx.random.seed(0)
    np.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(mx.init.Xavier())
    return net


def _weights(step):
    import jax

    return {n.split("_", 1)[-1]: np.asarray(jax.device_get(a))
            for n, a in step.params.items()}


def _run_steps(step, n=3, b=8, d=6):
    mx.random.seed(1)
    rng = np.random.RandomState(0)
    X = rng.rand(b, d).astype(np.float32)
    Y = rng.rand(b, 4).astype(np.float32)
    return [float(np.asarray(step.step(nd.array(X), nd.array(Y))))
            for _ in range(n)]


def test_dp_shim_parity_bitwise():
    """Legacy kwargs construction vs compile_step_with_plan(dp_plan) on
    the same 8-device mesh: bitwise losses and weights."""
    legacy = DataParallelStep(_dense_net(), gluon.loss.L2Loss(),
                              mesh=local_mesh(), optimizer="sgd",
                              optimizer_params={"learning_rate": 0.1,
                                                "momentum": 0.9})
    planned = compile_step_with_plan(
        _dense_net(), gluon.loss.L2Loss(), dp_plan(n_devices=8),
        mesh=local_mesh(), optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert _run_steps(legacy) == _run_steps(planned)
    wl, wp = _weights(legacy), _weights(planned)
    for k in wl:
        np.testing.assert_array_equal(wl[k], wp[k])
    # the legacy constructor built the equivalent Plan internally
    assert legacy.plan.strategy == planned.plan.strategy == "dp"


def _bert_net_for_plan():
    from mxnet_tpu.models import bert_small

    mx.random.seed(0)
    net = bert_small(dropout=0.0)
    net.initialize(mx.init.Normal(0.02))
    return net


def _mlm_loss():
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(logits, labels):
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1))

    return mlm_loss


def _bert_step(mesh, **kw):
    from mxnet_tpu.models.bert import bert_sharding_rules

    net = _bert_net_for_plan()
    kw.setdefault("rules", bert_sharding_rules())
    return DataParallelStep(net, _mlm_loss(), mesh=mesh, optimizer="adam",
                            optimizer_params={"learning_rate": 1e-3}, **kw)


def _bert_losses(step, n=2):
    mx.random.seed(1)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 512, (4, 16)).astype(np.int32)
    return [float(np.asarray(step.step(nd.array(tokens, dtype="int32"),
                                       nd.array(tokens.astype(np.float32)))))
            for _ in range(n)]


def test_tp_shim_parity_bitwise():
    """ShardingRules tp strategy: legacy rules= kwarg vs
    tensor_parallel_plan on the same dp2 x tp2 mesh — bitwise, and the
    qkv weights carry the tp sharding either way."""
    import jax

    from mxnet_tpu.models.bert import bert_sharding_rules

    devices = jax.devices("cpu")[:4]
    mesh = make_mesh(tp=2, devices=devices)
    legacy = _bert_step(mesh)
    plan = tensor_parallel_plan(bert_sharding_rules(), tp=2, dp=2)
    planned = compile_step_with_plan(
        _bert_net_for_plan(), _mlm_loss(), plan, mesh=mesh,
        optimizer="adam", optimizer_params={"learning_rate": 1e-3})
    assert _bert_losses(legacy) == _bert_losses(planned)
    qkv = [n for n in planned.params if n.endswith("qkv_weight")]
    assert qkv and "tp" in str(planned.params[qkv[0]].sharding.spec)
    assert legacy.plan.strategy == planned.plan.strategy == "dp+tp"


def test_ring_and_ulysses_shim_parity_bitwise():
    """ring/ulysses SP strategies: legacy ring_attention= kwarg vs
    ring_plan/ulysses_plan on the same dp2 x sp2 mesh — bitwise."""
    import jax

    from mxnet_tpu.models.bert import bert_sharding_rules

    devices = jax.devices("cpu")[:4]
    for mode, factory in (("ring", ring_plan), ("ulysses", ulysses_plan)):
        mesh = make_mesh(sp=2, devices=devices)
        legacy = _bert_step(mesh, ring_attention=(True if mode == "ring"
                                                  else "ulysses"))
        plan = factory(2, dp=2, rules=bert_sharding_rules())
        planned = compile_step_with_plan(
            _bert_net_for_plan(), _mlm_loss(), plan, mesh=mesh,
            optimizer="adam", optimizer_params={"learning_rate": 1e-3})
        assert _bert_losses(legacy) == _bert_losses(planned), mode
        assert planned.plan.sp_attention == mode
        assert legacy.plan.sp_attention == mode  # shimmed equivalently


def test_pp_shim_parity_bitwise():
    """pipeline strategy: legacy pp_microbatches kwarg vs pipeline_plan
    on the same dp2 x pp2 mesh — bitwise (the pp scope activates either
    way; a non-stacked model duplicates dp work across pp, which is
    exactly what the pre-refactor path did)."""
    import jax

    devices = jax.devices("cpu")[:4]
    mesh = make_mesh(pp=2, devices=devices)
    legacy = DataParallelStep(_dense_net(), gluon.loss.L2Loss(),
                              mesh=mesh, optimizer="sgd",
                              pp_microbatches=2,
                              optimizer_params={"learning_rate": 0.1})
    planned = compile_step_with_plan(
        _dense_net(), gluon.loss.L2Loss(),
        pipeline_plan(2, microbatches=2, dp=2), mesh=mesh,
        optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    assert _run_steps(legacy) == _run_steps(planned)
    assert legacy.plan.pp_microbatches == planned.plan.pp_microbatches == 2
    assert legacy.plan.strategy == planned.plan.strategy == "dp+pp"


# ---------------------------------------------------------------------------
# planner cost fixtures: hand-computed, known-optimal layouts
# ---------------------------------------------------------------------------
_HW = Hardware(flops_per_device=1e12, ici_bw=1e11, opt_slots=2.0)


def test_planner_dp_wins_fixture():
    """Tiny params, fat activations: the dp grad allreduce is ~free and
    anything that shards activations pays collective volume — pure dp
    must rank first, and every cost term matches the formulas."""
    sig = ModelSignature(param_shapes={"w": (16, 16)},
                         batch_shape=(64, 8),
                         flops_per_step=1e9, act_bytes=1e6)
    ranked = planner.enumerate_plans(sig, 2, hw=_HW)
    assert ranked, "nothing legal"
    best = ranked[0]
    assert best.plan.strategy == "dp"
    # hand-check: P = 16*16*4 = 1024 B; dp2 allreduce 2*(1/2)*1024/bw
    dp_cost = best.cost
    assert dp_cost["comm"]["dp"] == pytest.approx(
        2 * 0.5 * 1024 / 1e11)
    assert dp_cost["compute_s"] == pytest.approx(1e9 / (2 * 1e12))
    # the sp2 candidate pays activation collectives instead: 4*(1/2)*
    # (1e6/2)/bw — three orders of magnitude worse
    sp = [c for c in ranked if c.plan.axis_size("sp") == 2]
    assert sp and sp[0].cost["comm"]["sp"] == pytest.approx(
        4 * 0.5 * (1e6 / 2) / 1e11)
    assert sp[0].step_s > best.step_s


def test_planner_tp_wins_fixture():
    """Huge tp-shardable params, tiny activations: replicating the
    params makes the dp grad allreduce the bottleneck; tp shards it
    away — tp must rank first."""
    rules = ShardingRules([(r"w", (None, "tp"))])
    sig = ModelSignature(param_shapes={"w": (4096, 4096)},
                         batch_shape=(8,), rules=rules,
                         flops_per_step=1e9, act_bytes=1024.0)
    P = 4096 * 4096 * 4
    ranked = planner.enumerate_plans(sig, 2, hw=_HW)
    assert ranked[0].plan.strategy == "tp"
    tp_cost = ranked[0].cost
    assert tp_cost["comm"]["tp"] == pytest.approx(4 * 0.5 * 1024 / 1e11)
    dp = [c for c in ranked if c.plan.axis_size("dp") == 2][0]
    assert dp.cost["comm"]["dp"] == pytest.approx(2 * 0.5 * P / 1e11)
    assert dp.step_s > ranked[0].step_s
    # chosen plan carries the rules so compile_step_with_plan shards
    assert ranked[0].plan.rules.spec_for("w", 2) is not None


def test_planner_memory_forces_sharding_fixture():
    """dp would be fastest but replicated params + optimizer slots blow
    the per-device budget; only the tp layout fits — the planner must
    rank it first even at a worse predicted step time."""
    rules = ShardingRules([(r"w", (None, "tp"))])
    P = 1024 * 1024 * 4                        # 4 MiB params
    # act = P: dp's param allreduce (2*(1/2)*P) beats tp's activation
    # collectives (4*(1/2)*P) on SPEED — only memory forces tp
    sig = ModelSignature(param_shapes={"w": (1024, 1024)},
                         batch_shape=(8,), rules=rules,
                         flops_per_step=1e12, act_bytes=float(P))
    hw = Hardware(flops_per_device=1e12, ici_bw=1e11, opt_slots=2.0,
                  mem_per_device=3.2 * P)
    ranked = planner.enumerate_plans(sig, 2, hw=hw)
    best = ranked[0]
    assert best.plan.strategy == "tp"
    assert best.cost["mem_ok"]
    # tp: (2 + opt_slots) * P/2 + full acts (dp=1) = 2P + P = 3P fits
    assert best.cost["mem_bytes"] == pytest.approx(3 * P)
    dp = [c for c in ranked if c.plan.axis_size("dp") == 2][0]
    assert not dp.cost["mem_ok"]
    # dp=2 halves the activation share but still replicates all 4P of
    # param+grad+slots state: 4P + P/2 > 3.2P budget
    assert dp.cost["mem_bytes"] == pytest.approx(4 * P + P / 2)
    # ...and dp IS the faster plan: memory is the only forcer
    assert dp.step_s < best.step_s
    unbounded = Hardware(flops_per_device=1e12, ici_bw=1e11,
                         opt_slots=2.0)
    assert planner.enumerate_plans(
        sig, 2, hw=unbounded)[0].plan.strategy == "dp"


def test_planner_pp_bubble_and_legality():
    """pp plans only appear when stacked layers divide, and the bubble
    factor (M + pp - 1)/M lands in the compute term."""
    sig = ModelSignature(param_shapes={"w": (64, 64)},
                         batch_shape=(16,), stacked_layers=4,
                         flops_per_step=1e9, act_bytes=1e3)
    ranked = planner.enumerate_plans(sig, 4, hw=_HW, microbatches=4)
    pp = [c for c in ranked if c.plan.axis_size("pp") == 4]
    assert pp, "pp4 divides 4 stacked layers — must be enumerated"
    assert pp[0].cost["bubble"] == pytest.approx((4 + 4 - 1) / 4)
    assert pp[0].cost["compute_s"] == pytest.approx(
        1e9 / (4 * 1e12) * (7 / 4))
    # 3 layers: pp=4 and pp=2 both illegal (no divisibility)
    sig3 = ModelSignature(param_shapes={"w": (64, 64)},
                          batch_shape=(16,), stacked_layers=3,
                          flops_per_step=1e9, act_bytes=1e3)
    assert not any(c.plan.axis_size("pp") > 1
                   for c in planner.enumerate_plans(sig3, 4, hw=_HW))


def test_enumerated_plans_are_legal_property():
    """Property sweep: every enumerated plan of every random signature
    is structurally legal and serializes losslessly."""
    rng = np.random.RandomState(7)
    for trial in range(12):
        n = int(rng.choice([2, 4, 6, 8, 12]))
        batch = int(rng.choice([4, 6, 8, 16, 24]))
        seq = int(rng.choice([0, 4, 8, 12]))
        layers = int(rng.choice([0, 2, 3, 4, 8]))
        dim = int(rng.choice([8, 12, 16]))
        rules = (ShardingRules([(r".*w.*", (None, "tp"))])
                 if rng.rand() < 0.7 else None)
        sig = ModelSignature(
            param_shapes={"w1": (dim, dim), "w2": (dim, dim), "b": (dim,)},
            batch_shape=(batch, seq) if seq else (batch,),
            stacked_layers=layers or None, rules=rules)
        for choice in planner.enumerate_plans(sig, n, hw=_HW):
            plan, cost = choice.plan, choice.cost
            dp, tp = plan.axis_size("dp"), plan.axis_size("tp")
            pp, sp = plan.axis_size("pp"), plan.axis_size("sp")
            assert dp * tp * pp * sp == n
            assert batch % dp == 0
            if sp > 1:
                assert seq and seq % sp == 0
            if pp > 1:
                assert layers and layers % pp == 0
                assert (batch // dp) % plan.pp_microbatches == 0
            if tp > 1:
                assert rules is not None
                for name, shape in sig.param_shapes.items():
                    spec = tuple(plan.rules.spec_for(name, len(shape)))
                    for i, entry in enumerate(spec):
                        if entry == "tp" or (isinstance(entry, tuple)
                                             and "tp" in entry):
                            assert shape[i] % tp == 0, (name, shape, tp)
            assert cost["step_s"] > 0 and cost["mem_bytes"] > 0
            assert Plan.from_json(plan.to_json()) == plan


def test_plan_for_override_and_errors(monkeypatch):
    rules = ShardingRules([(r"w", (None, "tp"))])
    # fat activations: dp (param allreduce only) is the auto argmin
    sig = ModelSignature(param_shapes={"w": (64, 64)}, batch_shape=(16, 8),
                         rules=rules, stacked_layers=2,
                         flops_per_step=1e9, act_bytes=1e6)
    # auto: argmin (tiny params -> dp)
    monkeypatch.delenv("MX_PLAN", raising=False)
    assert planner.plan_for(sig, 4, hw=_HW).strategy == "dp"
    # env override pins the family even when dp ranks first
    monkeypatch.setenv("MX_PLAN", "tp")
    chosen = planner.plan_for(sig, 4, hw=_HW)
    assert chosen.axis_size("tp") > 1
    assert chosen.predicted["override"] == "tp"
    monkeypatch.setenv("MX_PLAN", "pp")
    assert planner.plan_for(sig, 4, hw=_HW,
                            microbatches=2).axis_size("pp") > 1
    monkeypatch.setenv("MX_PLAN", "ring")
    ring = planner.plan_for(sig, 4, hw=_HW)
    assert ring.axis_size("sp") > 1 and ring.sp_attention == "ring"
    monkeypatch.setenv("MX_PLAN", "ulysses")
    assert planner.plan_for(sig, 4, hw=_HW).sp_attention == "ulysses"
    # arg beats env; bogus value is loud
    assert planner.plan_for(sig, 4, hw=_HW, strategy="dp").strategy == "dp"
    monkeypatch.setenv("MX_PLAN", "bogus")
    with pytest.raises(MXNetError):
        planner.plan_for(sig, 4, hw=_HW)
    # no legal layout at all is loud too (batch 5 over 4 devices, dp
    # required but not divisible in any factorization using dp>1; tp
    # variants are capped by w's 64-dim? no — 5 % dp blocks dp>1 and
    # sp needs seq... tp4 IS legal, so use a rule-less sig)
    sig_bad = ModelSignature(param_shapes={"w": (64, 64)},
                             batch_shape=(5,), flops_per_step=1e9,
                             act_bytes=1e3)
    with pytest.raises(MXNetError):
        planner.plan_for(sig_bad, 4, hw=_HW)
    # the predicted ranking rides on the chosen plan
    monkeypatch.delenv("MX_PLAN", raising=False)
    best = planner.plan_for(sig, 4, hw=_HW)
    assert best.predicted["ranking"][0]["strategy"] == best.strategy
    assert best.predicted["step_s"] > 0


def test_signature_of_block():
    net = _dense_net()
    # materialize deferred-init shapes (in_units comes from data)
    net(nd.array(np.zeros((8, 6), np.float32)))
    sig = planner.signature_of(net, (8, 6))
    assert sig.param_shapes and sig.batch == 8
    assert sig.flops_per_step > 0 and sig.act_bytes > 0
    # matmul params only contribute to the 6ND flops estimate
    mats = sum(1 for s in sig.param_shapes.values() if len(s) >= 2)
    assert mats >= 2


# ---------------------------------------------------------------------------
# plan telemetry event
# ---------------------------------------------------------------------------
def test_plan_telemetry_event(tele):
    sig = ModelSignature(param_shapes={"w": (16, 16)}, batch_shape=(8, 4),
                         flops_per_step=1e9, act_bytes=1e3)
    plan = planner.plan_for(sig, 1, hw=_HW)
    import jax

    step = compile_step_with_plan(
        _dense_net(), gluon.loss.L2Loss(), plan,
        mesh=local_mesh(devices=[jax.devices("cpu")[0]]),
        optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    _run_steps(step, n=1)
    evs = [e for e in _events(tele) if e.get("kind") == "plan"]
    assert len(evs) == 1
    ev = evs[0]
    assert ev["strategy"] == plan.strategy
    assert ev["plan"]["mesh_axes"] == [[n, s] for n, s in plan.mesh_axes]
    # predicted costs ride along for the trace_report predicted-vs-
    # measured comparison
    assert ev["predicted"]["step_s"] > 0
    assert ev["predicted"]["ranking"]
    # and the step events to compare against are in the same stream
    assert any(e.get("kind") == "step" for e in _events(tele))


# ---------------------------------------------------------------------------
# platform features THROUGH the Plan path
# ---------------------------------------------------------------------------
def test_elastic_reshard_through_plan_path(tele):
    """state_dict from a dp2 plan-built step restores onto a dp4
    plan-built step (reshard), the layout round-trips the Plan, and the
    restored weights are bitwise the saved ones."""
    import jax

    devices = jax.devices("cpu")

    def build(ndev):
        return compile_step_with_plan(
            _dense_net(), gluon.loss.L2Loss(), dp_plan(n_devices=ndev),
            mesh=local_mesh(devices=devices[:ndev]),
            optimizer="sgd", optimizer_params={"learning_rate": 0.1,
                                               "momentum": 0.9})

    src = build(2)
    _run_steps(src, n=2)
    state, layout = src.state_dict(), src.layout()
    assert Plan.from_json(layout["plan"]) == src.plan

    dst = build(4)
    info = dst.load_state_dict(state, saved_layout=layout)
    assert info["resharded"]
    for k, v in _weights(src).items():
        np.testing.assert_array_equal(v, _weights(dst)[k])
    # and training continues through the plan path on the new mesh
    assert np.isfinite(_run_steps(dst, n=1)[0])
