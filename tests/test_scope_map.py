"""The step accounts for its device time by the program's own scopes (PR 35):
``Block.__call__`` names a traced block's ops, ``DataParallelStep`` adds
``mx_loss`` / ``mx_update`` and hands out the scope of each instruction it
compiled (``scope_map``), on ask only, and none of it changes a number."""
import contextlib
import gc
import hashlib
import weakref

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from benchmark import loader
from benchmark.drivers import train_job
from mxnet_tpu import hlo_scopes, telemetry

BENCH = loader.load_benchmark()
CELLS = {"bert": "bert_base_mlm.train_1chip",
         "nemotron": "nemotron_twotower_30b_a3b.train_2x8k",
         "zaya": "zaya1_8b.train_1x8k",
         "xing": "xing4_0_29b_a4b.train_1x4k"}
TRIVIAL = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


def _job(name, seed=5):
    cell = loader.Cell(BENCH, CELLS[name], rehearse=True)
    return train_job.Job(cell, seed, mx.cpu(), jax.devices()[:1])


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# ---------------------------------------------------------------- the rules
HLO = """HloModule jit_step, is_scheduled=true

%fused_adam (p0: f32[8,8], p1: f32[4,8]) -> (f32[8,8], f32[8,8]) {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[4,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(f32[4,8]{1,0} %p1, f32[4,8]{1,0} %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/transpose(jvp(Net.net0_))/jvp(Net.net0_)/checkpoint/Layer.l1_/Dense.up_/dot_general"}
  %m.1 = f32[8,8]{1,0} multiply(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %dot.1), metadata={op_name="jit(step)/mx_update/mul"}
  %v.1 = f32[8,8]{1,0} add(f32[8,8]{1,0} %p0, f32[8,8]{1,0} %dot.1), metadata={op_name="jit(step)/mx_update/add"}
  ROOT %tuple.9 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(f32[8,8]{1,0} %m.1, f32[8,8]{1,0} %v.1)
}

%add_reducer (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.r = f32[] add(f32[] %a, f32[] %b)
}

%body (arg: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {
  %arg = (s32[], f32[4,8]{1,0}) parameter(0)
  %gte.1 = f32[4,8]{1,0} get-tuple-element((s32[], f32[4,8]{1,0}) %arg), index=1
  %exp.7 = f32[4,8]{1,0} exponential(f32[4,8]{1,0} %gte.1), metadata={op_name="jit(step)/jvp(Net.net0_)/Layer.l1_/Experts.moe_/mx_moe_experts/while/body/exp"}
  ROOT %tuple.3 = (s32[], f32[4,8]{1,0}) tuple(s32[] %gte.0, f32[4,8]{1,0} %exp.7)
}

%cond (arg.c: (s32[], f32[4,8])) -> pred[] {
  %arg.c = (s32[], f32[4,8]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] compare(s32[] %c0, s32[] %c1), direction=LT
}

ENTRY %main.42 (x: f32[4,8], w: f32[8,8]) -> f32[8,8] {
  %x = f32[4,8]{1,0:T(4,128)} parameter(0)
  %w = f32[8,8]{1,0:T(8,128)} parameter(1)
  %while.5 = (s32[], f32[4,8]{1,0}) while((s32[], f32[4,8]{1,0}) %t0), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(Net.net0_)/Layer.l1_/Experts.moe_/mx_moe_experts/while"}
  %reduce.2 = f32[] reduce(f32[4,8]{1,0} %x, f32[] %zero), dimensions={0,1}, to_apply=%add_reducer, metadata={op_name="jit(step)/jvp(mx_loss)/reduce_sum"}
  %remat.3 = f32[4,8]{1,0} exponential(f32[4,8]{1,0} %x), metadata={op_name="jit(step)/transpose(jvp(Net.net0_))/jvp(Net.net0_)/checkpoint/rematted_computation/Layer.l1_/Dense.up_/exp"}
  %copy.4 = f32[4,8]{0,1} copy(f32[4,8]{1,0} %x)
  %copy.6 = f32[4,8]{0,1} copy(f32[4,8]{1,0} %remat.3)
  %zeros.7 = f32[8,8]{1,0} broadcast(f32[] %zero), dimensions={}
  %while.8 = (s32[], f32[4,8]{1,0}) while((s32[], f32[4,8]{1,0}) %t1), condition=%cond, body=%body
  %key.9 = u32[2]{0} add(u32[2]{0} %k, u32[2]{0} %k), metadata={op_name="jit(step)/add"}
  ROOT %fusion.9 = (f32[8,8]{1,0}, f32[8,8]{1,0}) fusion(f32[8,8]{1,0} %w, f32[4,8]{1,0} %x, f32[8,8]{1,0} %zeros.7), kind=kOutput, calls=%fused_adam, metadata={op_name="jit(step)/transpose(jvp(Net.net0_))/jvp(Net.net0_)/checkpoint/Layer.l1_/Dense.up_/dot_general"}
}
"""


def test_the_rules_on_a_small_module():
    m = hlo_scopes.scope_map_of(HLO)
    # a fusion takes its ROOT's scope (a tuple's first element), and names
    # the block of the product inside it that lies elsewhere
    assert m["fusion.9"] == {
        "scope": "mx_update", "block": "", "dir": "fwd", "entry": True,
        "mixed": "Net.net0_/Layer.l1_/Dense.up_"}
    # a while is one ENTRY event; its body's instructions are not ENTRY
    assert m["while.5"]["entry"] and not m["exp.7"]["entry"]
    assert m["exp.7"]["scope"] == "Net.net0_/Layer.l1_/Experts.moe_/mx_moe_experts"
    assert m["exp.7"]["block"] == "Experts.moe_"
    # the second forward lies INSIDE a transpose( and is asked first
    assert m["remat.3"]["dir"] == "remat" and m["reduce.2"]["dir"] == "fwd"
    assert m["reduce.2"]["scope"] == "mx_loss"
    # XLA's own instructions (no op_name) take a neighbour's scope: the
    # operand's, a loop its body's, zeros their user's; with no scoped
    # neighbour, and where the PROGRAM wrote outside every scope, none
    assert (m["copy.6"]["scope"], m["copy.6"]["dir"]) == (
        "Net.net0_/Layer.l1_/Dense.up_", "remat")
    assert m["zeros.7"]["scope"] == "mx_update"
    assert m["while.8"]["scope"] == m["exp.7"]["scope"]
    assert m["copy.4"]["scope"] == "" and m["copy.4"]["entry"]
    assert m["key.9"]["scope"] == ""
    # fused and reducer instructions are never events and are left out
    assert not {"dot.1", "m.1", "add.r"} & set(m)


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(M.m0_)/L.l3_/A.attn_/mx_front/Dense.q_/dot_general",
     ("M.m0_/L.l3_/A.attn_/mx_front/Dense.q_", "Dense.q_", "fwd")),
    ("jit(step)/transpose(jvp(M.m0_))/jvp(M.m0_)/checkpoint/L.l3_/mul",
     ("M.m0_/L.l3_", "L.l3_", "bwd")),
    ("jit(step)/transpose(jvp(M.m0_))/jvp(M.m0_)/checkpoint/"
     "rematted_computation/L.l3_/jit(silu)/exp",
     ("M.m0_/L.l3_", "L.l3_", "remat")),
    ("jit(step)/transpose(jvp(mx_loss))/mul;jit(step)/mx_update/add",
     ("mx_loss", "", "bwd")),
    ("jit(step)/mx_update/bld,vd->blv/dot_general", ("mx_update", "", "fwd")),
    ("", ("", "", "fwd")),
])
def test_an_op_name_gives_its_scope_block_and_direction(op_name, want):
    assert hlo_scopes.scope_of(op_name) == want


# ------------------------------------------------- the four programs' maps
def _compiled(step):
    """(the text scope_map parsed, the map)."""
    texts, parse = [], hlo_scopes.scope_map_of
    hlo_scopes.scope_map_of = lambda t: (texts.append(t), parse(t))[1]
    try:
        return texts, step.scope_map()
    finally:
        hlo_scopes.scope_map_of = parse


@pytest.mark.parametrize("name, dirs", [
    ("bert", {"fwd", "bwd"}), ("nemotron", {"fwd", "remat", "bwd"}),
    ("zaya", {"fwd", "remat", "bwd"}), ("xing", {"fwd", "remat", "bwd"})])
def test_every_product_of_the_step_lies_under_a_scope(name, dirs):
    job = _job(name)
    job.call()
    job.close()
    (text,), scopes = _compiled(job.step)
    comps, entry = hlo_scopes._parse(text)
    products, found = 0, set()
    for instr, opcode, _op, _root, line in comps[entry]:
        if opcode in TRIVIAL:
            continue
        row = scopes[instr]
        assert row["entry"]
        called = dict(hlo_scopes._CALLED.findall(line)).get("calls")
        inside = [o for _n, o, *_ in comps.get(called, ())]
        if opcode in hlo_scopes._PRODUCTS or set(inside) & set(
                hlo_scopes._PRODUCTS):
            products += 1
            assert row["scope"], (instr, line[:200])
        if row["scope"]:
            found.add(row["dir"])
    assert products > 10 and found == dirs
    kinds = {r["scope"].split("/")[-1] for r in scopes.values()}
    assert {"mx_loss", "mx_update"} <= kinds
    assert any(r["scope"].endswith("mx_head") or "/mx_head/" in r["scope"]
               for r in scopes.values())
    # the Gluon hierarchy is the path: class and the name inside the parent
    top = type(job.net).__name__ + "." + job.net.prefix
    assert all(r["scope"].startswith(top) for r in scopes.values()
               if r["block"])
    job.free()


# ------------------------------------------------------------ on ask only
class _Counting:
    """The step's jitted function, counting what is asked of it."""

    def __init__(self, fn):
        self.fn, self.asked = fn, []

    def __call__(self, *args):
        return self.fn(*args)

    def trace(self, *args):
        self.asked.append("trace")
        return self.fn.trace(*args)

    def lower(self, *args):
        self.asked.append("lower")
        return self.fn.lower(*args)


def test_twenty_steps_build_no_map_and_the_ask_outlives_the_step():
    from jax._src import stages

    job = _job("zaya")
    job.call()
    counting = job.step._jitted = _Counting(job.step._jitted)
    lowered, forwards = [], []
    model = type(job.net)
    real_lower, hook = stages.Traced.lower, model.hybrid_forward
    stages.Traced.lower = lambda self, *a, **k: (
        lowered.append(1), real_lower(self, *a, **k))[1]
    model.hybrid_forward = lambda self, *a, **k: (
        forwards.append(1), hook(self, *a, **k))[1]
    try:
        for _ in range(19):
            job.call()
        job.close()
        assert telemetry.scope_map() == {} and job.step._scope_map is None
        assert counting.asked == [] and lowered == []
        net, step = weakref.ref(job.net), weakref.ref(job.step)
        job.free()                  # drain(): hands the means, not the map
        counting.fn = None
        gc.collect()
        # jax's own record of the traced call: the model is not traced a
        # second time, nothing is lowered, nothing of the step stays alive
        assert counting.asked == ["trace"] and lowered == []
        assert forwards == []
        assert net() is None and step() is None
        (executor, scopes), = telemetry.scope_map().items()     # the ask
        assert lowered == [1]
        assert executor.startswith("DataParallelStep:ZayaModel")
        assert sum(r["entry"] for r in scopes.values()) > 100
        assert telemetry.scope_map()[executor] is scopes and lowered == [1]
    finally:
        stages.Traced.lower = real_lower
        model.hybrid_forward = hook


def test_a_step_that_never_ran_has_no_map_and_eager_calls_no_scope():
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep

    net = nn.Dense(4, in_units=3, prefix="probe_")
    net.initialize()
    entered = []
    real = jax.named_scope
    jax.named_scope = lambda name: (entered.append(name), real(name))[1]
    try:
        net(nd.ones((2, 3)))
    finally:
        jax.named_scope = real
    assert entered == [] and net._trace_name == "Dense.probe_"
    step = DataParallelStep(net, lambda out, y: out.sum(axis=1) * 0 + 1.0)
    assert step.scope_map() == {} and telemetry.scope_map() == {}
    step.drain()
    assert telemetry.scope_map() == {}


# --------------------------------------------------- metadata and no more
# the PARENT's losses of three steps (commit d5c5949 on this box, float.hex;
# rehearsal presets at seed 5).  Its parameters are not pinned: their last
# bits follow the host's thread count, the losses do not.
PARENT = {
    "bert": ["0x1.3606100000000p+2", "0x1.35aa1c0000000p+2",
             "0x1.3523320000000p+2"],
    "xing": ["0x1.5896f00000000p+2", "0x1.57ec240000000p+2",
             "0x1.5741ae0000000p+2"],
}


def _three_steps(name):
    job = _job(name)
    losses = [float(job.call()) for _ in range(3)]
    job.close()
    digest = hashlib.sha256()
    for key in sorted(job.step.params):
        digest.update(np.asarray(job.step.params[key]).tobytes())
    job.free()
    return [x.hex() for x in losses], digest.hexdigest()


@pytest.mark.parametrize("name", ["bert", "xing"])
def test_three_steps_are_bitwise_the_parents(name):
    losses, params = _three_steps(name)
    assert losses == PARENT[name]
    # and, parameters too, bitwise what the same program gives with every
    # scope taken out (the parent's program: metadata alone differs)
    real, hook = jax.named_scope, mx.gluon.Block.trace_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    mx.gluon.Block.trace_scope = lambda self: contextlib.nullcontext()
    try:
        bare = _three_steps(name)
    finally:
        jax.named_scope, mx.gluon.Block.trace_scope = real, hook
    assert bare == (losses, params)
