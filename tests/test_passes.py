"""Pass pipeline (docs/PRECISION.md §Pass pipeline; ISSUE 20 acceptance).

Covers: pipeline construction/validation (unknown pass names raise
naming the registered set, duplicates rejected), the ONE-shared-
fingerprint contract (order, toggle, and config changes all split it;
AMP∘quant vs quant∘AMP are distinct programs), the bitwise-off
guarantee (a disabled pass contributes nothing to the signature OR the
traced jaxpr; ``wrap_apply`` is identity when nothing is enabled),
JSON round-trips through the checkpoint-layout shape, MX_PASSES /
MX_PALLAS_FUSED env semantics, AMP's backward-graph cast metadata
seam, fused-kernel substitution at the traced dispatch branch, the
weight-only int4 serving path (pack/dequant math, ≤0.16x weight bytes,
top-1 agreement vs the fp32 engine, fingerprint splits, env gate), and
training-side wiring
(``DataParallelStep`` fingerprints, ``layout()`` round-trip, the
``plan`` telemetry event's pass fingerprint).

jax.make_jaxpr caches by function identity + avals, so every bitwise
comparison here traces a FRESH closure per configuration (the ``mk()``
factories) — a shared closure would replay a stale jaxpr and mask
scope changes.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import memwatch, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn
from mxnet_tpu.models.transformer import Transformer, label_smoothed_ce
from mxnet_tpu.ndarray import NDArray
from mxnet_tpu.parallel import (DataParallelStep, compile_step_with_plan,
                                dp_plan, local_mesh)
from mxnet_tpu.passes import (AmpPass, FusedKernelPass, PassPipeline,
                              QuantizeInt4Pass, QuantizeInt8Pass,
                              apply_env_toggles, available_passes,
                              fused_kernels_from_env, hooks,
                              pipeline_for_serving, pipeline_for_training,
                              resolve_pass_type)
from mxnet_tpu.precision import (AmpPolicy, Int4WeightAdapter,
                                 LossScaleConfig, PrecisionConfig,
                                 int4_adapter, maybe_int4_adapter)
from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

PAD, BOS, EOS = 0, 1, 2
PREC = PrecisionConfig(amp=AmpPolicy(),
                       loss_scale=LossScaleConfig(init_scale=16.0,
                                                  growth_interval=4))


def _amp():
    return AmpPass(AmpPolicy())


def _q4(group=32):
    # live-enough entries ({} activates an empty quant_scope); the layer
    # signature stands in for the packed-weight digests
    return QuantizeInt4Pass({}, group, (("dense0", "aa" * 8),))


@pytest.fixture
def tele(tmp_path):
    telemetry.reset()
    telemetry.enable(str(tmp_path))
    yield telemetry
    telemetry.reset()


# ---------------------------------------------------------------------------
# registry + construction
# ---------------------------------------------------------------------------
def test_registered_pass_catalog():
    assert available_passes() == ["amp", "fused_kernels", "quant_int4",
                                  "quant_int8"]


def test_unknown_pass_name_raises_naming_registered_set():
    with pytest.raises(MXNetError) as ei:
        resolve_pass_type("quant_int5")
    msg = str(ei.value)
    assert "quant_int5" in msg
    for name in available_passes():
        assert name in msg
    # the JSON path and the env path fail the same way
    with pytest.raises(MXNetError, match="unknown graph pass"):
        PassPipeline.from_json([{"pass": "nope", "config": {}}])
    with pytest.raises(MXNetError, match="unknown graph pass"):
        apply_env_toggles(PassPipeline(), {"MX_PASSES": "-nope"})


def test_pipeline_rejects_duplicates_and_non_passes():
    with pytest.raises(MXNetError, match="duplicate pass"):
        PassPipeline([_q4(), _q4(16)])
    with pytest.raises(MXNetError, match="not a GraphPass"):
        PassPipeline([object()])
    with pytest.raises(MXNetError, match="policy"):
        AmpPass(None)


# ---------------------------------------------------------------------------
# ACCEPTANCE: ONE shared fingerprint — order, toggle, config all split it
# ---------------------------------------------------------------------------
def test_pipeline_fingerprint_splits_on_config_toggle_and_order():
    """The 4-way split (the test_precision fingerprint pattern, now at
    the pipeline layer): empty / amp / fused / amp+fused are four
    distinct fingerprints, AMP∘quant and quant∘AMP differ (order is
    identity — pass i sees the graph under passes 0..i-1), and a config
    change inside one pass (int4 group size) splits too."""
    pipes = [
        PassPipeline([]),
        PassPipeline([_amp()]),
        PassPipeline([FusedKernelPass()]),
        PassPipeline([_amp(), FusedKernelPass()]),
        PassPipeline([_amp(), _q4()]),
        PassPipeline([_q4(), _amp()]),      # order flip
        PassPipeline([_amp(), _q4(16)]),    # group-size config change
    ]
    fps = [p.fingerprint() for p in pipes]
    assert len(set(fps)) == len(fps), fps


def test_disabled_pass_is_absent_from_signature():
    amp_off = AmpPass(AmpPolicy(), enabled=False)
    assert (PassPipeline([amp_off, FusedKernelPass()]).signature()
            == PassPipeline([FusedKernelPass()]).signature())
    assert PassPipeline([amp_off]).signature() == ("passes",)
    # and toggling back on restores the full identity
    on = PassPipeline([amp_off]).set_enabled("amp", True)
    assert on.signature() == PassPipeline([_amp()]).signature()
    with pytest.raises(MXNetError, match="no pass named"):
        on.set_enabled("quant_int4", False)


def test_wrap_apply_identity_when_nothing_enabled():
    def f(params, key, x):
        return x, None

    assert PassPipeline([]).wrap_apply(f) is f
    assert PassPipeline(
        [AmpPass(AmpPolicy(), enabled=False)]).wrap_apply(f) is f


# ---------------------------------------------------------------------------
# bitwise-off at the dispatch point (fresh closures per trace!)
# ---------------------------------------------------------------------------
def _mk_ln(pipeline):
    """Fresh traced fn per call: residual-add+LayerNorm through the op
    dispatch point, under ``pipeline``'s scope."""
    gamma = nd.array(np.linspace(0.5, 1.5, 8).astype(np.float32))
    beta = nd.array(np.linspace(-0.1, 0.1, 8).astype(np.float32))

    def f(x, r):
        with pipeline.scope():
            out = nd.contrib.add_layer_norm(
                NDArray(x, ctx=mx.cpu()), NDArray(r, ctx=mx.cpu()),
                gamma, beta)
        return out._data

    return f


def test_fused_pass_substitutes_in_trace_and_is_bitwise_off():
    """ACCEPTANCE (fused kernels): under the pass the traced program is
    a different jaxpr (the Pallas kernel) that agrees numerically with
    the stock op; with the pass DISABLED the jaxpr is byte-identical to
    the no-pipeline trace — bitwise absent, not merely close."""
    import jax

    rng = np.random.RandomState(0)
    x = rng.randn(4, 8).astype(np.float32)
    r = rng.randn(4, 8).astype(np.float32)

    bare = str(jax.make_jaxpr(_mk_ln(PassPipeline([])))(x, r))
    off = str(jax.make_jaxpr(
        _mk_ln(PassPipeline([FusedKernelPass(enabled=False)])))(x, r))
    assert off == bare
    fused = str(jax.make_jaxpr(
        _mk_ln(PassPipeline([FusedKernelPass()])))(x, r))
    assert fused != bare

    want = jax.jit(_mk_ln(PassPipeline([])))(x, r)
    got = jax.jit(_mk_ln(PassPipeline([FusedKernelPass()])))(x, r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the dispatch hook state restored (no leak out of the scope)
    assert not hooks.active()


def test_amp_pass_parity_and_bitwise_off():
    """The amp pass traces the EXACT program the PR 15 module-global
    path (``apply_amp``) traces — absorbing it as a pass changed its
    identity, not its lowering.  Disabled, the wrapped apply is the
    bare-f32 program."""
    import jax

    from mxnet_tpu.precision.amp_pass import apply_amp

    rng = np.random.RandomState(1)
    w = rng.randn(4, 8).astype(np.float32)
    b = np.zeros(4, np.float32)
    x = rng.randn(3, 8).astype(np.float32)

    def mk():
        def apply(params, key, inp):
            out = nd.FullyConnected(
                NDArray(inp, ctx=mx.cpu()),
                NDArray(params["w"], ctx=mx.cpu()),
                NDArray(params["b"], ctx=mx.cpu()), num_hidden=4)
            return out._data, None

        return apply

    params = {"w": w, "b": b}

    def trace(fn):
        return str(jax.make_jaxpr(lambda p, v: fn(p, None, v))(params, x))

    policy = AmpPolicy()
    via_pass = trace(pipeline_for_training(
        PrecisionConfig(amp=policy), environ={}).wrap_apply(mk()))
    via_global = trace(apply_amp(mk(), policy))
    assert via_pass == via_global
    assert "bf16" in via_pass  # the cast actually happened

    bare = trace(mk())
    off = trace(PassPipeline(
        [AmpPass(policy, enabled=False)]).wrap_apply(mk()))
    assert off == bare
    assert "bf16" not in bare


# ---------------------------------------------------------------------------
# serialization: the checkpoint-layout JSON shape
# ---------------------------------------------------------------------------
def test_pipeline_json_roundtrip_preserves_identity():
    pipe = PassPipeline([_amp(), _q4(16), FusedKernelPass(enabled=False)])
    recs = json.loads(json.dumps(pipe.to_json()))
    back = PassPipeline.from_json(recs)
    assert back.signature() == pipe.signature()
    assert back.fingerprint() == pipe.fingerprint()
    assert back.names() == pipe.names()
    assert back.get("fused_kernels").enabled is False
    # a quant pass rebuilt from JSON is a DESCRIPTOR: same fingerprint,
    # but its twins' device buffers are gone — activating must raise,
    # not silently serve the fp32 graph under an int4 fingerprint
    with pytest.raises(MXNetError, match="descriptor"):
        with back.get("quant_int4").scope():
            pass


def test_metadata_never_enters_the_fingerprint():
    """Satellite: AMP publishes its backward-graph cast decisions as
    pass metadata (the future quantized-grads seam) — declarative facts
    only, no trace or fingerprint effect."""
    p = _amp()
    meta = p.metadata()["backward"]
    assert meta["grad_dtype"] == "bfloat16"
    assert "FullyConnected" in meta["low"]
    assert meta["widen"] and "cotangent" in meta["note"]
    pipe = PassPipeline([p])
    assert pipe.metadata()["amp"]["backward"] == meta
    # mutating what a consumer reads cannot move the fingerprint
    before = pipe.fingerprint()
    meta["low"].append("FakeOp")
    assert pipe.fingerprint() == before


# ---------------------------------------------------------------------------
# env surface
# ---------------------------------------------------------------------------
def test_mx_passes_toggles():
    pipe = PassPipeline([_amp(), FusedKernelPass()])
    apply_env_toggles(pipe, {"MX_PASSES": "-fused_kernels"})
    assert pipe.get("fused_kernels").enabled is False
    assert pipe.get("amp").enabled is True
    # a bare registered name is validated but (today) a no-op
    apply_env_toggles(pipe, {"MX_PASSES": "amp, -quant_int4"})
    assert pipe.get("amp").enabled is True
    assert pipe.signature() == PassPipeline([_amp()]).signature()


def test_mx_pallas_fused_env_semantics():
    assert fused_kernels_from_env({"MX_PALLAS_FUSED": "0"}) is None
    forced = fused_kernels_from_env({"MX_PALLAS_FUSED": "1"})
    assert isinstance(forced, FusedKernelPass)
    assert "_contrib_add_layer_norm" in forced._ops
    with pytest.raises(MXNetError, match="MX_PALLAS_FUSED"):
        fused_kernels_from_env({"MX_PALLAS_FUSED": "sometimes"})
    # auto on this CPU box: interpret-only kernels stay out of real runs
    assert fused_kernels_from_env({}) is None


def test_op_hook_nesting_restores():
    class H(hooks.OpHook):
        pass

    a, b = H(), H()
    assert not hooks.active()
    with hooks.op_hook(a):
        with hooks.op_hook(b):
            assert hooks._OP_HOOKS == (a, b)
        assert hooks._OP_HOOKS == (a,)
    assert not hooks.active()


# ---------------------------------------------------------------------------
# training wiring: DataParallelStep + plan telemetry
# ---------------------------------------------------------------------------
def _make_step(precision=None):
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(16, activation="relu", in_units=8),
            nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    from mxnet_tpu.gluon import loss as gloss

    loss_fn = gloss.SoftmaxCrossEntropyLoss()
    return DataParallelStep(
        net, lambda o, l: loss_fn(o, l), mesh=local_mesh(),
        optimizer="sgd", optimizer_params={"learning_rate": 0.1},
        precision=precision)


def test_training_pipeline_splits_step_fingerprint(monkeypatch):
    """The pipeline signature joins the step's fingerprint: amp
    on/off × fused on/off are four distinct executables."""
    sig = ((((16, 8), "float32"),), ((16,), "float32"))
    monkeypatch.delenv("MX_PALLAS_FUSED", raising=False)
    monkeypatch.delenv("MX_PASSES", raising=False)
    parts = [_make_step(None)._fingerprint_parts(sig),
             _make_step(PREC)._fingerprint_parts(sig)]
    monkeypatch.setenv("MX_PALLAS_FUSED", "1")
    parts += [_make_step(None)._fingerprint_parts(sig),
              _make_step(PREC)._fingerprint_parts(sig)]
    fps = [memwatch.fingerprint(p) for p in parts]
    assert len(set(fps)) == 4, fps


def test_step_layout_roundtrips_pipeline(monkeypatch):
    """Satellite: the pipeline rides the checkpoint layout — the JSON
    the step writes rebuilds a pipeline with the identical fingerprint
    (what a restore-side consistency check compares)."""
    monkeypatch.setenv("MX_PALLAS_FUSED", "1")
    monkeypatch.delenv("MX_PASSES", raising=False)
    step = _make_step(PREC)
    assert step._pipeline.names() == ["amp", "fused_kernels"]
    recs = json.loads(json.dumps(step.layout()["passes"]))
    assert (PassPipeline.from_json(recs).fingerprint()
            == step._pipeline.fingerprint())


def test_plan_event_carries_pass_fingerprint(tele, tmp_path):
    """Satellite: the ``plan`` telemetry event names the pass set and
    the shared fingerprint that names the step's executables."""
    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=8))
    net.initialize(mx.init.Xavier())
    from mxnet_tpu.gluon import loss as gloss

    loss_fn = gloss.SoftmaxCrossEntropyLoss()
    plan = dataclasses.replace(dp_plan(), precision=PREC)
    step = compile_step_with_plan(net, lambda o, l: loss_fn(o, l), plan)
    telemetry.flush()
    events = [json.loads(line)
              for line in open(telemetry.event_path(str(tmp_path), 0))]
    plans = [e for e in events if e["kind"] == "plan"]
    assert plans, [e["kind"] for e in events]
    assert plans[-1]["passes"] == ["amp"]
    assert plans[-1]["pass_fingerprint"] == step._pipeline.fingerprint()


# ---------------------------------------------------------------------------
# int4 math: pack -> in-trace dequantize
# ---------------------------------------------------------------------------
def test_int4_pack_dequantize_roundtrip():
    """Packing is exact over the nibble lattice: dequantize_int4
    reproduces q*scale bitwise, reconstruction error is bounded by half
    a quantization step per group, and a non-multiple input dim pads
    with exact zeros that the ``cols`` slice removes."""
    from mxnet_tpu.contrib.quantization import _quantize_weight_int4_np

    rng = np.random.RandomState(0)
    w = (rng.randn(8, 64) * 2).astype(np.float32)
    packed, scales, cols = _quantize_weight_int4_np(w, 32)
    assert packed.shape == (8, 32) and packed.dtype == np.uint8
    assert scales.shape == (8, 2) and scales.dtype == np.float16
    assert cols == 64

    back = nd.contrib.dequantize_int4(
        nd.array(packed, dtype=np.uint8),
        nd.array(scales, dtype=np.float16),
        group_size=32, cols=64).asnumpy()
    # manual nibble unpack (low nibble = even column, two's complement)
    lo = (packed & 0x0F).astype(np.int32)
    hi = (packed >> 4).astype(np.int32)
    lo = np.where(lo > 7, lo - 16, lo)
    hi = np.where(hi > 7, hi - 16, hi)
    q = np.stack([lo, hi], axis=-1).reshape(8, -1)
    assert np.abs(q).max() <= 7
    ref = (q.reshape(8, -1, 32).astype(np.float32)
           * scales.astype(np.float32)[..., None]).reshape(8, -1)
    np.testing.assert_array_equal(back, ref)
    # half-step error bound, per group
    step = scales.astype(np.float32)[..., None]
    err = np.abs((back - w).reshape(8, -1, 32))
    assert (err <= step * 0.5 + 1e-6).all()

    w2 = (rng.randn(4, 70)).astype(np.float32)
    p2, s2, c2 = _quantize_weight_int4_np(w2, 32)
    assert c2 == 70 and p2.shape == (4, 48)  # padded to 96 cols
    back2 = nd.contrib.dequantize_int4(
        nd.array(p2, dtype=np.uint8), nd.array(s2, dtype=np.float16),
        group_size=32, cols=70).asnumpy()
    assert back2.shape == (4, 70)


def test_int4_pack_validation():
    from mxnet_tpu.contrib.quantization import _quantize_weight_int4_np

    w = np.ones((4, 8), np.float32)
    with pytest.raises(MXNetError, match="even"):
        _quantize_weight_int4_np(w, 7)
    with pytest.raises(MXNetError, match="2-D"):
        _quantize_weight_int4_np(np.ones(8, np.float32), 4)


def test_int4_dense_twin_matches_manual_dequant_fc():
    """The Int4Dense lowering is exactly dequantize -> stock
    FullyConnected (+ activation) — one composition, eager-checked."""
    from mxnet_tpu.contrib.quantization import Int4Dense

    mx.random.seed(3)
    dense = nn.Dense(16, activation="relu", in_units=32)
    dense.initialize(mx.init.Xavier())
    imp = Int4Dense(dense, group_size=32)
    assert imp.nbytes < 0.16 * imp.orig_nbytes
    rng = np.random.RandomState(5)
    x = nd.array(rng.randn(3, 32).astype(np.float32))
    got = imp(x).asnumpy()
    w = nd.contrib.dequantize_int4(imp._packed, imp._scales,
                                   group_size=32, cols=imp._cols)
    want = nd.Activation(
        nd.FullyConnected(x, w, dense.bias.data(), num_hidden=16),
        act_type="relu").asnumpy()
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# ACCEPTANCE: weight-only int4 serving
# ---------------------------------------------------------------------------
def _reverse_batch(rng, B, L=6, vocab=16):
    src = np.zeros((B, L + 1), np.int32)
    tgt_in = np.zeros((B, L + 2), np.int32)
    tgt_out = np.zeros((B, L + 2), np.int32)
    for b in range(B):
        toks = rng.randint(3, vocab, L)
        src[b, :L] = toks
        rev = toks[::-1]
        tgt_in[b, 0] = BOS
        tgt_in[b, 1:L + 1] = rev
        tgt_out[b, :L] = rev
        tgt_out[b, L] = EOS
    return src, tgt_in, tgt_out


@pytest.fixture(scope="module")
def trained():
    """Reverse-task transformer (the test_serving recipe): sharp logits
    so greedy decode is decision-stable across the fp32 and int4
    executables.  units=32 and hidden=64 are multiples of the default
    group (32): no padding dilutes the weight-bytes ratio."""
    mx.random.seed(0)
    net = Transformer(16, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=20, dropout=0.0)
    net.initialize(mx.init.Xavier())
    rng = np.random.RandomState(2)
    src, tgt_in, tgt_out = _reverse_batch(rng, 8)
    step = DataParallelStep(
        net, lambda lo, la: label_smoothed_ce(lo, la, smoothing=0.0),
        mesh=local_mesh(devices=[mx.current_context().jax_device]),
        optimizer="adam", optimizer_params={"learning_rate": 5e-3})
    sb = nd.array(src, dtype="int32")
    tb = nd.array(tgt_in, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32))
    for _ in range(48):
        step.step((sb, tb), lb)
    step.sync_to_block()
    return net, src


def _serve(engine, src, n=6):
    reqs = [Request(src[i], max_new_tokens=9, bos_id=BOS, eos_id=EOS)
            for i in range(n)]
    out = engine.serve(reqs, arrival_steps=[0, 0, 0, 2, 5, 9][:n])
    return reqs, out


def test_int4_engine_weight_bytes_and_top1_agreement(trained):
    """ACCEPTANCE: the int4 rewrite holds ≤0.16x the fp32 bytes for the
    rewritten layers' weights (0.5625 bytes/weight at group 32) and the
    int4 engine's greedy decode agrees ≥0.99 top-1 with the fp32
    engine on the memorized reverse task."""
    net, src = trained
    eng32 = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=3,
                          page_size=4, max_len=12, stream_every=4)
    reqs32, out32 = _serve(eng32, src)

    qad = int4_adapter(TransformerAdapter(net, src_max_len=7))
    assert qad.precision == "int4"
    ratio = qad.quantized_weight_bytes() / qad.fp32_weight_bytes()
    assert ratio <= 0.16, ratio
    # whole-model accounting still counts f32 embeddings/norms
    assert qad.quantized_param_bytes() < qad.fp32_param_bytes()
    engq = ServingEngine(qad, slots=3, page_size=4, max_len=12,
                         stream_every=4)
    assert engq._pipeline.names() == ["quant_int4"]
    reqsq, outq = _serve(engq, src)

    agree, total = 0, 0
    for a, b in zip(reqs32, reqsq):
        ta, tb = list(out32[a.id]), list(outq[b.id])
        n = min(len(ta), len(tb))
        agree += sum(1 for i in range(n) if ta[i] == tb[i])
        total += max(len(ta), len(tb))
    assert total > 0
    assert agree / total >= 0.99, (agree, total)
    # solved, not just agreed upon
    for i, r in enumerate(reqsq[:3]):
        assert list(outq[r.id][:6]) == list(src[i, :6][::-1])
    # packed nibbles + scales are census-attributed device residency
    cats = memwatch.census()["categories"]
    assert "quantized" in cats, sorted(cats)
    assert cats["quantized"]["count"] >= len(qad._entries)


def test_int4_config_splits_engine_fingerprint(trained):
    """ACCEPTANCE: fp32 vs int4 vs a different MX_QUANT_GROUP are three
    distinct fingerprints, while re-packing the same weights at the
    same group reproduces the SAME fingerprint (the restart-stability
    half of the contract)."""
    net, src = trained
    mk = lambda ad: ServingEngine(ad, slots=2, page_size=4, max_len=8,
                                  stream_every=2)
    engines = [mk(TransformerAdapter(net, src_max_len=7)),
               mk(int4_adapter(TransformerAdapter(net, src_max_len=7))),
               mk(int4_adapter(TransformerAdapter(net, src_max_len=7),
                               group_size=16))]
    parts = [e._fingerprint_parts(("decode", 4, 2), []) for e in engines]
    fps = [memwatch.fingerprint(p) for p in parts]
    assert len(set(fps)) == len(fps), fps

    again = mk(int4_adapter(TransformerAdapter(net, src_max_len=7)))
    assert memwatch.fingerprint(
        again._fingerprint_parts(("decode", 4, 2), [])) == fps[1]


def test_maybe_int4_env_gate(monkeypatch, trained):
    net, src = trained
    adapter = TransformerAdapter(net, src_max_len=7)
    monkeypatch.delenv("MX_SERVE_INT4", raising=False)
    monkeypatch.delenv("MX_QUANTIZE", raising=False)
    assert maybe_int4_adapter(adapter) is adapter
    monkeypatch.setenv("MX_SERVE_INT4", "1")
    q = maybe_int4_adapter(adapter)
    assert isinstance(q, Int4WeightAdapter)
    assert q._group_size == 32
    monkeypatch.setenv("MX_QUANT_GROUP", "16")
    assert maybe_int4_adapter(adapter)._group_size == 16
    monkeypatch.setenv("MX_QUANT_GROUP", "lots")
    with pytest.raises(MXNetError, match="MX_QUANT_GROUP"):
        maybe_int4_adapter(adapter)
    monkeypatch.setenv("MX_QUANT_GROUP", "7")
    with pytest.raises(MXNetError, match="even"):
        maybe_int4_adapter(adapter)
    monkeypatch.delenv("MX_QUANT_GROUP", raising=False)
    monkeypatch.setenv("MX_QUANTIZE", "int8")
    with pytest.raises(MXNetError, match="pick one"):
        maybe_int4_adapter(adapter)
    monkeypatch.delenv("MX_QUANTIZE", raising=False)
    monkeypatch.setenv("MX_SERVE_INT4", "sometimes")
    with pytest.raises(MXNetError, match="MX_SERVE_INT4"):
        maybe_int4_adapter(adapter)


# ---------------------------------------------------------------------------
# fused kernels inside the serving engine (interpret mode on CPU)
# ---------------------------------------------------------------------------
def test_fused_pass_in_serving_engine(monkeypatch, trained):
    """MX_PALLAS_FUSED=1 swaps the registered kernels into the engine's
    compiled decode/prefill (interpret mode here), splits the
    fingerprint, agrees top-1 with the stock engine, and MX_PASSES can
    veto the pass back out of the signature."""
    net, src = trained
    monkeypatch.delenv("MX_PALLAS_FUSED", raising=False)
    monkeypatch.delenv("MX_PASSES", raising=False)
    base = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=2,
                         page_size=4, max_len=12, stream_every=4)
    assert base._pipeline.names() == []
    reqs0, out0 = _serve(base, src, n=3)

    monkeypatch.setenv("MX_PALLAS_FUSED", "1")
    engf = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=2,
                         page_size=4, max_len=12, stream_every=4)
    assert engf._pipeline.names() == ["fused_kernels"]
    fp = lambda e: memwatch.fingerprint(
        e._fingerprint_parts(("decode", 4, 2), []))
    assert fp(engf) != fp(base)
    reqsf, outf = _serve(engf, src, n=3)
    for a, b in zip(reqs0, reqsf):
        assert list(out0[a.id]) == list(outf[b.id])

    monkeypatch.setenv("MX_PASSES", "-fused_kernels")
    vetoed = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=2,
                           page_size=4, max_len=12, stream_every=4)
    assert vetoed._pipeline.get("fused_kernels").enabled is False
    assert fp(vetoed) == fp(base)
