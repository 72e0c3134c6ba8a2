"""Int8 quantized serving (docs/PRECISION.md §Int8 serving; ISSUE 15
acceptance).

Covers: quantize->dequantize round-trip vs the ops/quantization.py
oracle, the calibrated int8 engine's top-1 agreement with the fp32
engine on the reverse-task model, the ONE-int8-decode-executable
property (telemetry compile events), the fingerprint split on a changed
quant config, the MX_QUANTIZE env gate, precision telemetry labels, and
the `quantized` memwatch census category.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import memwatch, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer import Transformer, label_smoothed_ce
from mxnet_tpu.precision import (QuantizedAdapter, maybe_quantize_adapter,
                                 quantize_adapter)
from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

PAD, BOS, EOS = 0, 1, 2


@pytest.fixture
def tele(tmp_path):
    telemetry.reset()
    memwatch.reset()
    telemetry.enable(str(tmp_path))
    yield telemetry
    telemetry.reset()
    memwatch.reset()


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
    so greedy decode is decision-stable across the fp32 and int8
    executables."""
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

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


def _quantize(net, src, calib_mode="naive", exclude=()):
    adapter = TransformerAdapter(net, src_max_len=7)

    def calib_fn(batch):
        net.translate(nd.array(batch, dtype="int32"), bos_id=BOS,
                      eos_id=EOS, max_len=10, beam_size=1)

    return quantize_adapter(adapter, [src[i:i + 1] for i in range(len(src))],
                            calib_fn, calib_mode=calib_mode,
                            exclude=exclude)


# ---------------------------------------------------------------------------
# int8 math round-trip vs the ops oracle
# ---------------------------------------------------------------------------
def test_quantize_dequantize_roundtrip_vs_oracle():
    """contrib.quantize_v2 -> dequantize reconstructs within one scale
    step of the symmetric 127-level oracle, and matches the numpy
    reference scheme exactly."""
    rng = np.random.RandomState(0)
    x = (rng.randn(64).astype(np.float32) * 3).astype(np.float32)
    t = float(np.abs(x).max())
    q, mn, mx_ = nd.contrib.quantize_v2(nd.array(x), min_calib_range=-t,
                                        max_calib_range=t)
    assert q.dtype == np.int8
    ref_q = np.clip(np.round(x * (127.0 / t)), -127, 127).astype(np.int8)
    np.testing.assert_array_equal(q.asnumpy(), ref_q)
    back = nd.contrib.dequantize(q, mn, mx_).asnumpy()
    np.testing.assert_allclose(back, x, atol=t / 127.0 + 1e-6)
    np.testing.assert_allclose(back, ref_q.astype(np.float32) * (t / 127.0),
                               rtol=1e-6)


def test_quantized_dense_twin_matches_eager_quantized_ops(trained):
    """The traced int8 Dense twin computes exactly what composing the
    eager ops/quantization.py primitives computes."""
    from mxnet_tpu.precision.quantize import collect_quantizable

    net, _src = trained
    qad = _quantize(net, _src)
    path, layer = collect_quantizable(net)[0]
    twin = qad._by_path[path]
    impl = twin._impl  # the contrib eager twin owning the int8 lowering
    bias = layer.bias.data() if layer.bias is not None else None
    rng = np.random.RandomState(1)
    x = nd.array(rng.randn(3, impl._qweight.shape[1]).astype(np.float32))
    got = twin(nd, x, bias).asnumpy()
    t = twin.act_thresh
    qx, mn, mx_ = nd.contrib.quantize_v2(x, min_calib_range=-t,
                                         max_calib_range=t)
    acc, amn, amx = nd.contrib.quantized_fully_connected(
        qx, impl._qweight, bias if bias is not None else impl._bias,
        mn, mx_, impl._w_min, impl._w_max, num_hidden=impl._units,
        no_bias=impl._no_bias, flatten=impl._flatten)
    want = nd.contrib.dequantize(acc, amn, amx).asnumpy()
    if impl._act_type:
        want = nd.Activation(nd.array(want),
                             act_type=impl._act_type).asnumpy()
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# ACCEPTANCE: calibrated int8 engine vs fp32 engine
# ---------------------------------------------------------------------------
def test_int8_engine_top1_agreement_and_param_bytes(trained):
    net, src = trained
    eng32 = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=3,
                          page_size=4, max_len=12, stream_every=4)
    reqs32 = [Request(src[i], max_new_tokens=9, bos_id=BOS, eos_id=EOS)
              for i in range(6)]
    out32 = eng32.serve(reqs32, arrival_steps=[0, 0, 0, 2, 5, 9])

    qad = _quantize(net, src)
    # params-bytes: the int8 graph holds well under half the fp32 bytes
    assert qad.quantized_param_bytes() < 0.5 * qad.fp32_param_bytes()
    engq = ServingEngine(qad, slots=3, page_size=4, max_len=12,
                         stream_every=4)
    reqsq = [Request(src[i], max_new_tokens=9, bos_id=BOS, eos_id=EOS)
             for i in range(6)]
    outq = engq.serve(reqsq, arrival_steps=[0, 0, 0, 2, 5, 9])

    agree, total = 0, 0
    for a, b in zip(reqs32, reqsq):
        ta, tb = list(out32[a.id]), list(outq[b.id])
        n = min(len(ta), len(tb))
        agree += sum(1 for i in range(n) if ta[i] == tb[i])
        total += max(len(ta), len(tb))
    assert total > 0
    # the memorized reverse task decodes identically through int8 on
    # this model; the acceptance floor is 90% top-1 agreement
    assert agree / total >= 0.9, (agree, total)
    # and the task is actually solved, not just agreed upon
    for i, r in enumerate(reqsq[:3]):
        assert list(outq[r.id][:6]) == list(src[i, :6][::-1])


def test_one_int8_decode_executable(tele, tmp_path, trained):
    """ACCEPTANCE: the quantized engine books exactly ONE decode compile
    event (plus one prefill) on a mixed-length mid-flight trace — the
    int8 rewrite lives inside the one executable, not per layer."""
    net, src = trained
    qad = _quantize(net, src)
    eng = ServingEngine(qad, slots=3, page_size=4, max_len=12,
                        stream_every=4)
    reqs = [Request(src[i], max_new_tokens=n, bos_id=BOS, eos_id=EOS)
            for i, n in enumerate((5, 9, 11))]
    eng.serve(reqs, arrival_steps=[0, 2, 6])
    telemetry.flush()
    events = [json.loads(line)
              for line in open(telemetry.event_path(str(tmp_path), 0))]
    compiles = [e for e in events if e["kind"] == "compile"
                and e.get("executor") == "ServingEngine"]
    sites = sorted(e["site"] for e in compiles)
    assert sites == ["serving_decode", "serving_prefill"], sites


def test_quant_config_splits_fingerprint(trained):
    """ACCEPTANCE: a different quant config (calib mode, excluded
    layers, or fp32 vs int8) produces a different executable
    fingerprint on its compile events."""
    net, src = trained
    naive = _quantize(net, src, calib_mode="naive")
    entropy = _quantize(net, src, calib_mode="entropy")
    excl = _quantize(net, src, exclude=(next(iter(naive._by_path)),))
    engines = [
        ServingEngine(TransformerAdapter(net, src_max_len=7), slots=2,
                      page_size=4, max_len=8, stream_every=2),
        ServingEngine(naive, slots=2, page_size=4, max_len=8,
                      stream_every=2),
        ServingEngine(entropy, slots=2, page_size=4, max_len=8,
                      stream_every=2),
        ServingEngine(excl, slots=2, page_size=4, max_len=8,
                      stream_every=2),
    ]
    parts = [e._fingerprint_parts(("decode", 4, 2), []) for e in engines]
    fps = [memwatch.fingerprint(p) for p in parts]
    assert len(set(fps)) == len(fps), fps


def test_precision_telemetry_labels(tele, tmp_path, trained):
    net, src = trained
    qad = _quantize(net, src)
    eng = ServingEngine(qad, slots=2, page_size=4, max_len=10,
                        stream_every=4)
    reqs = [Request(src[i], max_new_tokens=5, bos_id=BOS, eos_id=EOS)
            for i in range(2)]
    eng.serve(reqs)
    s = telemetry.summary()["serving"]
    assert s["precision"] == "int8"
    prom = open(telemetry.export_prometheus()).read()
    assert 'mx_serve_precision_info{rank="0",precision="int8"} 1' in prom
    telemetry.flush()
    events = [json.loads(line)
              for line in open(telemetry.event_path(str(tmp_path), 0))]
    serve_evs = [e for e in events if e["kind"] == "serve_request"]
    assert serve_evs and all(e["precision"] == "int8" for e in serve_evs)


def test_quantized_census_category(trained):
    net, src = trained
    qad = _quantize(net, src)
    eng = ServingEngine(qad, slots=2, page_size=4, max_len=8,
                        stream_every=2)
    census = memwatch.census()
    cats = census["categories"]
    assert "quantized" in cats, sorted(cats)
    # every int8 weight buffer is attributed (22 Dense layers x 3 arrays)
    assert cats["quantized"]["count"] >= len(qad._entries)
    del eng


def test_maybe_quantize_env_gate(monkeypatch, trained):
    net, src = trained
    adapter = TransformerAdapter(net, src_max_len=7)
    monkeypatch.delenv("MX_QUANTIZE", raising=False)
    assert maybe_quantize_adapter(adapter) is adapter
    monkeypatch.setenv("MX_QUANTIZE", "int8")
    with pytest.raises(MXNetError, match="calibration data"):
        maybe_quantize_adapter(adapter)

    def calib_fn(batch):
        net.translate(nd.array(batch, dtype="int32"), bos_id=BOS,
                      eos_id=EOS, max_len=8, beam_size=1)

    monkeypatch.setenv("MX_QUANT_CALIB", "naive")
    q = maybe_quantize_adapter(adapter, [src[:1]], calib_fn)
    assert isinstance(q, QuantizedAdapter)
    assert q.precision == "int8"
    monkeypatch.setenv("MX_QUANTIZE", "int4")
    with pytest.raises(MXNetError, match="MX_QUANTIZE"):
        maybe_quantize_adapter(adapter, [src[:1]], calib_fn)


def test_degenerate_calibration_fails_loudly(trained):
    """All-zero calibration activations raise naming the layer path and
    calib mode (the quantize_net satellite, via the shared check)."""
    net, src = trained
    adapter = TransformerAdapter(net, src_max_len=7)

    from mxnet_tpu.precision.quantize import calibrate, collect_quantizable

    layers = collect_quantizable(net)
    with pytest.raises(MXNetError) as ei:
        # observe() never fires (calib_fn does nothing) -> the
        # calibrator has no data for any layer
        calibrate(layers, [src[:1]], lambda batch: None,
                  calib_mode="naive")
    assert "no calibration data" in str(ei.value)


def test_quantize_adapter_requires_model():
    from mxnet_tpu.serving import FullPrefixAdapter

    ad = FullPrefixAdapter(lambda F, buf: None, max_len=8)
    with pytest.raises(MXNetError, match="model"):
        QuantizedAdapter(ad, {})


def test_calibrate_observes_through_hybridized_blocks():
    """Forward-pre hooks never fire through a CachedOp fast path, so
    calibrate(root=...) must deactivate hybridized blocks for the eager
    pass (the quantize_net recipe) and restore them after — without
    root, a hybridized serving model would raise 'no calibration data'
    for every layer."""
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.precision.quantize import calibrate, collect_quantizable

    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu", in_units=4),
                nn.Dense(4, in_units=8))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = nd.array(np.random.RandomState(0).rand(2, 4).astype(np.float32))
    net(x)  # build the cached graph
    assert net._active

    layers = collect_quantizable(net)
    # without root the hooks never observe through the cached graph
    with pytest.raises(MXNetError, match="no calibration data"):
        calibrate(layers, [x], lambda b: net(b), calib_mode="naive")
    thresholds = calibrate(layers, [x], lambda b: net(b),
                           calib_mode="naive", root=net)
    assert set(thresholds) == {p for p, _ in layers}
    assert all(t > 0 for t in thresholds.values())
    assert net._active  # hybridization restored after the pass
