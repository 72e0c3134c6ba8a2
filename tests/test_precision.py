"""Precision subsystem (docs/PRECISION.md; ISSUE 15 acceptance): graph-
level AMP pass, traced dynamic loss scaling, Plan/checkpoint round-trips.

Covers: cast-policy semantics at the op-dispatch point, bf16-policy
compiled steps tracking the fp32 oracle within tolerance, loss-scale
skip-step semantics (injected non-finite grads leave weights / optimizer
state / Adam's t untouched, scale halves, then regrows), AMP-off runs
staying bitwise f32,
executable-fingerprint splits on precision config, env parsing, and
``Plan.precision`` + scaler state surviving checkpoint save -> elastic
reshard -> restore.
"""
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.base import MXNetError
from mxnet_tpu.parallel import DataParallelStep, Plan, dp_plan, local_mesh
from mxnet_tpu.parallel.mesh import make_mesh
from mxnet_tpu.precision import (AmpPolicy, LossScaleConfig,
                                 PrecisionConfig, amp_scope)

LS = LossScaleConfig(init_scale=16.0, growth_interval=4)
PREC_BF16 = PrecisionConfig(amp=AmpPolicy(), loss_scale=LS)


def _data(n=16, d=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.rand(n, d).astype(np.float32),
            rng.randint(0, classes, n).astype(np.float32))


def _make_step(precision=None, optimizer="sgd", lr=0.1, mesh=None,
               seed=0, clip_global=None):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    # in_units known -> parameters initialize HERE, under the seed just
    # set (deferred init would draw from wherever the global RNG stream
    # has advanced to by the first step — runs wouldn't be comparable)
    net.add(gluon.nn.Dense(16, activation="relu", in_units=8),
            gluon.nn.Dense(4, in_units=16))
    net.initialize(mx.init.Xavier())
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(
        net, lambda o, l: loss_fn(o, l), mesh=mesh or local_mesh(),
        optimizer=optimizer, optimizer_params={"learning_rate": lr},
        clip_global_norm=clip_global, precision=precision)
    return step


def _host(x):
    return np.asarray(x)


def _in_order(params):
    """``params.items()`` in creation order.  Gluon's name counters are
    global to the process (``dense9``, then ``dense10``), so a plain sort
    pairs two nets' layers wrongly whenever a counter gains a digit between
    them."""
    return sorted(params.items(), key=lambda kv: [
        int(t) if t.isdigit() else t for t in re.split(r"(\d+)", kv[0])])


# ---------------------------------------------------------------------------
# the cast policy at the dispatch point
# ---------------------------------------------------------------------------
def test_amp_scope_casts_low_and_widen_classes():
    import ml_dtypes

    a = nd.array(np.ones((4, 4), np.float32))
    with amp_scope(AmpPolicy()):
        low = nd.dot(a, a)                      # low class: bf16 compute
        assert low.dtype == ml_dtypes.bfloat16
        wide = low.softmax(axis=-1)             # widen class: back to f32
        assert wide.dtype == np.float32
    # scope off: nothing casts
    assert nd.dot(a, a).dtype == np.float32


def test_amp_policy_validation_and_custom_lists():
    with pytest.raises(MXNetError, match="ONE disposition"):
        AmpPolicy(low=("dot",), widen=("dot",))
    with pytest.raises(MXNetError, match="dtype"):
        AmpPolicy(dtype="int8")
    pol = AmpPolicy(low=("dot",), widen=())
    assert pol.op_class("dot") == "low"
    assert pol.op_class("FullyConnected") is None


def test_precision_config_env_parsing(monkeypatch):
    monkeypatch.delenv("MX_AMP", raising=False)
    assert PrecisionConfig.from_env() is None
    monkeypatch.setenv("MX_AMP", "bf16")
    cfg = PrecisionConfig.from_env()
    assert cfg.amp.dtype == "bfloat16" and cfg.loss_scale is None
    monkeypatch.setenv("MX_AMP", "fp16")
    cfg = PrecisionConfig.from_env()
    assert cfg.amp.dtype == "float16" and cfg.loss_scale is not None
    monkeypatch.setenv("MX_LOSS_SCALE", "128.0")
    cfg = PrecisionConfig.from_env()
    assert cfg.loss_scale.init_scale == 128.0 and not cfg.loss_scale.dynamic
    monkeypatch.setenv("MX_LOSS_SCALE", "off")
    assert PrecisionConfig.from_env().loss_scale is None
    monkeypatch.setenv("MX_AMP_POLICY", '{"low": ["dot"], "widen": []}')
    cfg = PrecisionConfig.from_env()
    assert cfg.amp.low == ("dot",)
    monkeypatch.setenv("MX_AMP", "int4")
    with pytest.raises(MXNetError, match="MX_AMP"):
        PrecisionConfig.from_env()


def test_precision_json_roundtrip_via_plan():
    from dataclasses import replace

    plan = replace(dp_plan(1), precision=PREC_BF16)
    rec = plan.to_json()
    assert rec["precision"]["amp"]["dtype"] == "bfloat16"
    back = Plan.from_json(rec)
    assert back.precision == PREC_BF16
    # absent precision round-trips as None (pre-precision checkpoints)
    rec2 = dp_plan(1).to_json()
    assert Plan.from_json(rec2).precision is None


# ---------------------------------------------------------------------------
# ACCEPTANCE: bf16 AMP parity + one-executable composition
# ---------------------------------------------------------------------------
def test_amp_bf16_step_tracks_fp32_oracle():
    """The bf16-policy compiled step's loss trajectory tracks the fp32
    oracle within documented tolerance, and still converges."""
    x, y = _data()
    f32 = _make_step(None)
    amp = _make_step(PREC_BF16)
    l32, lamp = [], []
    for _ in range(15):
        l32.append(float(f32.step(nd.array(x), nd.array(y))))
        lamp.append(float(amp.step(nd.array(x), nd.array(y))))
    assert lamp[-1] < lamp[0]
    # documented tolerance: bf16 carries ~3 decimal digits; the tiny-net
    # trajectories stay within 5e-2 absolute over 15 steps
    np.testing.assert_allclose(lamp, l32, atol=5e-2)
    # the env default wires the same config through the Plan
    assert amp.plan.precision == PREC_BF16
    # scale grew on schedule (15 finite steps / interval 4 -> 3 growths)
    assert float(_host(amp.scaler_state["scale"])) == 16.0 * 2 ** 3
    assert int(_host(amp.scaler_state["skipped"])) == 0


def test_amp_off_is_bitwise_f32():
    """ACCEPTANCE: without a precision config nothing in the program
    changes — two identically-seeded steps (one built through the
    precision kwarg explicitly None) are bitwise identical, f32 end to
    end, and their Plan carries no precision."""
    x, y = _data()
    a = _make_step(None)
    b = _make_step(precision=None)
    for _ in range(5):
        la = float(a.step(nd.array(x), nd.array(y)))
        lb = float(b.step(nd.array(x), nd.array(y)))
        assert la == lb
    assert a.plan.precision is None and a.scaler_state is None
    # gluon name counters differ between the two nets (dense0 vs dense2);
    # sorted order still pairs corresponding params
    for (_, arr_a), (_, arr_b) in zip(_in_order(a.params),
                                      _in_order(b.params)):
        assert np.asarray(arr_a).dtype == np.float32
        np.testing.assert_array_equal(np.asarray(arr_a),
                                      np.asarray(arr_b))


def test_amp_env_default_attaches_to_plan(monkeypatch):
    monkeypatch.setenv("MX_AMP", "bf16")
    step = _make_step(None)
    assert step.plan.precision is not None
    assert step.plan.precision.amp.dtype == "bfloat16"
    assert step.plan.precision.loss_scale is None  # bf16 default: off
    x, y = _data()
    v = float(step.step(nd.array(x), nd.array(y)))
    assert np.isfinite(v)


def test_fp16_amp_with_dynamic_scaling_trains():
    prec = PrecisionConfig(amp=AmpPolicy(dtype="float16"),
                           loss_scale=LossScaleConfig(init_scale=2.0 ** 8,
                                                      growth_interval=50))
    x, y = _data()
    step = _make_step(prec, lr=0.05)
    losses = [float(step.step(nd.array(x), nd.array(y)))
              for _ in range(15)]
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0]
    assert int(_host(step.scaler_state["skipped"])) == 0


# ---------------------------------------------------------------------------
# ACCEPTANCE: loss-scale skip-step semantics (traced, no host sync)
# ---------------------------------------------------------------------------
def test_skip_step_holds_state_halves_scale_then_regrows():
    x, y = _data()
    step = _make_step(PREC_BF16, optimizer="adam", lr=0.01)
    step.step(nd.array(x), nd.array(y)).wait()
    w0 = {n: _host(a).copy() for n, a in step.params.items()}
    m0 = {n: _host(a).copy() for n, a in step.opt_state[0].items()}
    t0 = int(_host(step.opt_state[2]))
    scale0 = float(_host(step.scaler_state["scale"]))

    bad = x.copy()
    bad[0, 0] = np.inf  # non-finite forward -> non-finite grads
    step.step(nd.array(bad), nd.array(y)).wait()
    # weights, Adam moments AND the bias-correction counter t all hold:
    # the skipped step is a traced no-op update
    for n in w0:
        np.testing.assert_array_equal(w0[n], _host(step.params[n]))
        np.testing.assert_array_equal(m0[n], _host(step.opt_state[0][n]))
    assert int(_host(step.opt_state[2])) == t0
    assert float(_host(step.scaler_state["scale"])) == scale0 * 0.5
    assert int(_host(step.scaler_state["skipped"])) == 1
    assert int(_host(step.scaler_state["growth"])) == 0

    # regrowth: growth_interval finite steps double the scale again
    for _ in range(LS.growth_interval):
        step.step(nd.array(x), nd.array(y)).wait()
    assert float(_host(step.scaler_state["scale"])) == scale0
    assert int(_host(step.scaler_state["skipped"])) == 1  # cumulative


def test_static_scale_never_moves_but_still_skips():
    prec = PrecisionConfig(
        amp=AmpPolicy(),
        loss_scale=LossScaleConfig(init_scale=32.0, dynamic=False))
    x, y = _data()
    step = _make_step(prec)
    step.step(nd.array(x), nd.array(y)).wait()
    w0 = {n: _host(a).copy() for n, a in step.params.items()}
    bad = x.copy()
    bad[0, 0] = np.nan
    step.step(nd.array(bad), nd.array(y)).wait()
    for n in w0:
        np.testing.assert_array_equal(w0[n], _host(step.params[n]))
    assert float(_host(step.scaler_state["scale"])) == 32.0
    assert int(_host(step.scaler_state["skipped"])) == 1


def test_loss_scale_composes_with_clip_global_norm():
    """Un-scaling folds into rescale BEFORE the global-norm clip, so the
    clipped update matches the unscaled step's update exactly (finite
    case)."""
    x, y = _data()
    a = _make_step(None, clip_global=0.5)
    b = _make_step(PrecisionConfig(loss_scale=LossScaleConfig(
        init_scale=64.0, dynamic=False)), clip_global=0.5)
    for _ in range(5):
        la = float(a.step(nd.array(x), nd.array(y)))
        lb = float(b.step(nd.array(x), nd.array(y)))
        np.testing.assert_allclose(la, lb, rtol=2e-6)
    for (_, arr_a), (_, arr_b) in zip(_in_order(a.params),
                                      _in_order(b.params)):
        np.testing.assert_allclose(_host(arr_a), _host(arr_b),
                                   rtol=2e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# executable identity: precision splits the fingerprint
# ---------------------------------------------------------------------------
def test_precision_splits_executable_fingerprint():
    from mxnet_tpu import memwatch

    sig = ((( (16, 8), "float32"),), ((16,), "float32"))
    base = _make_step(None)._fingerprint_parts(sig)
    amp = _make_step(PREC_BF16)._fingerprint_parts(sig)
    fp16 = _make_step(PrecisionConfig(
        amp=AmpPolicy(dtype="float16"),
        loss_scale=LS))._fingerprint_parts(sig)
    static = _make_step(PrecisionConfig(
        amp=AmpPolicy(),
        loss_scale=LossScaleConfig(init_scale=16.0, growth_interval=4,
                                   dynamic=False)))._fingerprint_parts(sig)
    fps = [memwatch.fingerprint(p) for p in (base, amp, fp16, static)]
    assert len(set(fps)) == 4, fps


# ---------------------------------------------------------------------------
# ACCEPTANCE: Plan.precision + scaler state survive save -> reshard ->
# restore
# ---------------------------------------------------------------------------
def test_scaler_and_precision_survive_elastic_reshard(tmp_path):
    """Save on a dp4 mesh, restore onto dp2 (a real elastic reshard —
    layouts differ): Plan.precision rides the layout, amp.* scaler
    state rides opt_state, and the restored trajectory continues with
    the learned scale, not init_scale."""
    import jax

    from mxnet_tpu import checkpoint

    x, y = _data(n=16)
    step = _make_step(PREC_BF16, optimizer="adam", lr=0.01,
                      mesh=make_mesh(devices=jax.devices()[:4]))
    for _ in range(5):  # one growth at interval 4
        step.step(nd.array(x), nd.array(y))
    step.drain()
    assert float(_host(step.scaler_state["scale"])) == 32.0
    ck = checkpoint.AsyncCheckpointer(str(tmp_path), save_every=1)
    ck.step(step)
    ck.close()

    # the layout on disk carries the full precision config
    import json

    meta = json.load(open(tmp_path / "step-1" / "meta.json"))
    assert meta["layout"]["plan"]["precision"]["amp"]["dtype"] == \
        "bfloat16"
    assert meta["layout"]["plan"]["precision"]["loss_scale"][
        "growth_interval"] == 4

    step2 = _make_step(PREC_BF16, optimizer="adam", lr=0.01,
                       mesh=make_mesh(devices=jax.devices()[:2]),
                       seed=7)  # different init: restore must overwrite
    assert checkpoint.restore(str(tmp_path), step2) == 1
    assert float(_host(step2.scaler_state["scale"])) == 32.0
    assert int(_host(step2.scaler_state["growth"])) == \
        int(_host(step.scaler_state["growth"]))
    for (_, pa), (_, pb) in zip(_in_order(step.params),
                                _in_order(step2.params)):
        np.testing.assert_array_equal(_host(pa), _host(pb))
    # training continues on the new mesh with the restored scale
    v = float(step2.step(nd.array(x), nd.array(y)))
    assert np.isfinite(v)


def test_restore_without_scaler_state_warns_and_inits_fresh(tmp_path, caplog):
    import logging

    x, y = _data()
    plain = _make_step(None)
    plain.step(nd.array(x), nd.array(y)).wait()
    sd = plain.state_dict()
    lay = plain.layout()
    assert not any(k.startswith("amp.") for k in sd["opt_state"])

    scaled = _make_step(PREC_BF16)
    with caplog.at_level(logging.WARNING):
        scaled.load_state_dict(sd, saved_layout=lay)
    assert any("FRESH scaler" in r.message for r in caplog.records)
    assert float(_host(scaled.scaler_state["scale"])) == LS.init_scale

    # and the mirror: scaler state in the checkpoint, step without
    scaled.step(nd.array(x), nd.array(y)).wait()
    sd2 = scaled.state_dict()
    plain2 = _make_step(None)
    with caplog.at_level(logging.WARNING):
        plain2.load_state_dict(sd2, saved_layout=scaled.layout())
    assert plain2.scaler_state is None


# ---------------------------------------------------------------------------
# satellites: quantize_net degenerate threshold, eager shim delegation
# ---------------------------------------------------------------------------
def test_quantize_net_degenerate_calibration_names_layer_and_mode():
    from mxnet_tpu.contrib.quantization import quantize_net

    mx.random.seed(0)
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.init.Xavier())
    net(nd.array(np.zeros((4, 6), np.float32)))
    # all-zero calibration: layer 0 sees zeros -> degenerate threshold
    with pytest.raises(MXNetError) as ei:
        quantize_net(net, calib_data=[nd.array(np.zeros((4, 6),
                                                        np.float32))],
                     calib_mode="naive")
    msg = str(ei.value)
    assert "'0'" in msg and "naive" in msg and "degenerate" in msg


def test_eager_scaler_shim_single_fused_readback():
    """The contrib/amp DynamicLossScaler delegates overflow detection to
    ONE fused reduce (precision.loss_scale.overflow_flag) — semantics
    unchanged: finite grads -> False, any inf/nan -> True."""
    from mxnet_tpu import autograd
    from mxnet_tpu.contrib.amp import DynamicLossScaler

    mx.random.seed(0)
    net = gluon.nn.Dense(4)
    net.initialize(mx.init.Xavier())
    x = nd.array(np.random.RandomState(0).rand(4, 6).astype(np.float32))
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    params = list(net.collect_params().values())
    scaler = DynamicLossScaler()
    assert scaler.has_overflow(params) is False
    g = params[0].grad()
    bad = np.array(g.asnumpy())
    bad[0, 0] = np.inf
    g._set_data(nd.array(bad)._data)
    assert scaler.has_overflow(params) is True


def test_overflow_flag_is_device_value():
    """overflow_flag returns a DEVICE scalar (no sync inside — the hot
    entry mxlint guards); the readback is the caller's explicit act."""
    import jax

    from mxnet_tpu.precision.loss_scale import overflow_flag

    arrs = [jax.numpy.ones((4,)), jax.numpy.ones((2, 2))]
    flag = overflow_flag(arrs)
    assert isinstance(flag, jax.Array)
    assert bool(np.asarray(flag)) is False
    arrs[0] = arrs[0].at[1].set(np.nan)
    assert bool(np.asarray(overflow_flag(arrs))) is True
