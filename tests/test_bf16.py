"""End-to-end bfloat16 coverage — the flagship dtype path (BASELINE config 2
is bf16 ResNet; reference AMP lists in python/mxnet/contrib/amp/lists/
symbol_fp16.py drive the same layers through fp16).

These tests exist because round 2 shipped "130 passed" while the bf16 fused
step was broken in two places (Pooling iinfo crash; conv transpose dtype
mismatch): no test cast a network.  Every case here casts to bfloat16 and
drives the compiled step the benchmark's cells run.
"""
import ml_dtypes
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn

BF16 = ml_dtypes.bfloat16


def _tiny_convnet():
    net = nn.HybridSequential()
    net.add(nn.Conv2D(8, 3, padding=1), nn.BatchNorm(), nn.Activation("relu"),
            nn.MaxPool2D(2), nn.Conv2D(8, 3, padding=1), nn.BatchNorm(),
            nn.Activation("relu"), nn.GlobalAvgPool2D(), nn.Dense(10))
    return net


def test_pooling_bf16_forward():
    # BENCH_r02 crash: Pooling picked the max identity via dtype.kind, which
    # is 'V' for ml_dtypes bfloat16.
    x = nd.array(np.random.rand(2, 3, 8, 8).astype(BF16), dtype=BF16)
    for pool_type in ("max", "avg", "sum", "lp"):
        y = nd.Pooling(x, kernel=(2, 2), stride=(2, 2), pool_type=pool_type)
        assert y.dtype == BF16
        assert np.isfinite(y.asnumpy().astype(np.float32)).all()


def test_conv_bf16_grad():
    # conv transpose rule must see matching dtypes (the second r2 bf16 bug).
    x = nd.array(np.random.rand(2, 3, 8, 8).astype(BF16), dtype=BF16)
    w = nd.array(np.random.rand(4, 3, 3, 3).astype(BF16), dtype=BF16)
    x.attach_grad()
    w.attach_grad()
    with autograd.record():
        y = nd.Convolution(x, w, kernel=(3, 3), num_filter=4, no_bias=True)
        loss = y.sum()
    loss.backward()
    assert x.grad.dtype == BF16
    assert w.grad.dtype == BF16
    assert np.isfinite(w.grad.asnumpy().astype(np.float32)).all()


def test_fused_step_bf16_convnet():
    """cast('bfloat16') conv+BN+pool net through the fused DataParallelStep:
    finite loss, weights stay bf16, loss decreases over a few steps."""
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    mx.random.seed(0)
    ctx = mx.current_context()
    net = _tiny_convnet()
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(
        net, loss_fn, mesh=local_mesh(devices=[ctx.jax_device]),
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
    x = np.random.rand(8, 3, 16, 16).astype(BF16)
    y = np.random.randint(0, 10, 8).astype("float32")
    xb, yb = nd.array(x, ctx=ctx, dtype=BF16), nd.array(y, ctx=ctx)
    losses = [float(np.asarray(step.step(xb, yb))) for _ in range(8)]
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    step.sync_to_block()
    for name, p in net.collect_params().items():
        assert p.data().dtype == BF16, (name, p.data().dtype)


def test_fused_step_bf16_dp_sharded():
    """Same fused bf16 step over the full virtual 8-device DP mesh."""
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    mx.random.seed(0)
    net = _tiny_convnet()
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(net, loss_fn, mesh=local_mesh(), optimizer="sgd",
                            optimizer_params={"learning_rate": 0.05})
    x = np.random.rand(16, 3, 16, 16).astype(BF16)
    y = np.random.randint(0, 10, 16).astype("float32")
    loss = step.step(nd.array(x, dtype=BF16), nd.array(y))
    assert np.isfinite(float(np.asarray(loss)))


def test_eager_bf16_forward_backward():
    """Eager (non-fused) training step in bf16: the reference Trainer path."""
    mx.random.seed(0)
    net = _tiny_convnet()
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(np.random.rand(4, 3, 16, 16).astype(BF16), dtype=BF16)
    y = nd.array(np.random.randint(0, 10, 4).astype("float32"))
    with autograd.record():
        out = net(x)
        loss = loss_fn(out, y)
    loss.backward()
    trainer.step(4)
    val = float(loss.mean().asnumpy().astype(np.float32))
    assert np.isfinite(val)


def test_softmax_output_bf16_label_grad():
    # the nn.py SoftmaxOutput backward must treat bf16 labels (numpy kind
    # 'V') as float labels, not fall into the integer/float0 branch.
    x = nd.array(np.random.rand(4, 10).astype(BF16), dtype=BF16)
    lab = nd.array(np.random.randint(0, 10, 4).astype(BF16), dtype=BF16)
    x.attach_grad()
    with autograd.record():
        y = nd.SoftmaxOutput(x, lab)
        s = y.sum()
    s.backward()
    assert np.isfinite(x.grad.asnumpy().astype(np.float32)).all()


def test_fp16_safe_accumulation():
    # MXNET_SAFE_ACCUMULATION: naive fp16 accumulation of 4096 ones stalls
    # at 2048 (fp16 integers are exact only to 2048; beyond, +1 rounds
    # away), while f32 accumulation gives exactly 4096 — which still fits
    # fp16.
    x = nd.array(np.ones((2, 4096), np.float16), dtype=np.float16)
    w = nd.array(np.ones((3, 4096), np.float16), dtype=np.float16)
    y = nd.FullyConnected(x, w, num_hidden=3, no_bias=True)
    v = y.asnumpy().astype(np.float64)
    np.testing.assert_allclose(v, np.full((2, 3), 4096.0), rtol=1e-3)


def test_hybridized_bf16_matches_eager():
    mx.random.seed(0)
    net = _tiny_convnet()
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16")
    x = nd.array(np.random.rand(2, 3, 16, 16).astype(BF16), dtype=BF16)
    eager = net(x).asnumpy().astype(np.float32)
    net.hybridize()
    hybrid = net(x).asnumpy().astype(np.float32)
    np.testing.assert_allclose(eager, hybrid, rtol=2e-2, atol=2e-2)
