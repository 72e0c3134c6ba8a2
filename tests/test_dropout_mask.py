"""Dropout's mask: the stream it draws is pinned bit for bit, and a training
trace makes each mask once (one optimization barrier per live site), so the
backward reads the forward's mask instead of regenerating it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.models import bert_small
from mxnet_tpu.ops import nn as ops_nn
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import DataParallelStep, local_mesh
from mxnet_tpu.parallel.data_parallel import _block_apply_fn


def plain_dropout(data, key, p=0.5, mode="training", axes=(), training=False,
                  cudnn_off=False):
    """The operator as it stood before the mask was held: the reference."""
    if not training or p <= 0.0:
        return data
    shape = [1 if i in axes else data.shape[i] for i in range(data.ndim)]
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape)).astype(data.dtype)
    return data * mask / keep


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _case(dtype, axes):
    x = jax.random.normal(jax.random.PRNGKey(7), (6, 5, 8), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(8), (6, 5, 8), jnp.float32)
    shape = tuple(1 if i in axes else x.shape[i] for i in range(x.ndim))
    return x.astype(dtype), ct.astype(dtype), shape


def _assert_stream(y, g, want_y, want_g, key, p, shape):
    """``want_*`` come from the plain three lines run through the same
    program as the operator (XLA folds ``/ keep`` and signs its zeros alike
    in both)."""
    np.testing.assert_array_equal(_bits(y), _bits(want_y))
    np.testing.assert_array_equal(_bits(g), _bits(want_g))
    # and the plain lines are what they say: zero exactly where bernoulli is
    kept = np.broadcast_to(np.asarray(
        jax.random.bernoulli(key, 1.0 - p, shape)), np.shape(y))
    assert 0 < kept.mean() < 1
    np.testing.assert_array_equal(np.asarray(y, np.float32) != 0, kept)
    np.testing.assert_array_equal(np.asarray(g, np.float32) != 0, kept)


@pytest.mark.parametrize("axes", [(), (1,)], ids=["plain", "axes"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_stream_is_bernoulli_in_jitted_value_and_grad(dtype, axes):
    x, ct, shape = _case(dtype, axes)
    key, p = jax.random.PRNGKey(11), 0.3

    def run(op):
        def weighted(x):
            y = op(x, key, p=p, axes=axes, training=True)
            return jnp.sum((y * ct).astype(jnp.float32)), y

        (_, y), g = jax.jit(jax.value_and_grad(weighted, has_aux=True))(x)
        return y, g

    _assert_stream(*run(ops_nn.dropout), *run(plain_dropout), key, p, shape)


@pytest.mark.parametrize("axes", [(), (1,)], ids=["plain", "axes"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_stream_is_bernoulli_on_the_eager_tape(dtype, axes):
    x, ct, shape = _case(dtype, axes)
    p, seed = 0.3, 5
    mx.random.seed(seed)
    key = jax.random.split(jax.random.PRNGKey(seed))[1]   # the first draw
    xn = nd.array(np.asarray(x.astype(jnp.float32)), dtype=dtype)
    xn.attach_grad()
    with autograd.record():
        y = nd.Dropout(xn, p=p, axes=axes)
    y.backward(nd.array(np.asarray(ct.astype(jnp.float32)), dtype=dtype))

    # the tape's two programs: the forward, and a backward that rebuilds it
    plain = jax.jit(lambda x: plain_dropout(x, key, p=p, axes=axes,
                                            training=True))
    want_g = jax.jit(lambda x, ct: jax.vjp(plain, x)[1](ct)[0])(x, ct)
    _assert_stream(y._data, xn.grad._data, plain(x), want_g, key, p, shape)


def _mlm_loss():
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    return lambda logits, labels: ce(
        logits.reshape(-1, logits.shape[-1]), labels.reshape(-1))


def _bert_step():
    """Two-layer bert_small under DataParallelStep, dropout on: seven live
    sites (embedding; per layer probabilities, attention output, FFN)."""
    mx.random.seed(3)
    net = bert_small(dropout=0.1)
    net.initialize(mx.init.Normal(0.02))
    step = DataParallelStep(net, _mlm_loss(), mesh=local_mesh(
        devices=jax.devices("cpu")[:1]), optimizer="adam",
        optimizer_params={"learning_rate": 1e-3})
    tokens = np.random.RandomState(0).randint(0, 512, (4, 16)).astype(np.int32)
    return net, step, (nd.array(tokens, dtype="int32"),
                       nd.array(tokens.astype(np.float32)))


def _three_steps():
    net, step, (x, y) = _bert_step()
    losses = [np.asarray(step.step(x, y)) for _ in range(3)]
    # gluon numbers its prefixes process-wide: the second net is bertformlm1_
    return losses, {k[len(net.prefix):]: np.asarray(v)
                    for k, v in step.params.items()}


def test_bert_step_bitwise_equal_to_the_plain_operator(monkeypatch):
    losses, params = _three_steps()
    monkeypatch.setattr(get_op("Dropout"), "fn", plain_dropout)
    ref_losses, ref_params = _three_steps()
    for got, ref in zip(losses, ref_losses):
        np.testing.assert_array_equal(_bits(got), _bits(ref))
    assert params.keys() == ref_params.keys()
    for k in params:
        np.testing.assert_array_equal(_bits(params[k]), _bits(ref_params[k]),
                                      err_msg=k)
    assert losses[0] != losses[1]       # dropout and Adam did move the loss


def test_training_trace_holds_one_mask_per_site():
    net, step, (x, y) = _bert_step()
    step.step(x, y)
    lowered = step._jitted.lower(
        step.params, step.opt_state, jax.random.PRNGKey(0), np.float32(1e-3),
        (x._data,), y._data).as_text()
    assert lowered.count("optimization_barrier") == 7
    # the same block outside training draws no mask at all
    apply_fn, _ = _block_apply_fn(net, mx.cpu(), train=False)
    infer = jax.jit(lambda p, k, t: apply_fn(p, k, t)[0]).lower(
        step.params, jax.random.PRNGKey(0), x._data).as_text()
    assert infer.count("optimization_barrier") == 0
    assert "threefry" not in infer and "xor" not in infer
