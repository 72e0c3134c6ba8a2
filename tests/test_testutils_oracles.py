"""Exercise the test_utils oracles themselves (check_numeric_gradient /
check_consistency / rand_ndarray / with_seed), per SURVEY §4.3: the
reference applies these per-op in test_operator.py; here the utilities are
driven through representative layer ops so they stay load-bearing.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import (check_consistency, check_numeric_gradient,
                                  rand_ndarray, with_seed)


@with_seed(7)
def test_check_numeric_gradient_fc():
    x = rand_ndarray((3, 4))
    w = rand_ndarray((5, 4))
    b = rand_ndarray((5,))

    def loss(x_, w_, b_):
        return (nd.FullyConnected(x_, w_, b_, num_hidden=5) ** 2).sum()

    check_numeric_gradient(loss, [x, w, b])


@with_seed(8)
def test_check_numeric_gradient_conv_bn():
    x = rand_ndarray((2, 3, 5, 5))
    k = rand_ndarray((4, 3, 3, 3))

    def loss(x_, k_):
        out = nd.Convolution(x_, k_, kernel=(3, 3), num_filter=4,
                             no_bias=True, pad=(1, 1))
        return nd.tanh(out).sum()

    check_numeric_gradient(loss, [x, k], eps=1e-2, rtol=5e-2)


@with_seed(9)
def test_check_numeric_gradient_detects_wrong_grad():
    """The oracle must actually FAIL on a broken gradient."""
    x = rand_ndarray((4,))

    import jax

    @jax.custom_vjp
    def bad_square(a):
        return a * a

    def f(a):
        return a * a, a

    def b(res, g):
        return (g * res,)  # WRONG: should be 2*a*g

    bad_square.defvjp(f, b)

    def loss(x_):
        from mxnet_tpu.ops import registry as reg

        return reg.invoke_fn(bad_square, [x_]).sum()

    with pytest.raises(AssertionError):
        check_numeric_gradient(loss, [x])


def test_check_consistency_cpu_contexts():
    """Same computation across contexts (cpu vs cpu here; the tpu row runs
    on a TPU host via tools/check_consistency.py)."""
    inputs = [np.random.RandomState(0).rand(4, 6).astype(np.float32)]

    def fn(x):
        return nd.softmax(nd.dot(x, x.T))

    check_consistency(fn, [mx.cpu(), mx.cpu(1)], inputs_np=inputs)
