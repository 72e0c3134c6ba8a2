"""The stream mix's Mosaic kernels (``ops/pallas/mhc_mix.py``) in interpret
mode: each kernel and the whole sublayer against the jax form of
``ops/hc_ops.py`` and against the benchmark's plain reference."""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import nd
from mxnet_tpu.models.common import checkpointed
from mxnet_tpu.ops import hc_ops, recompute
from mxnet_tpu.ops import pallas as pk
from mxnet_tpu.ops.pallas import mhc_mix

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N, K = 4, 24
ATTRS = (N, 20, 1e-6, -30.0, 30.0, 1e-6)
CFG = mhc_mix._Cfg(*ATTRS, True)
REF_CFG = {"rms_norm_eps": 1e-6, "mhc_h_res_clamp_min": -30,
           "mhc_h_res_clamp_max": 30, "hc_sinkhorn_iters": 20,
           "hc_eps": 1e-6}


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "xing_reference", os.path.join(_REPO, "benchmark", "references",
                                       "xing4_0_29b_a4b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def kernels_on(monkeypatch):
    """The ops of ``hc_ops`` take the kernels (interpreted here) wherever the
    shapes allow, as they do on a TPU in per-device code."""
    monkeypatch.setattr(
        hc_ops, "_kernels", lambda streams, n, k:
        mhc_mix if mhc_mix.supported(streams, n, k) else None)


def _rand(key, shape, scale=1.0, dtype=jnp.float32):
    return (jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
            * scale).astype(dtype)


def _leaves(d, dtype=jnp.float32, key=6, b_scale=1.0):
    return {"norm_gamma": (1 + _rand(key, (N * d,), 0.1)).astype(dtype),
            "phi_weight": _rand(key + 1, (K, N * d), 0.1, dtype),
            "a": jnp.array([0.5, 0.7, 0.9], dtype),
            "b": _rand(key + 2, (K,), b_scale, dtype)}


def _params(P):
    return P["norm_gamma"], P["phi_weight"], P["a"], P["b"]


def _jax_sublayer(X, P, w, ct):
    """loss, (C, u, X') of the jax form with ``F(u) = w u``."""
    c = hc_ops._coefficients(X, *_params(P), *ATTRS)
    u = hc_ops._pre(X, c, N)
    out = hc_ops._post(X, (u * w).astype(X.dtype), c, N)
    return (out.astype(jnp.float32) * ct).sum(), (c, u, out)


def _op_sublayer(X, P, w, ct):
    """The same through the three registered ops."""
    c = hc_ops.mhc_coefficients(X, *_params(P))
    u = hc_ops.mhc_pre(X, c)
    out = hc_ops.mhc_post(X, (u * w).astype(X.dtype), c)
    return (out.astype(jnp.float32) * ct).sum(), (c, u, out)


def _rel(got, want):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _case(tokens, d, dtype=jnp.float32):
    shape = (1, tokens, N * d) if tokens % 2 else (2, tokens // 2, N * d)
    return (_rand(14, shape, 1.0, dtype), _leaves(d, dtype, key=11),
            _rand(15, (d,), 1.0, dtype), _rand(16, shape))


# tokens: whole tiles, a count that is no whole tile (padded), one short row
@pytest.mark.parametrize("tokens,d", [(32, 128), (40, 256), (7, 128)])
def test_a_sublayer_in_f32_matches_the_jax_form_and_the_reference(
        ref, kernels_on, tokens, d):
    X, P, w, ct = _case(tokens, d)
    grad = lambda f: jax.value_and_grad(f, (0, 1, 2), has_aux=True)
    (l0, aux0), g0 = grad(_jax_sublayer)(X, P, w, ct)
    (l1, aux1), g1 = grad(_op_sublayer)(X, P, w, ct)
    for got, want in zip(aux1, aux0):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # values against the plain reference
    h_pre, h_post, h_res = ref.mixing(
        P, "", X.reshape(X.shape[:2] + (N, d)), REF_CFG)
    want = jnp.concatenate([h_pre, h_post, h_res.reshape(X.shape[:2]
                                                         + (N * N,))], -1)
    np.testing.assert_allclose(aux1[0], want, rtol=1e-5, atol=1e-6)
    # all seven gradients: X, gain, phi, a, b, and through u (w's) and y
    leaves = lambda g: jax.tree_util.tree_leaves(g)
    assert len(leaves(g1)) == 6
    for got, want in zip(leaves(g1), leaves(g0)):
        assert float(jnp.abs(want).max()) > 0
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(want).max()))
    gy0 = jax.grad(lambda y: (hc_ops._post(X, y, aux0[0], N) * ct).sum())(
        aux0[1])
    gy1 = jax.grad(lambda y: (hc_ops.mhc_post(X, y, aux0[0]) * ct).sum())(
        aux0[1])
    np.testing.assert_allclose(gy1, gy0, rtol=2e-4, atol=2e-5)


def test_in_bf16_the_kernels_are_no_further_from_f32_than_the_jax_form(
        kernels_on):
    X, P, w, ct = _case(64, 256, jnp.bfloat16)
    f32 = lambda t: jax.tree_util.tree_map(
        lambda v: v.astype(jnp.float32), t)
    grad = lambda f: jax.value_and_grad(f, (0, 1, 2), has_aux=True)
    (_, aux32), g32 = grad(_jax_sublayer)(f32(X), f32(P), f32(w), ct)
    (_, aux0), g0 = grad(_jax_sublayer)(X, P, w, ct)
    (_, aux1), g1 = grad(_op_sublayer)(X, P, w, ct)
    assert aux1[1].dtype == aux1[2].dtype == jnp.bfloat16
    assert aux1[0].dtype == jnp.float32
    leaves = jax.tree_util.tree_leaves
    for got, base, want in zip(leaves((aux1, g1)), leaves((aux0, g0)),
                               leaves((aux32, g32))):
        assert got.dtype == base.dtype
        # a rounding's worth of room: the two forms round at other places
        assert _rel(got, want) <= 1.5 * _rel(base, want) + 2e-3


def test_the_clamp_case_of_the_jax_forms_test(kernels_on):
    d = 128
    X = _rand(9, (2, 5, N * d))
    P = _leaves(d)
    zero_phi = jnp.zeros_like(P["phi_weight"])
    far = P["b"].at[8:].set(jnp.where(P["b"][8:] > 0, 100.0, -100.0))
    at = P["b"].at[8:].set(jnp.where(P["b"][8:] > 0, 30.0, -30.0))

    def res(b, **kw):
        return hc_ops.mhc_coefficients(X, P["norm_gamma"], zero_phi, P["a"],
                                       b, **kw)[..., 8:]

    assert bool(jnp.isfinite(res(far)).all())
    np.testing.assert_array_equal(res(far), res(at))
    assert float(jnp.abs(res(far) - res(far, clamp_min=-1.0,
                                        clamp_max=1.0)).max()) > 1e-3
    np.testing.assert_allclose(
        res(far), hc_ops._coefficients(X, P["norm_gamma"], zero_phi, P["a"],
                                       far, *ATTRS)[..., 8:],
        rtol=1e-5, atol=1e-6)


def _flat(v):
    return v.reshape((1, -1, v.shape[-1]))


@pytest.mark.parametrize("kernel", ["coef", "pre", "post", "post_bwd",
                                    "coef_pre_bwd"])
def test_each_kernel_alone_against_the_jax_form(kernel):
    d, tokens, tile = 128, 32, 16
    X, P, _, ct = _case(tokens, d)
    X, ct = _flat(X), _flat(ct)
    gain, phi, a, b = _params(P)
    c0 = hc_ops._coefficients(X, gain, phi, a, b, *ATTRS)
    y = _rand(21, (1, tokens, d))
    close = lambda got, want: np.testing.assert_allclose(
        got, want, rtol=2e-4, atol=2e-5 * float(jnp.abs(want).max()))
    if kernel == "coef":
        c, s = mhc_mix._coef(CFG, tile, X, gain, phi, a, b)
        close(c, c0)
        # S: the product before a and b, then the norm's factor
        xf = X[0]
        r = jax.lax.rsqrt(jnp.square(xf).mean(-1) + 1e-6)
        close(s[0, :, K], r)
        close(s[0, :, :K], (xf * r[:, None] * gain) @ phi.T)
        assert float(jnp.abs(s[0, :, K + 1:]).max()) == 0
    elif kernel == "pre":
        close(mhc_mix._pre(CFG, tile, X, c0), hc_ops._pre(X, c0, N))
    elif kernel == "post":
        close(mhc_mix._post(CFG, tile, X, y, c0), hc_ops._post(X, y, c0, N))
    elif kernel == "post_bwd":
        _, vjp = jax.vjp(lambda x, y, c: hc_ops._post(x, y, c, N), X, y, c0)
        gx0, gy0, dc0 = vjp(ct)
        gy, gx, dc = mhc_mix._post_bwd(CFG, tile, ct, X, y, c0)
        close(gy, gy0), close(gx, gx0), close(dc, dc0)
        assert float(jnp.abs(dc[..., :N]).max()) == 0
    else:
        def both(x, gain, phi, a, b):
            c = hc_ops._coefficients(x, gain, phi, a, b, *ATTRS)
            return c, hc_ops._pre(x, c, N)

        _, vjp = jax.vjp(both, X, gain, phi, a, b)
        gc, gu, gxp = _rand(22, c0.shape), _rand(23, y.shape), ct
        want = vjp((gc, gu))
        _, s = mhc_mix._coef(CFG, tile, X, gain, phi, a, b)
        got = mhc_mix._coefficients_pre_bwd(
            CFG, (X, s, gain, phi, a, b), (gc, gu, gxp))
        close(got[0], want[0] + gxp)
        for g, w in zip(got[1:], want[1:]):
            close(g, w)


class _Layer:
    """Two hyper-connected sublayers, as ``models/xing.py`` ``XingLayer``
    calls the ops, with ``F(u) = w u``."""

    def __init__(self, leaves, w):
        self.leaves, self.w = leaves, w

    def __call__(self, x):
        for P in self.leaves:
            c = nd._contrib_mhc_coefficients(
                x, *(nd.NDArray(v) for v in _params(P)))
            u = nd._contrib_mhc_pre(x, c)
            x = nd._contrib_mhc_post(x, u * nd.NDArray(self.w), c)
        return x


def _layer_loss(X, leaves, w, ct):
    out = checkpointed(_Layer(leaves, w), nd.NDArray(X))._data
    return (out * ct).sum()


def test_a_recomputed_layer_keeps_c_and_runs_sinkhorn_once(kernels_on):
    d, tokens = 128, 32
    X, P, w, ct = _case(tokens, d)
    leaves = [P, _leaves(d, key=31)]
    with recompute.tally() as kept:
        text = str(jax.make_jaxpr(jax.grad(_layer_loss, (0, 1)))(
            X, leaves, w, ct))
    # call sites of the kernels' jitted wrappers (a wrapper's own jaxpr, the
    # pallas_call in it, prints once however often it is called)
    calls = re.findall(r"name=(_coef|_pre|_post|_post_bwd|_coef_pre_bwd)\b",
                       text)
    assert set(re.findall(r"name=(mx_mhc_\w+)", text)) == {
        "mx_mhc_coef", "mx_mhc_pre", "mx_mhc_post", "mx_mhc_post_bwd",
        "mx_mhc_coef_pre_bwd"}
    # the coefficients once a sublayer; u again in the second forward; the
    # second sublayer's X' is the layer's result and not made again
    assert {name: calls.count(name) for name in set(calls)} == {
        "_coef": 2, "_pre": 4, "_post": 3, "_post_bwd": 2,
        "_coef_pre_bwd": 2}
    # nothing of the jax form, Sinkhorn's scan least of all
    assert "name=_coefficients" not in text
    # C and S of both sublayers stay: (tokens, 24) and (tokens, 32) f32
    assert (kept.layers, kept.tensors) == (1, 4)
    assert kept.bytes == 2 * tokens * (K + mhc_mix.S_WIDTH) * 4
    # and the gradients are the jax form's
    g1 = jax.grad(_layer_loss, (0, 1))(X, leaves, w, ct)
    hc_ops._kernels = lambda *a: None       # the fixture puts it back
    g0 = jax.grad(_layer_loss, (0, 1))(X, leaves, w, ct)
    for got, want in zip(jax.tree_util.tree_leaves(g1),
                         jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(want).max()))


def test_ops_that_do_not_come_as_a_sublayer_still_agree(kernels_on):
    """``mhc_pre`` with other streams than the coefficients were made from
    takes the jax form; ``mhc_post`` then reads its own streams."""
    X, P, w, ct = _case(32, 128)
    other = _rand(41, X.shape)

    def loss(form, X, other):
        pre, post = ((hc_ops.mhc_pre, hc_ops.mhc_post) if form == "ops" else
                     (lambda s, c: hc_ops._pre(s, c, N),
                      lambda s, y, c: hc_ops._post(s, y, c, N)))
        c = (hc_ops.mhc_coefficients(X, *_params(P)) if form == "ops" else
             hc_ops._coefficients(X, *_params(P), *ATTRS))
        return (post(other, pre(other, c) * w, c) * ct).sum()

    g1 = jax.grad(lambda *a: loss("ops", *a), (0, 1))(X, other)
    g0 = jax.grad(lambda *a: loss("jax", *a), (0, 1))(X, other)
    for got, want in zip(g1, g0):
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(want).max()))


def _s(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("shape,dtype,n,want", [
    ((1, 4096, 14336), jnp.bfloat16, 4, True),      # the cell
    ((2, 100, 512), jnp.float32, 4, True),          # padded tokens
    ((4096, 14336), jnp.bfloat16, 4, True),
    ((1, 64, 4 * 96), jnp.bfloat16, 4, False),      # no whole lane tiles
    ((1, 64, 512), jnp.float16, 4, False),
    ((1, 64, 6 * 128), jnp.bfloat16, 6, False),     # 48 coefficients
    ((1, 64, 4 * 65536), jnp.bfloat16, 4, False),   # no tile fits VMEM
    ((14336,), jnp.bfloat16, 4, False),
])
def test_supported_reads_shapes_and_types_only(shape, dtype, n, want):
    assert mhc_mix.supported(_s(shape, dtype), n) is want


def test_the_kernels_are_chosen_on_a_tpu_in_per_device_code_only():
    x = _s((1, 4096, 14336))
    assert hc_ops._kernels(x, 4, 24) is None                 # the CPU
    with pk.compute_on("tpu"):
        assert hc_ops._kernels(x, 4, 24) is mhc_mix
        assert hc_ops._kernels(_s((1, 64, 4 * 96)), 4, 24) is None
    with pk.compute_on("tpu", partitioned=True):
        assert hc_ops._kernels(x, 4, 24) is None
    tiles = mhc_mix._tiles(4096, 14336, 4, 24, 2)
    assert tiles == {"coef": 128, "pre": 128, "post": 64, "post_bwd": 32,
                     "coef_pre_bwd": 32}
    assert mhc_mix._tiles(7, 512, 4, 24, 4)["post"] == 16
