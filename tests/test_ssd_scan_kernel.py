"""The Mamba-2 chunked scan's Pallas kernels (``ops/pallas/ssd_scan.py``) in
interpret mode on the CPU: against the einsum form ``_ssd_scan`` and against
the per-position recurrence of the plain reference, values and all seven
gradients; the state carried across chunks against ``_chunk_states``; and
the rule by which ``ssd_scan`` picks one form or the other."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import pallas, ssm_ops
from mxnet_tpu.ops.pallas import ssd_scan as kernel

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")


@pytest.fixture(scope="module")
def ref():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "nemotron_reference", os.path.join(
            _REPO, "benchmark", "references",
            "nemotron_twotower_30b_a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _operands(length, dtype, rows=2, heads=4, p=8, groups=2, n=16):
    """Heads > groups; x, dt, b, c and the cotangent in ``dtype``."""
    ks = jax.random.split(jax.random.PRNGKey(length), 8)
    rand = lambda k, shape, scale=1.0: jax.random.normal(
        k, shape, jnp.float32) * scale
    x = rand(ks[0], (rows, length, heads, p)).astype(dtype)
    dt = (rand(ks[1], (rows, length, heads)) - 3.0).astype(dtype)
    b = rand(ks[2], (rows, length, groups, n)).astype(dtype)
    c = rand(ks[3], (rows, length, groups, n)).astype(dtype)
    a_log, d, dt_bias = (rand(ks[4], (heads,), 0.5), rand(ks[5], (heads,)),
                         rand(ks[6], (heads,), 0.3))
    ct = rand(ks[7], (rows, length, heads, p)).astype(dtype)
    return (x, dt, a_log, b, c, d, dt_bias), ct


def _by_kernel(*args):
    return kernel.ssd_scan(*args, chunk=128)


def _by_einsums(*args):
    return ssm_ops._ssd_scan(*args, 128)


def _recurrence(ref):
    def plain(x, dt, a_log, b, c, d, dt_bias):
        return ref.recurrence(x, jax.nn.softplus(dt + dt_bias),
                              -jnp.exp(a_log), b, c, d)
    return plain


def _value_and_grads(fn, args, ct):
    y, vjp = jax.vjp(fn, *args)
    return [y] + list(vjp(ct))


def _gaps(got, want):
    """Largest difference of every output over the largest of ``want``."""
    out = []
    for g, w in zip(got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        out.append(float(np.abs(g - w).max() / (np.abs(w).max() + 1e-6)))
    return out


@pytest.mark.parametrize("length", [256, 200])
def test_kernels_match_the_einsum_form_and_the_recurrence_in_f32(ref, length):
    args, ct = _operands(length, jnp.float32)
    got = _value_and_grads(_by_kernel, args, ct)
    for other in (_by_einsums, _recurrence(ref)):
        want = _value_and_grads(other, args, ct)
        np.testing.assert_allclose(got[0], want[0], rtol=2e-4, atol=2e-4)
        for name, gap in zip(NAMES, _gaps(got[1:], want[1:])):
            assert gap < 3e-4, (other.__name__, name, gap)


@pytest.mark.parametrize("length", [256, 200])
def test_kernels_in_bf16_stay_within_the_einsum_forms_own_gap(ref, length):
    """In bf16 the two forms round at different places; they agree to within
    what the einsum form itself loses against the f32 recurrence."""
    args, ct = _operands(length, jnp.bfloat16)
    f32 = lambda v: v.astype(jnp.float32)
    exact = _value_and_grads(_recurrence(ref), [f32(a) for a in args],
                             f32(ct))
    got = _value_and_grads(_by_kernel, args, ct)
    want = _value_and_grads(_by_einsums, args, ct)
    assert got[0].dtype == jnp.bfloat16
    assert [g.dtype for g in got[1:]] == [a.dtype for a in args]
    own = _gaps(want, exact)
    for name, gap, room, to_exact in zip(("y",) + NAMES, _gaps(got, want),
                                         own, _gaps(got, exact)):
        # one rounding of the result itself on top of the form's own gap
        assert gap <= room + 2.0 ** -7, (name, gap, room)
        assert to_exact <= 2 * room + 2.0 ** -7, (name, to_exact, room)


def test_state_carried_across_chunks_matches_chunk_states():
    length, q = 384, 128
    (x, dt, a_log, b, c, _d, dt_bias), _ct = _operands(length, jnp.float32)
    rows, _, heads, p = x.shape
    groups, n = b.shape[2:]
    rep, nc = heads // groups, length // q
    got = kernel.chunk_states(x, dt, a_log, b, c, dt_bias, chunk=q)
    assert got.shape == (rows, nc, groups, rep, p, n)
    assert got.dtype == jnp.float32
    # the same from the definitions, through ssm_ops._chunk_states
    dtv = jax.nn.softplus(dt + dt_bias).reshape(rows, nc, q, groups, rep)
    acum = jnp.cumsum(dtv * -jnp.exp(a_log).reshape(groups, rep), axis=2)
    to_end = jnp.exp(acum[:, :, -1:] - acum)
    xw = x.reshape(rows, nc, q, groups, rep, p) * (dtv * to_end)[..., None]
    s_local = jnp.einsum("bcsgn,bcsgrp->bcgrpn",
                         b.reshape(rows, nc, q, groups, n), xw)
    want = ssm_ops._chunk_states(jnp.exp(acum[:, :, -1]), s_local)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert not np.asarray(got[:, 0]).any()       # h_0 = 0


def _scan_kernels(platform=None, partitioned=False, n=128, p=64):
    """Names of the Pallas calls ``ssm_ops.ssd_scan`` traces to."""
    z = jnp.zeros
    args = (z((1, 256, 2, p)), z((1, 256, 2)), z((2,)), z((1, 256, 1, n)),
            z((1, 256, 1, n)), z((2,)), z((2,)))
    fn = lambda *a: ssm_ops.ssd_scan(*a, chunk=128)
    if platform is None:
        jaxpr = jax.make_jaxpr(fn)(*args)
    else:
        with pallas.compute_on(platform, partitioned):
            jaxpr = jax.make_jaxpr(fn)(*args)
    return _pallas_calls(jaxpr.jaxpr)


def _pallas_calls(jaxpr):
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in eqn.params.values():      # custom_vjp and jit bodies
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                names += _pallas_calls(sub)
    return names


@pytest.mark.parametrize("where, want", [
    ((None,), []),                            # this process: the CPU
    (("cpu",), []),
    (("tpu", True), []),                      # GSPMD would have to split it
    (("tpu",), ["mx_ssd_fwd"]),
])
def test_ssd_scan_picks_the_kernel_on_an_unpartitioned_tpu_only(where, want):
    assert _scan_kernels(*where) == want


@pytest.mark.parametrize("shape", [dict(n=16), dict(p=4), dict(p=48)])
def test_ssd_scan_keeps_the_einsum_form_for_shapes_off_the_tiles(shape):
    assert _scan_kernels("tpu", **shape) == []


def test_supported_reads_chunk_state_head_dim_and_groups():
    x = jax.ShapeDtypeStruct((2, 8192, 64, 64), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16)
    assert kernel.supported(x, b, 128)
    assert not kernel.supported(x, b, 64)                    # chunk
    odd = jax.ShapeDtypeStruct((2, 8192, 7, 128), jnp.bfloat16)
    assert not kernel.supported(x, odd, 128)                 # heads % groups
    wide = jax.ShapeDtypeStruct((2, 8192, 8, 256), jnp.bfloat16)
    assert kernel.supported(wide, b, 256)                    # P of two tiles
    assert not kernel.supported(wide, b, 128)                # wider than a chunk
    lone = jax.ShapeDtypeStruct((2, 8192, 8, 64), jnp.bfloat16)
    assert not kernel.supported(lone, b, 128)    # one 64-wide head a group
