"""Hyper-connections with manifold-constrained mixing (arXiv:2409.19606,
arXiv:2512.24880): the residual path of a layer is ``n`` streams, and every
sublayer ``F`` reads a mix of them and writes back into a mix of them.

No reference counterpart.  The streams of a token lie side by side on the
last axis, ``X = [X_0 | ... | X_(n-1)]`` (``n * d`` wide: ``vec(X)`` is the
tensor itself and a stream is a slice at whole lane tiles).  A sublayer is

    C = mhc_coefficients(X, ...)          # per token: H_pre, H_post, H_res
    u = mhc_pre(X, C)                     # sum_i H_pre[i] X_i
    X' = mhc_post(X, F(u), C)             # X'_i = sum_j H_res[i, j] X_j
                                          #        + H_post[i] y

with, in f32, ``x' = RMSNorm(vec(X))``, ``[P | Q | R] = a * (x' phi) + b``
(ONE product ``n d`` by ``2 n + n^2``), ``H_pre = sigmoid(P)``, ``H_post = 2
sigmoid(Q)`` and ``H_res = SK(clip(R))``: ``exp`` then ``iters`` times rows
over their sums, columns over their sums (Sinkhorn-Knopp: the matrix ends
nearly doubly stochastic, so the mix of the streams neither grows nor
shrinks them).  The iterations run with tokens on the LANES, as ``(n, n,
tokens)``: a ``(tokens, n, n)`` tensor would fill 4 of every 128 lanes.
Everything is differentiable jax (the iterations are a ``lax.scan``); the ops
trace once a shape (``jax.jit`` inside), not once a sublayer.

On a TPU, where the code is per-device and the streams are whole lane tiles
wide (``pallas.mhc_mix.supported``), the three ops are Mosaic kernels under
two ``custom_vjp``s with a written-out backward (``ops/pallas/mhc_mix.py``);
anywhere else they are the jax form below, which stays the one statement of
the equations.  The kernels want a sublayer whole: ``mhc_coefficients`` there
makes ``u`` in the same op and remembers it for the ``mhc_pre`` that comes
with the SAME streams and coefficients (the very arrays, inside one trace),
and ``mhc_post`` reads the streams through that op, so that one backward
kernel gets every part of the streams' gradient.  A call that does not come
so (other streams, an eager call op by op) takes ``u`` from the jax form.
"""
from __future__ import annotations

import functools
from contextvars import ContextVar
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register

SCOPE = "mx_mhc_mix"

class _Sublayer(NamedTuple):
    """What the kernel form of ``mhc_coefficients`` made beside ``coeffs``."""
    streams: jax.Array
    coeffs: jax.Array
    u: jax.Array
    through: jax.Array      # the streams as ``coefficients_pre`` hands them on


#: the last kernel-made sublayer of this trace
_sublayer: ContextVar = ContextVar("mhc_sublayer", default=None)


def _kernels(streams, n, k):
    """The Mosaic kernels' module where they are selected, else None."""
    from . import pallas as _pk
    from .pallas import mhc_mix as _kernel

    if _pk.enabled() and _pk.use_compiled() \
            and _kernel.supported(streams, n, k):
        return _kernel
    return None


def _flat(v):
    return v.reshape((1, -1, v.shape[-1]))


def _linked(streams, coeffs):
    """What ``mhc_coefficients`` remembered, if these are its arrays."""
    link = _sublayer.get()
    if link is not None and link.streams is streams \
            and link.coeffs is coeffs:
        return link
    return None


def sinkhorn(r, iters, eps):
    """``r`` (n, n, ...) -> ``exp(r)`` with its rows (axis 1 summed) then
    its columns (axis 0 summed) normalised, ``iters`` times; ``eps`` is
    added to every sum.  One ``lax.scan`` (differentiable, and its body
    compiles once, not once an iteration); the sums are written as adds of
    slices, so that an iteration is elementwise work XLA can fuse."""
    n = r.shape[0]

    def step(m, _):
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
        m = m / (sum(m[i] for i in range(n))[None] + eps)
        return m, None

    return jax.lax.scan(step, jnp.exp(r), None, length=int(iters))[0]


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9, 10))
def _coefficients(streams, gain, phi, a, b, n, iters, eps, clamp_min,
                  clamp_max, rms_eps):
    f32 = jnp.float32
    x = streams.astype(f32)
    x = x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + rms_eps) \
        * gain.astype(f32)
    # tokens on lanes from here: (2 n + n^2, tokens)
    proj = jnp.einsum("kc,tc->kt", phi, x.astype(phi.dtype).reshape(
        -1, x.shape[-1]), preferred_element_type=f32)
    scale = jnp.repeat(a.astype(f32), np.array([n, n, n * n]),
                       total_repeat_length=2 * n + n * n)
    proj = proj * scale[:, None] + b.astype(f32)[:, None]
    h_pre = jax.nn.sigmoid(proj[:n])
    h_post = 2 * jax.nn.sigmoid(proj[n:2 * n])
    h_res = sinkhorn(jnp.clip(proj[2 * n:], clamp_min, clamp_max).reshape(
        n, n, -1), iters, eps)
    out = jnp.concatenate([h_pre, h_post, h_res.reshape(n * n, -1)], 0)
    return out.T.reshape(streams.shape[:-1] + (2 * n + n * n,))


@register("_contrib_mhc_coefficients")
def mhc_coefficients(streams, gain, phi, a, b, n=4, iters=20, eps=1e-6,
                     clamp_min=-30.0, clamp_max=30.0, rms_eps=1e-6):
    """The mixing coefficients of one sublayer, per token.

    streams (..., n d); gain (n d,) of the RMSNorm over all streams; phi (2 n
    + n^2, n d): rows 0 .. n-1 project to ``P``, the next n to ``Q``, the last
    n^2 to ``R`` row-major; a (3,) the scalars of P, Q, R; b (2 n + n^2,).
    Returns (..., 2 n + n^2) f32: ``H_pre | H_post | H_res`` row-major
    (``H_res[i, j]`` at ``2 n + i n + j``)."""
    attrs = (int(n), int(iters), float(eps), float(clamp_min),
             float(clamp_max), float(rms_eps))
    kernel = _kernels(streams, int(n), phi.shape[0])
    with jax.named_scope(SCOPE):
        if kernel is None:
            return _coefficients(streams, gain, phi, a, b, *attrs)
        c, u, through = kernel.coefficients_pre(
            kernel.config(*attrs), _flat(streams), gain, phi, a, b)
        c = c.reshape(streams.shape[:-1] + c.shape[-1:])
        _sublayer.set(_Sublayer(
            streams, c, u.reshape(streams.shape[:-1] + (-1,)),
            through.reshape(streams.shape)))
        return c


def _split(streams, n):
    d = streams.shape[-1] // n
    return [streams[..., i * d:(i + 1) * d].astype(jnp.float32)
            for i in range(n)]


@functools.partial(jax.jit, static_argnums=(2,))
def _pre(streams, coeffs, n):
    xs = _split(streams, n)
    u = sum(coeffs[..., i:i + 1] * xs[i] for i in range(n))
    return u.astype(streams.dtype)


@register("_contrib_mhc_pre")
def mhc_pre(streams, coeffs, n=4):
    """What the sublayer reads: ``sum_i H_pre[i] X_i`` (..., d), summed in
    f32 and rounded once."""
    link = _linked(streams, coeffs)
    if link is not None:
        return link.u
    with jax.named_scope(SCOPE):
        return _pre(streams, coeffs, int(n))


@functools.partial(jax.jit, static_argnums=(3,))
def _post(streams, y, coeffs, n):
    xs, y = _split(streams, n), y.astype(jnp.float32)
    out = []
    for i in range(n):
        row = coeffs[..., n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            row = row + coeffs[..., k:k + 1] * xs[j]
        out.append(row.astype(streams.dtype))
    return jnp.concatenate(out, -1)


@register("_contrib_mhc_post")
def mhc_post(streams, y, coeffs, n=4):
    """The streams after the sublayer: ``X'_i = sum_j H_res[i, j] X_j +
    H_post[i] y`` (..., n d), summed in f32 and rounded once."""
    n = int(n)
    kernel = _kernels(streams, n, coeffs.shape[-1])
    with jax.named_scope(SCOPE):
        if kernel is None or y.dtype != streams.dtype:
            return _post(streams, y, coeffs, n)
        link = _linked(streams, coeffs)
        if link is not None:
            _sublayer.set(None)         # the sublayer's last op
        out = kernel.post(kernel.config(n),
                          _flat(streams if link is None else link.through),
                          _flat(y), _flat(coeffs))
        return out.reshape(streams.shape)
