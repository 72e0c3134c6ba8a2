"""Fused row-wise Pallas kernels: softmax cross-entropy and layer norm.

Reference parity:
  * softmax_cross_entropy: src/operator/nn/softmax{-inl.h,.cc,.cu} fused
    log-softmax + gather (the reference fuses softmax with its grad; here
    the whole loss row reduces in one VMEM pass);
  * layer_norm: src/operator/nn/layer_norm* (Welford pass + affine in one
    kernel).

Backward passes are closed-form jnp expressions under jax.custom_vjp —
XLA fuses those chains on its own; the win of Pallas is the forward
single-pass reduction without materialising intermediates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _interpret() -> bool:
    from . import interpret

    return interpret()


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Mosaic double-buffers every blocked operand inside a 16 MiB scoped-VMEM
# limit (v5e); half of it for the row blocks leaves the kernel's own
# temporaries their room.
_BLOCK_VMEM_BYTES = 8 << 20


def _row_block(n: int, row_bytes: int) -> int:
    """Rows per grid step for a row-wise kernel: at most 256, fewer when
    the rows are wide.  ``row_bytes`` is what one row occupies across all
    the (bn, C) operands, inputs and outputs, at lane-padded width."""
    fit = _BLOCK_VMEM_BYTES // (2 * row_bytes) // 8 * 8
    return max(8, min(256, fit, _round_up(n, 8)))


def _lane_bytes(c: int, *dtypes) -> int:
    return _round_up(c, 128) * sum(jnp.dtype(d).itemsize for d in dtypes)


# ---------------------------------------------------------------------------
# softmax cross-entropy
# ---------------------------------------------------------------------------
def _sce_kernel(ignore_label, x_ref, y_ref, loss_ref):
    x = x_ref[...].astype(jnp.float32)            # (bn, C)
    y = y_ref[...]                                # (bn, 1) int32
    m = x.max(axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    lse = m[:, 0] + jnp.log(e.sum(axis=-1))
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.where(cols == y, x, 0.0).sum(axis=-1)
    loss = lse - picked
    if ignore_label is not None:
        loss = jnp.where(y[:, 0] == ignore_label, 0.0, loss)
    loss_ref[...] = loss[:, None]


def _sce_fwd_impl(logits, labels, ignore_label):
    n, c = logits.shape
    bn = _row_block(n, _lane_bytes(c, logits.dtype))
    n_p = _round_up(n, bn)
    x = jnp.pad(logits, ((0, n_p - n), (0, 0)))
    y = jnp.pad(labels.astype(jnp.int32), ((0, n_p - n),))[:, None]
    loss = pl.pallas_call(
        functools.partial(_sce_kernel, ignore_label),
        name="mx_softmax_xent",
        grid=(n_p // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
        interpret=_interpret(),
    )(x, y)
    return loss[:n, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def softmax_cross_entropy(logits, labels, ignore_label=None):
    """Per-row -log softmax(logits)[label]; logits (N, C), labels (N,) int.

    Rows whose label equals `ignore_label` contribute zero loss/grad.
    """
    return _sce_fwd_impl(logits, labels, ignore_label)


def _sce_fwd(logits, labels, ignore_label):
    return _sce_fwd_impl(logits, labels, ignore_label), (logits, labels)


def _sce_bwd(ignore_label, res, g):
    logits, labels = res
    x = logits.astype(jnp.float32)
    p = jax.nn.softmax(x, axis=-1)
    onehot = jax.nn.one_hot(labels, x.shape[-1], dtype=jnp.float32)
    d = (p - onehot) * g[:, None]
    if ignore_label is not None:
        d = jnp.where((labels == ignore_label)[:, None], 0.0, d)
    return d.astype(logits.dtype), None


softmax_cross_entropy.defvjp(_sce_fwd, _sce_bwd)


# ---------------------------------------------------------------------------
# layer norm
# ---------------------------------------------------------------------------
def _ln_kernel(eps, x_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref):
    x = x_ref[...].astype(jnp.float32)            # (bn, C)
    mu = x.mean(axis=-1, keepdims=True)
    xc = x - mu
    var = (xc * xc).mean(axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o = xc * rstd * g_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _ln_fwd_impl(x, gamma, beta, eps):
    n, c = x.shape
    bn = _row_block(n, _lane_bytes(c, x.dtype, x.dtype))
    n_p = _round_up(n, bn)
    xp = jnp.pad(x, ((0, n_p - n), (0, 0)))
    out, mu, rstd = pl.pallas_call(
        functools.partial(_ln_kernel, eps),
        name="mx_layer_norm_fwd",
        grid=(n_p // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_p, c), x.dtype),
                   jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n_p, 1), jnp.float32)],
        interpret=_interpret(),
    )(xp, gamma[None, :], beta[None, :])
    return out[:n], mu[:n], rstd[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x, gamma, beta, eps=1e-5):
    """Row-wise layer norm over the last axis; x (N, C), gamma/beta (C,)."""
    out, _, _ = _ln_fwd_impl(x, gamma, beta, eps)
    return out


def _ln_fwd(x, gamma, beta, eps):
    out, mu, rstd = _ln_fwd_impl(x, gamma, beta, eps)
    return out, (x, gamma, mu, rstd)


def _ln_bwd(eps, res, g):
    x, gamma, mu, rstd = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = (xf - mu) * rstd
    dgamma = (gf * xhat).sum(axis=0)
    dbeta = gf.sum(axis=0)
    dxhat = gf * gamma.astype(jnp.float32)[None, :]
    c = x.shape[-1]
    dx = rstd / c * (c * dxhat - dxhat.sum(-1, keepdims=True)
                     - xhat * (dxhat * xhat).sum(-1, keepdims=True))
    return dx.astype(x.dtype), dgamma.astype(gamma.dtype), dbeta.astype(
        gamma.dtype)


layer_norm.defvjp(_ln_fwd, _ln_bwd)


# ---------------------------------------------------------------------------
# fused residual-add + layer norm
# ---------------------------------------------------------------------------
def _aln_kernel(eps, x_ref, r_ref, g_ref, b_ref, o_ref, mu_ref, rstd_ref):
    x = (x_ref[...].astype(jnp.float32)
         + r_ref[...].astype(jnp.float32))     # (bn, C): the fused add
    mu = x.mean(axis=-1, keepdims=True)
    xc = x - mu
    var = (xc * xc).mean(axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    o = xc * rstd * g_ref[...].astype(jnp.float32) + b_ref[...].astype(
        jnp.float32)
    o_ref[...] = o.astype(o_ref.dtype)
    mu_ref[...] = mu
    rstd_ref[...] = rstd


def _aln_fwd_impl(x, res, gamma, beta, eps):
    n, c = x.shape
    bn = _row_block(n, _lane_bytes(c, x.dtype, res.dtype, x.dtype))
    n_p = _round_up(n, bn)
    xp = jnp.pad(x, ((0, n_p - n), (0, 0)))
    rp = jnp.pad(res, ((0, n_p - n), (0, 0)))
    out, mu, rstd = pl.pallas_call(
        functools.partial(_aln_kernel, eps),
        name="mx_add_layer_norm_fwd",
        grid=(n_p // bn,),
        in_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((bn, c), lambda i: (i, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0)),
                  pl.BlockSpec((1, c), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((bn, c), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_p, c), x.dtype),
                   jax.ShapeDtypeStruct((n_p, 1), jnp.float32),
                   jax.ShapeDtypeStruct((n_p, 1), jnp.float32)],
        interpret=_interpret(),
    )(xp, rp, gamma[None, :], beta[None, :])
    return out[:n], mu[:n], rstd[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def add_layer_norm(x, res, gamma, beta, eps=1e-5):
    """Fused residual add + row-wise layer norm: LN(x + res) in ONE VMEM
    pass — the pre-norm transformer block boundary never materialises
    the sum.  x/res (N, C), gamma/beta (C,)."""
    out, _, _ = _aln_fwd_impl(x, res, gamma, beta, eps)
    return out


def _aln_fwd(x, res, gamma, beta, eps):
    out, mu, rstd = _aln_fwd_impl(x, res, gamma, beta, eps)
    return out, (x, res, gamma, mu, rstd)


def _aln_bwd(eps, resids, g):
    x, res, gamma, mu, rstd = resids
    s = x.astype(jnp.float32) + res.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = (s - mu) * rstd
    dgamma = (gf * xhat).sum(axis=0)
    dbeta = gf.sum(axis=0)
    dxhat = gf * gamma.astype(jnp.float32)[None, :]
    c = x.shape[-1]
    ds = rstd / c * (c * dxhat - dxhat.sum(-1, keepdims=True)
                     - xhat * (dxhat * xhat).sum(-1, keepdims=True))
    # the add fans the cotangent out to BOTH branches unchanged
    return (ds.astype(x.dtype), ds.astype(res.dtype),
            dgamma.astype(gamma.dtype), dbeta.astype(gamma.dtype))


add_layer_norm.defvjp(_aln_fwd, _aln_bwd)
