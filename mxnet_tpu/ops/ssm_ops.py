"""State-space (Mamba-2) operators and the norms that go with them.

No reference counterpart: MXNet 1.x has no RMSNorm, no state-space layer and
no chunked scan.  The equations are those of Dao & Gu 2024 ("Transformers
are SSMs", the SSD form) as the ``nemotron_h`` family publishes them.

``_contrib_ssd_scan`` computes, per head (A < 0 a scalar, state N wide),

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t,   y_t = h_t C_t + D x_t

by the chunked form: inside a chunk of ``chunk`` positions the recurrence is
one masked (chunk x chunk) product, across chunks only the state at each
chunk's end is carried.  Autodiff through it keeps one state per CHUNK, never
one per position; lengths that are no multiple of the chunk are padded with
``dt = 0`` (decay 1, no input), which leaves every real position as it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register


@register("_contrib_rms_norm")
def rms_norm(data, gamma, eps=1e-5):
    """``x / sqrt(mean(x^2) + eps) * gamma`` over the last axis, in f32."""
    x32 = data.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (x32 * inv * gamma.astype(jnp.float32)).astype(data.dtype)


@register("_contrib_gated_rms_norm")
def gated_rms_norm(data, gate, gamma, group_size=None, eps=1e-5):
    """``RMSNorm_grouped(data * silu(gate)) * gamma``: the norm is taken
    over consecutive groups of ``group_size`` channels (all of them when
    None), the gain is per channel."""
    c = data.shape[-1]
    g = c if group_size is None else int(group_size)
    x = data.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
    xg = x.reshape(x.shape[:-1] + (c // g, g))
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(xg), -1, keepdims=True) + eps)
    out = (xg * inv).reshape(x.shape) * gamma.astype(jnp.float32)
    return out.astype(data.dtype)


@register("_contrib_relu2")
def relu2(data):
    """``max(x, 0)^2``."""
    r = jnp.maximum(data, 0)
    return r * r


@register("_contrib_causal_conv1d")
def causal_conv1d(data, weight, bias=None, activation=None):
    """Depthwise causal convolution along axis 1 of ``data`` (B, L, C) with
    ``weight`` (C, K): ``y_t = sum_k w[:, k] x_{t-K+1+k} (+ bias)``, zeros
    before position 0; ``activation="silu"`` applies it to the result."""
    with jax.named_scope("mx_conv1d"):
        k = weight.shape[-1]
        length = data.shape[1]
        x = jnp.pad(data.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        w = weight.astype(jnp.float32)
        out = sum(x[:, i:i + length] * w[:, i] for i in range(k))
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        if activation == "silu":
            out = jax.nn.silu(out)
        elif activation is not None:
            raise ValueError(f"causal_conv1d: activation {activation!r}")
        return out.astype(data.dtype)


@register("_contrib_causal_conv1d_heads")
def causal_conv1d_heads(data, weight, bias=None):
    """Causal convolution along axis 1 of ``data`` (B, L, H * D) that mixes
    the D channels inside each of its H heads: ``weight`` (H, K, D, D) holds
    a (D in, D out) matrix a head a tap, ``y_t[h] = sum_k x_{t-K+1+k}[h]
    weight[h, k] (+ bias)``, zeros before position 0.  The taps are
    contracted as one product K * D deep, summed in f32."""
    with jax.named_scope("mx_conv1d_heads"):
        heads, k, d, _ = weight.shape
        batch, length, _ = data.shape
        x = jnp.pad(data.reshape(batch, length, heads, d),
                    ((0, 0), (k - 1, 0), (0, 0), (0, 0)))
        taps = jnp.concatenate([x[:, i:i + length] for i in range(k)], -1)
        out = jnp.einsum("blhc,hce->blhe", taps,
                         weight.reshape(heads, k * d, d),
                         preferred_element_type=jnp.float32)
        out = out.reshape(batch, length, heads * d)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
        return out.astype(data.dtype)


def _chunk_states(decay, s_local):
    """State at the START of every chunk: ``h_0 = 0``,
    ``h_{c+1} = decay_c h_c + s_local_c``; (b, c, ...) with c the scanned
    axis."""
    def step(h, inp):
        d, s = inp
        return d[..., None, None] * h + s, h

    h0 = jnp.zeros_like(s_local[:, 0])
    _last, starts = jax.lax.scan(
        step, h0, (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(s_local, 1, 0)))
    return jnp.moveaxis(starts, 0, 1)


@register("_contrib_ssd_scan")
def ssd_scan(x, dt, a_log, b, c, d, dt_bias, chunk=128):
    """Mamba-2 selective state-space scan by chunks.

    x (B, L, H, P); dt (B, L, H) before its softplus; a_log, d, dt_bias
    (H,); b, c (B, L, G, N) with H a multiple of G (a group serves H / G
    heads).  Returns y (B, L, H, P) in x's type.  Decays are computed in
    f32; the matrix products take their operands in x's type and
    accumulate in f32.

    On a TPU, where the code is per-device and the shapes are whole tiles
    (``pallas.ssd_scan.supported``), the scan is two Mosaic kernels that keep
    a chunk's intermediates on chip (``ops/pallas/ssd_scan.py``); anywhere
    else it is the einsum form below.  Same equations, same precision.
    """
    from . import pallas as _pk
    from .pallas import ssd_scan as _kernel

    if _pk.enabled() and _pk.use_compiled() \
            and _kernel.supported(x, b, int(chunk)):
        return _kernel.ssd_scan(x, dt, a_log, b, c, d, dt_bias,
                                chunk=int(chunk))
    with jax.named_scope("mx_ssd_scan"):
        return _ssd_scan(x, dt, a_log, b, c, d, dt_bias, int(chunk))


def _ssd_scan(x, dt, a_log, b, c, d, dt_bias, q):
    f32 = jnp.float32
    bsz, length, heads, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    rep = heads // groups
    dtype = x.dtype
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    a = -jnp.exp(a_log.astype(f32))
    pad = (-length) % q
    if pad:
        widths = ((0, 0), (0, pad))
        x = jnp.pad(x, widths + ((0, 0), (0, 0)))
        b = jnp.pad(b, widths + ((0, 0), (0, 0)))
        c = jnp.pad(c, widths + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, widths + ((0, 0),))
    nc = (length + pad) // q
    # head-major, so that the large operands end in (chunk, chunk),
    # (chunk, P) or (P, N): whole tiles on the chip
    xc = jnp.moveaxis(x.reshape(bsz, nc, q, groups, rep, p), 2, 4)
    bc = jnp.moveaxis(b.reshape(bsz, nc, q, groups, n), 2, 3)
    cc = jnp.moveaxis(c.reshape(bsz, nc, q, groups, n), 2, 3)
    dtc = jnp.moveaxis(dt.reshape(bsz, nc, q, groups, rep), 2, 4)
    acum = jnp.cumsum(dtc * a.reshape(groups, rep, 1), axis=-1)  # log decay
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)     # (b,c,g,r,q,p)

    # inside a chunk: y_t += sum_{s<=t} exp(acum_t - acum_s) (C_t.B_s) dt_s x_s
    cb = jnp.einsum("bcgqn,bcgsn->bcgqs", cc, bc,
                    preferred_element_type=f32)
    seg = acum[..., :, None] - acum[..., None, :]             # (b,c,g,r,q,s)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg,
                              -jnp.inf))
    m = (cb[:, :, :, None] * decay).astype(dtype)
    y = jnp.einsum("bcgrqs,bcgrsp->bcgrqp", m, xdt,
                   preferred_element_type=f32)

    # each chunk's own contribution to the state at its end
    to_end = jnp.exp(acum[..., -1:] - acum)                   # (b,c,g,r,q)
    xw = (xdt.astype(f32) * to_end[..., None]).astype(dtype)
    s_local = jnp.einsum("bcgsn,bcgrsp->bcgrpn", bc, xw,
                         preferred_element_type=f32)
    starts = _chunk_states(jnp.exp(acum[..., -1]), s_local)   # (b,c,g,r,p,n)

    # what the state at the chunk's start adds
    y_in = jnp.einsum("bcgqn,bcgrpn->bcgrqp", cc, starts.astype(dtype),
                      preferred_element_type=f32)
    y = y + y_in * jnp.exp(acum)[..., None]
    y = y + xc.astype(f32) * d.astype(f32).reshape(groups, rep, 1, 1)
    y = jnp.moveaxis(y, 4, 2).reshape(bsz, nc * q, heads, p)[:, :length]
    return y.astype(dtype)
