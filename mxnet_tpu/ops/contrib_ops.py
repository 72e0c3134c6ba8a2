"""Contrib operators: fused attention (reference src/operator/contrib/
transformer.cc interleaved_matmul_selfatt_qk/valatt ~L1-300, superseded
here by a full flash-attention fusion).

CV contrib ops (NMS / multibox / ROI) live in cv_ops.py.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .registry import register


def _dense_attention(q, k, v, causal, sm_scale):
    s = jnp.einsum("nqd,nkd->nqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        qpos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        kpos = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where((qpos >= kpos)[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("nqk,nkd->nqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _yarn_blend(freq, rot, theta, factor, beta_fast, beta_slow, original_max):
    """YaRN's frequencies from the plain ones (``rot / 2`` of them): pair
    ``i`` keeps ``f_i`` below ``lo``, takes ``f_i / factor`` above ``hi`` and
    a linear blend between, ``lo`` and ``hi`` the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over ``original_max`` positions."""
    def pair(beta):
        return rot * math.log(original_max / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(beta_fast)), 0)
    hi = min(math.ceil(pair(beta_slow)), rot - 1)
    ramp = jnp.clip((jnp.arange(rot // 2, dtype=jnp.float32) - lo)
                    / max(hi - lo, 1e-3), 0, 1)
    return freq / factor * ramp + freq * (1 - ramp)


@register("_contrib_rotary")
def rotary(data, theta=10000.0, fraction=1.0, axis=1, yarn_factor=None,
           yarn_beta_fast=32.0, yarn_beta_slow=1.0, yarn_original_max=4096):
    """Rotary position on the first ``fraction`` of the last axis of
    ``data``; positions 0, 1, ... run along ``axis``.  With ``R = fraction *
    D`` rotated channels (even), channel ``i < R / 2`` pairs with ``i + R /
    2`` (rotate-half) and the pair turns by ``position * theta^(-2 i / R)``;
    channels from R on pass unchanged.  Angles and the rotation in f32.
    With ``yarn_factor`` the frequencies are YaRN's (``_yarn_blend``: a
    context stretched ``yarn_factor`` times past ``yarn_original_max``);
    cos and sin are not rescaled."""
    with jax.named_scope("mx_rotary"):
        d = data.shape[-1]
        rot = int(round(d * float(fraction)))
        if rot % 2 or not 0 < rot <= d:
            raise ValueError(f"rotary: {fraction} of {d} channels")
        axis = axis % data.ndim
        half = rot // 2
        freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
        if yarn_factor is not None:
            freq = _yarn_blend(freq, rot, float(theta), float(yarn_factor),
                               yarn_beta_fast, yarn_beta_slow,
                               yarn_original_max)
        pos = jnp.arange(data.shape[axis], dtype=jnp.float32)
        shape = [1] * data.ndim
        shape[axis], shape[-1] = data.shape[axis], half
        angle = (pos[:, None] * freq[None, :]).reshape(shape)
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x = data.astype(jnp.float32)
        x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
        out = jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)
        return out.astype(data.dtype)


@register("_contrib_flash_attention")
def flash_attention_op(q, k, v, causal=False, sm_scale=None):
    """Fused softmax(q k^T) v.  q/k/v: (N, L, D) or (B, H, L, D); with 4-d
    inputs k/v may carry fewer heads, (B, Hkv, L, D), H a multiple of Hkv
    (grouped-query attention): the Pallas kernel reads a key-value head for
    its H / Hkv query heads by index, the dense path repeats K and V.

    Pallas blockwise kernel on TPU; dense jnp composition elsewhere
    (XLA still fuses the chain, it just materialises scores).  Inside a
    DataParallelStep(ring_attention=True) trace with an active sp axis,
    3-d inputs route through the sequence-parallel ring kernel
    (parallel/ring.py): K/V rotate over ICI via ppermute and the full
    (L, L) score matrix never exists on any device.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    from ..parallel import ring_scope

    scope = ring_scope()
    if scope is not None and q.ndim == 3:
        mesh, batch_axes, mode = scope
        shape = dict(mesh.shape)
        sp = shape.get("sp", 1)
        n_batch = 1
        for a in batch_axes:
            n_batch *= shape.get(a, 1)
        # route to the SP kernel only when shard_map's divisibility holds
        # for EVERY operand dim it shards (self-attention, seq and batch
        # dims divisible; Ulysses also shards heads) — anything else
        # silently keeps the dense/Pallas path that runs the same shapes
        # without the scope
        ok = (sp > 1
              and q.shape[1] == k.shape[1] == v.shape[1]
              and q.shape[1] % sp == 0
              and q.shape[0] % max(n_batch, 1) == 0)
        if ok and mode == "ulysses":
            ok = (q.shape[0] // max(n_batch, 1)) % sp == 0
        if ok:
            if mode == "ulysses":
                from ..parallel.ulysses import ulysses_self_attention as sp_fn
            else:
                from ..parallel.ring import ring_self_attention as sp_fn
            return sp_fn(mesh, q, k, v, causal=causal, sm_scale=sm_scale,
                         batch_axes=batch_axes or None)
    from . import pallas as _pk

    if _pk.enabled() and _pk.use_compiled():
        return _pk.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if q.ndim == 4:
        b, h = q.shape[:2]
        if k.shape[1] != h:      # grouped-query heads: the dense path repeats
            k = jnp.repeat(k, h // k.shape[1], axis=1)
            v = jnp.repeat(v, h // v.shape[1], axis=1)
        out = _dense_attention(q.reshape(b * h, *q.shape[2:]),
                               k.reshape(b * h, *k.shape[2:]),
                               v.reshape(b * h, *v.shape[2:]),
                               causal, sm_scale)
        return out.reshape(b, h, *out.shape[1:])
    return _dense_attention(q, k, v, causal, sm_scale)


@register("_contrib_interleaved_matmul_selfatt_qk")
def interleaved_matmul_selfatt_qk(queries_keys_values, heads=1):
    """(L, B, 3*H*D) interleaved qkv -> scaled q k^T scores (B*H, L, L).

    Reference semantics: scores scaled by 1/sqrt(D) (transformer.cc ~L40).
    """
    L, B, P = queries_keys_values.shape
    D = P // (3 * heads)
    x = queries_keys_values.reshape(L, B, heads, 3, D)
    q = x[:, :, :, 0, :].transpose(1, 2, 0, 3).reshape(B * heads, L, D)
    k = x[:, :, :, 1, :].transpose(1, 2, 0, 3).reshape(B * heads, L, D)
    return jnp.einsum("nqd,nkd->nqk", q, k) / math.sqrt(D)


@register("_contrib_interleaved_matmul_selfatt_valatt")
def interleaved_matmul_selfatt_valatt(queries_keys_values, attention, heads=1):
    """attention (B*H, L, L) @ v from interleaved qkv -> (L, B, H*D)."""
    L, B, P = queries_keys_values.shape
    D = P // (3 * heads)
    x = queries_keys_values.reshape(L, B, heads, 3, D)
    v = x[:, :, :, 2, :].transpose(1, 2, 0, 3).reshape(B * heads, L, D)
    out = jnp.einsum("nqk,nkd->nqd", attention, v)
    return out.reshape(B, heads, L, D).transpose(2, 0, 1, 3).reshape(
        L, B, heads * D)


# ---------------------------------------------------------------------------
# adaptive pooling / deformable convolution / CTC (r2 compat tail)
# ---------------------------------------------------------------------------
@register("_contrib_AdaptiveAvgPooling2D")
def adaptive_avg_pooling(data, output_size=1):
    """Adaptive average pooling to a fixed output grid (reference:
    src/operator/contrib/adaptive_avg_pooling.cc).

    Output cell (i, j) averages input window [floor(i*H/H0), ceil((i+1)*H/H0))
    — computed via a 2-D integral image so uneven windows stay one fused
    gather, not a python loop per cell.
    """
    import numpy as np

    if isinstance(output_size, int):
        oh = ow = int(output_size)
    else:
        oh, ow = (int(output_size[0]), int(output_size[-1]))
    n, c, h, w = data.shape
    x32 = data.astype(jnp.float32)
    # integral image with a leading zero row/col
    integ = jnp.pad(jnp.cumsum(jnp.cumsum(x32, axis=2), axis=3),
                    ((0, 0), (0, 0), (1, 0), (1, 0)))
    hs = np.floor(np.arange(oh) * h / oh).astype(np.int32)
    he = np.ceil((np.arange(oh) + 1) * h / oh).astype(np.int32)
    ws = np.floor(np.arange(ow) * w / ow).astype(np.int32)
    we = np.ceil((np.arange(ow) + 1) * w / ow).astype(np.int32)
    area = ((he - hs)[:, None] * (we - ws)[None, :]).astype(np.float32)
    s = (integ[:, :, he][:, :, :, we] - integ[:, :, hs][:, :, :, we]
         - integ[:, :, he][:, :, :, ws] + integ[:, :, hs][:, :, :, ws])
    return (s / area).astype(data.dtype)


@register("histogram")
def histogram(data, *bin_arr, bin_cnt=None, range=None, bins=10):
    """np.histogram semantics (reference: src/operator/tensor/histogram.cc).

    Either bin_cnt+range (uniform bins) or an explicit bin-edge array.
    Returns (counts, bin_edges)."""
    x = data.reshape(-1).astype(jnp.float32)
    if bin_arr:
        edges = bin_arr[0].astype(jnp.float32)
        nbins = edges.shape[0] - 1
        idx = jnp.searchsorted(edges, x, side="right") - 1
        # right-most edge is inclusive (numpy semantics)
        idx = jnp.where(x == edges[-1], nbins - 1, idx)
        valid = (idx >= 0) & (idx < nbins)
        counts = jnp.zeros((nbins,), jnp.int32).at[
            jnp.where(valid, idx, 0)].add(valid.astype(jnp.int32))
        return counts, edges
    cnt = int(bin_cnt if bin_cnt is not None else bins)
    if range is None:
        lo, hi = jnp.min(x), jnp.max(x)
    else:
        lo, hi = jnp.asarray(range[0], jnp.float32), jnp.asarray(
            range[1], jnp.float32)
    span = jnp.where(hi > lo, hi - lo, 1.0)
    idx = jnp.floor((x - lo) / span * cnt).astype(jnp.int32)
    idx = jnp.clip(idx, 0, cnt - 1)
    valid = (x >= lo) & (x <= hi)
    counts = jnp.zeros((cnt,), jnp.int32).at[
        jnp.where(valid, idx, 0)].add(valid.astype(jnp.int32))
    edges = lo + (hi - lo) * jnp.arange(cnt + 1, dtype=jnp.float32) / cnt
    return counts, edges


def _bilinear_gather(img, y, x):
    """img (C, H, W); y/x arbitrary equal shapes of float coords.
    Zero padding outside (reference deformable conv im2col behavior)."""
    c, h, w = img.shape
    y0 = jnp.floor(y)
    x0 = jnp.floor(x)
    wy1, wx1 = y - y0, x - x0
    wy0, wx0 = 1.0 - wy1, 1.0 - wx1

    def at(yy, xx):
        inside = (yy >= 0) & (yy < h) & (xx >= 0) & (xx < w)
        yc = jnp.clip(yy.astype(jnp.int32), 0, h - 1)
        xc = jnp.clip(xx.astype(jnp.int32), 0, w - 1)
        v = img[:, yc, xc]
        return jnp.where(inside, v, 0.0)

    return (at(y0, x0) * (wy0 * wx0) + at(y0, x0 + 1) * (wy0 * wx1)
            + at(y0 + 1, x0) * (wy1 * wx0) + at(y0 + 1, x0 + 1) * (wy1 * wx1))


@register("_contrib_DeformableConvolution")
def deformable_convolution(data, offset, weight, *bias, kernel=(3, 3),
                           stride=(1, 1), dilate=(1, 1), pad=(0, 0),
                           num_filter=1, num_group=1, num_deformable_group=1,
                           no_bias=False, workspace=1024, layout=None):
    """Deformable convolution v1 (reference: src/operator/contrib/
    deformable_convolution.cc — Dai et al. 2017).

    offset: (N, 2*dg*kh*kw, H0, W0), ordered (y, x) per kernel tap.
    Implementation: bilinear-sample a deformed im2col volume, then one
    einsum onto the MXU — the gather is the only non-matmul work.
    """
    kh, kw = kernel
    sh, sw = stride if stride else (1, 1)
    dh, dw = dilate if dilate else (1, 1)
    ph, pw = pad if pad else (0, 0)
    n, cin, h, w = data.shape
    h0 = (h + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    w0 = (w + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    dg = num_deformable_group
    x32 = data.astype(jnp.float32)
    off = offset.astype(jnp.float32).reshape(n, dg, kh * kw, 2, h0, w0)

    base_y = (jnp.arange(h0) * sh - ph)[:, None]  # (h0, 1)
    base_x = (jnp.arange(w0) * sw - pw)[None, :]  # (1, w0)
    ky = (jnp.arange(kh) * dh)[:, None].repeat(kw, 1).reshape(-1)  # (kh*kw,)
    kx = (jnp.arange(kw) * dw)[None, :].repeat(kh, 0).reshape(-1)

    # sample positions: (dg, kh*kw, h0, w0)
    y_pos = base_y[None, None] + ky[None, :, None, None] + off[:, :, :, 0]
    x_pos = base_x[None, None] + kx[None, :, None, None] + off[:, :, :, 1]

    cpg = cin // dg  # channels per deformable group

    def sample_one(img, yp, xp):
        # img (cin, h, w); yp/xp (dg, K, h0, w0) -> (cin, K, h0, w0)
        outs = []
        for g in range(dg):
            outs.append(_bilinear_gather(img[g * cpg:(g + 1) * cpg],
                                         yp[g], xp[g]))
        return jnp.concatenate(outs, axis=0)

    cols = jax.vmap(sample_one)(x32, y_pos, x_pos)  # (n, cin, K, h0, w0)
    wmat = weight.astype(jnp.float32).reshape(num_filter, cin // num_group,
                                              kh * kw)
    if num_group == 1:
        out = jnp.einsum("nckhw,fck->nfhw", cols, wmat)
    else:
        cg = cin // num_group
        fg = num_filter // num_group
        cols_g = cols.reshape(n, num_group, cg, kh * kw, h0, w0)
        wmat_g = wmat.reshape(num_group, fg, cg, kh * kw)
        out = jnp.einsum("ngckhw,gfck->ngfhw", cols_g, wmat_g).reshape(
            n, num_filter, h0, w0)
    out = out.astype(data.dtype)
    if not no_bias and bias:
        out = out + bias[0].reshape(1, -1, 1, 1)
    return out
