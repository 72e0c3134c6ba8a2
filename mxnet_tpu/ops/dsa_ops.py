"""Learned sparse attention (DeepSeek-V3.2's lightning indexer, as Keye-VL-2.0
runs it): the indexer's scores, each query's top-k selection as packed bits,
attention under the selection, and the KL loss that trains the indexer.

With the indexer's rotated queries ``qi`` (B, L, J, Dk), its one rotated key
head ``ki`` (B, L, Dk) and the heads' weights ``w`` (B, L, J), scaled by
``1 / sqrt(J Dk)`` before they come here:

* ``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])`` for ``s <= t``;
* ``S_t = {s <= t : I[t, s] >= theta_t}``, ``theta_t`` the ``topk``-th
  largest of row t (every ``s <= t`` while ``t < topk``; ties are in);
* attention of every head over ``S_t`` (one selection for all heads);
* ``KL(p_t || softmax_{S_t} I_t)`` a query, ``p_t`` the heads' mean
  attention probability over ``S_t`` (a target: no gradient reaches it).

On a TPU the work runs in Pallas kernels (``pallas/dsa.py``, the flash
kernels' selection mode) a block of 512 queries at a time; elsewhere as the
dense compositions below, which define the results.  The selection travels
as packed int32 words (``pack``; ``flash_attention.py`` says the layout),
``bits`` by query rows and ``bits_t`` by key rows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import recompute
from .pallas.flash_attention import (_NEG, SELECT_GROUP, check_selection,
                                     select_block, sparse_flash_attention)

__all__ = ["index_scores", "thresholds", "pack", "unpack", "select",
           "sparse_attention", "indexer_kl", "tile_hit"]


def _compiled():
    from . import pallas as _pk

    return _pk.enabled() and _pk.use_compiled()


def _interpret():
    from . import pallas as _pk

    return _pk.interpret()


def padded(length):
    """(tile, length padded to it) of a call under a selection."""
    blk = select_block(length)
    return blk, -(-length // blk) * blk


def index_scores(qi, ki, w):
    """I (B, L, L) f32, ``_NEG`` above the diagonal (the dense form)."""
    s = jnp.einsum("btjd,bsd->btjs", qi, ki,
                   preferred_element_type=jnp.float32)
    score = jnp.einsum("btj,btjs->bts", w.astype(jnp.float32),
                       jnp.maximum(s, 0.0))
    n = qi.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    return jnp.where(causal, score, _NEG)


def thresholds(score, topk):
    """theta (B, L): the ``topk``-th largest of each row (``_NEG`` where a
    row has fewer causal keys), by ``lax.top_k``."""
    k = min(int(topk), score.shape[-1])
    return jax.lax.top_k(score, k)[0][..., -1]


def pack(sel, rows):
    """int32 words (B, rows / 32, C) of a bool (B, R, C) selection padded
    with False to ``rows`` (a multiple of 256): row ``256 a + 8 j + s`` is
    bit ``j`` of word row ``8 a + s``."""
    b, r, c = sel.shape
    sel = jnp.pad(sel, ((0, 0), (0, rows - r), (0, 0)))
    bits = sel.reshape(b, rows // SELECT_GROUP, 32, 8, c).astype(jnp.uint32)
    shifted = jnp.left_shift(bits, jnp.arange(32, dtype=jnp.uint32)[
        None, None, :, None, None])
    words = jnp.sum(shifted, axis=2, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words.reshape(b, rows // 32, c),
                                        jnp.int32)


def unpack(words):
    """The bool (B, R, C) selection of words (B, R / 32, C)."""
    b, g, c = words.shape
    w = jax.lax.bitcast_convert_type(words, jnp.uint32).reshape(
        b, g // 8, 1, 8, c)
    bit = jnp.right_shift(w, jnp.arange(32, dtype=jnp.uint32)[
        None, None, :, None, None]) & 1
    return bit.reshape(b, g * 32, c) != 0


def tile_hit(bits, length):
    """Share of the causal (query block, key block) tiles the flash kernels
    visit under a selection whose words (``bits``) hold a selected pair."""
    blk, lp = padded(length)
    b, n = bits.shape[0], lp // blk
    tiles = bits.reshape(b, n, blk // 32, n, blk)
    hit = jnp.any(tiles != 0, axis=(2, 4))
    causal = jnp.tril(jnp.ones((n, n), bool))
    return jnp.sum(jnp.where(causal, hit, False)) / (b * n * (n + 1) / 2)


def _selection_dense(qi, ki, w, topk):
    score = index_scores(qi, ki, w)
    theta = thresholds(score, topk)
    n = qi.shape[1]
    causal = jnp.tril(jnp.ones((n, n), bool))
    return jnp.logical_and(score >= theta[..., None], causal)


def _pad_to(x, axis, lp):
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, lp - x.shape[axis])
    return jnp.pad(x, widths)


def _index_operands(qi, ki, w, lp):
    """The indexer's operands of its kernels, padded to ``lp``: qi (B, J,
    Lp, Dk), ki (B, Lp, Dk), w (B, J, Lp) f32."""
    return (jnp.swapaxes(_pad_to(qi, 1, lp), 1, 2), _pad_to(ki, 1, lp),
            jnp.swapaxes(_pad_to(w.astype(jnp.float32), 1, lp), 1, 2))


def _selection_blocks(qi, ki, w, topk, length):
    """(bits, bits_t) by the kernels, a block of queries at a time."""
    from .pallas import dsa

    blk, lp = padded(length)
    b = qi.shape[0]
    qt, kp, wt = _index_operands(qi, ki, w, lp)
    interpret = _interpret()

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(qt, q0, blk, axis=2)
        wb = jax.lax.dynamic_slice_in_dim(wt, q0, blk, axis=2)
        st = dsa.scores(q0, qb, kp, wb, length, interpret)   # (B, Lp, blk)
        theta = dsa.topk(q0, st, topk, interpret)            # (B, 1, blk)
        kpos = jnp.arange(lp)[:, None]
        qpos = q0 + jnp.arange(blk)[None, :]
        sel_t = (st >= theta) & (kpos <= qpos) & (qpos < length)
        return pack(jnp.swapaxes(sel_t, 1, 2), blk), pack(sel_t, lp)

    rows, cols = jax.lax.map(block, jnp.arange(0, lp, blk, dtype=jnp.int32))
    bits = jnp.moveaxis(rows, 0, 1).reshape(b, lp // 32, lp)
    bits_t = jnp.moveaxis(cols, 0, 2).reshape(b, lp // 32, lp)
    return bits, bits_t


def select(qi, ki, w, topk):
    """(bits, bits_t) of each batch row's selection: int32 words (B, Lp/32,
    Lp), Lp the length padded to the tile.  Not differentiable.  Both words
    are named as a kernel's results: a recomputed layer keeps them from its
    forward pass, since the attention's and the KL's backward read them."""
    qi, ki, w = (jax.lax.stop_gradient(x) for x in (qi, ki, w))
    length = qi.shape[1]
    with jax.named_scope("mx_dsa_select"):
        if _compiled():
            words = _selection_blocks(qi, ki, w, topk, length)
        else:
            sel = _selection_dense(qi, ki, w, topk)
            _blk, lp = padded(length)
            sel = jnp.pad(sel, ((0, 0), (0, lp - length), (0, lp - length)))
            words = pack(sel, lp), pack(jnp.swapaxes(sel, 1, 2), lp)
        return recompute.kernel_out(*words)


def _selected(bits, length):
    return unpack(bits)[:, :length, :length]


def sparse_attention(q, k, v, bits, bits_t, sm_scale=None):
    """(out (B, H, L, Dv), lse (B, H, L) f32) of attention under the
    selection: q (B, H, L, D), k and v (B, Hkv, L, D); differentiable in q,
    k and v."""
    if _compiled():
        return sparse_flash_attention(q, k, v, bits, bits_t, sm_scale)
    check_selection(q.shape, k.shape, bits, bits_t)
    b, h, n, d = q.shape
    rep = h // k.shape[1]
    kr, vr = (jnp.repeat(x, rep, axis=1) for x in (k, v))
    scale = d ** -0.5 if sm_scale is None else sm_scale
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   kr.astype(jnp.float32)) * scale
    s = jnp.where(_selected(bits, n)[:, None], s, _NEG)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhts,bhsd->bhtd", p, vr.astype(jnp.float32))
    return out.astype(q.dtype), jax.lax.stop_gradient(lse)


def mean_probs(q, k, lse, sel):
    """The heads' mean attention probability (B, L, L) over the selection,
    from q (B, H, L, D), k (B, Hkv, L, D) and the rows' logsumexp."""
    rep = q.shape[1] // k.shape[1]
    s = jnp.einsum("bhtd,bhsd->bhts", q.astype(jnp.float32),
                   jnp.repeat(k, rep, axis=1).astype(jnp.float32)) \
        * q.shape[-1] ** -0.5
    p = jnp.exp(jnp.where(sel[:, None], s, _NEG) - lse[..., None])
    return jnp.where(sel, jnp.mean(p, axis=1), 0.0)


def _kl_dense(qi, ki, w, q, k, lse, bits_t):
    n = qi.shape[1]
    sel = jnp.swapaxes(_selected(bits_t, n), 1, 2)
    p = jax.lax.stop_gradient(mean_probs(q, k, lse, sel))
    score = index_scores(qi, ki, w)
    lse_i = jax.nn.logsumexp(jnp.where(sel, score, -jnp.inf), axis=-1)
    logp = jnp.log(jnp.where(p > 0, p, 1.0))
    return jnp.sum(jnp.where(sel, p * (logp - score + lse_i[..., None]),
                             0.0), axis=-1)


@jax.custom_vjp
def _kl_kernels(qi, ki, w, q, k, lse, bits_t):
    return _kl_kernels_fwd(qi, ki, w, q, k, lse, bits_t)[0]


def _kl_kernels_fwd(qi, ki, w, q, k, lse, bits_t):
    """The KL and the residuals of its backward: the target ``p`` that
    ``kl_fwd`` makes, named with its statistics so that a recomputed layer
    keeps both and its backward makes the target no second time."""
    from .pallas import dsa

    length = qi.shape[1]
    _blk, lp = padded(length)
    target = (_pad_to(x, 2, lp) for x in (q, k, lse.astype(jnp.float32)))
    st, p = recompute.kernel_out(*dsa.kl_fwd(
        *target, *_index_operands(qi, ki, w, lp), bits_t, _interpret()))
    lse_i = st[:, 0] + jnp.log(st[:, 1])
    kl = st[:, 2] - st[:, 3] + st[:, 4] * lse_i
    return kl[:, :length], (qi, ki, w, bits_t, p, lse_i, st[:, 4])


def _kl_kernels_bwd(res, g):
    from .pallas import dsa

    qi, ki, w, bits_t, p, lse_i, total = res
    b, length = g.shape
    lp = bits_t.shape[-1]
    rows = jnp.zeros((b, 8, lp), jnp.float32)
    rows = rows.at[:, 0].set(lse_i).at[:, 1].set(total).at[:, 2, :length].set(
        g.astype(jnp.float32))
    dqt, dwt, dk = dsa.kl_bwd(p, *_index_operands(qi, ki, w, lp), bits_t,
                              rows, _interpret())
    dqi = jnp.moveaxis(dqt, 3, 1)[:, :length]         # (B, L, J, Dk)
    dw = jnp.swapaxes(dwt, 1, 2)[:, :length]
    # q, k and lse make the target: no gradient reaches them
    return (dqi.astype(qi.dtype), dk[:, :length].astype(ki.dtype),
            dw.astype(w.dtype), None, None, None,
            np.zeros(bits_t.shape, jax.dtypes.float0))


_kl_kernels.defvjp(_kl_kernels_fwd, _kl_kernels_bwd)


def indexer_kl(qi, ki, w, q, k, lse, bits_t):
    """``KL(p_t || softmax_{S_t} I_t)`` (B, L) f32 a query; differentiable
    in qi, ki and w only (q, k and the attention's row logsumexp ``lse``
    (B, H, L) make the target)."""
    q, k, lse = (jax.lax.stop_gradient(x) for x in (q, k, lse))
    with jax.named_scope("mx_dsa_kl"):
        if _compiled():
            return _kl_kernels(qi, ki, w, q, k, lse, bits_t)
        return _kl_dense(qi, ki, w, q, k, lse, bits_t)


def indexer_scale(heads, dim):
    """The heads' weights' factor, ``1 / (sqrt(J) sqrt(Dk))``."""
    return 1.0 / (math.sqrt(heads) * math.sqrt(dim))
