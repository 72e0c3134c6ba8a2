"""The learned sparse attention's own kernels (DeepSeek's lightning indexer
and the loss that trains it), in Pallas (TPU).

With the indexer's queries ``qi`` (J heads of Dk), its one key head ``ki``
and the heads' weights ``w`` (already scaled by ``1 / sqrt(J Dk)``), a score
``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])`` for ``s <= t``.  Every
kernel here holds a tile TRANSPOSED, keys as rows and queries as lanes
(``(bk, bq)``), so that a query's statistics are rows of lanes and a head's
weight broadcasts down the sublanes:

* ``scores``: ``I^T`` of one block of queries over every key (``_NEG``
  above the diagonal), for ``topk``;
* ``topk``: each query's ``k``-th largest score, by a bisection on the
  float's bits over a VMEM-resident column block (no sort);
* ``kl_fwd`` / ``kl_bwd``: ``KL(p_t || softmax_{S_t} I_t)`` a query, with
  ``p`` the attention heads' mean probability over the selection ``S_t``
  (made again from q, k and the attention's row logsumexp, tile by tile, in
  ``kl_fwd``, which returns it for ``kl_bwd`` to read), and its gradient in
  ``qi``, ``ki`` and ``w``.

The selection arrives as the transposed packed words of
``flash_attention.py`` (``bits_t``, unpacked by ``_unpack``).  Lengths are
padded to the tile (``select_block``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _NEG, _unpack

_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)
#: key rows a step of the bisection's count reads
_SLAB = 512
#: query columns of one bisection block
_COLS = 128


def _index_tile(ki, qi_ref, w_ref, j):
    """One head's ``(bk, bq)`` pre-activation and its weight row."""
    s = jax.lax.dot_general(ki, qi_ref[0, j], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return s, w_ref[0, pl.ds(j, 1), :]


def _index_scores(ki, qi_ref, w_ref, heads):
    acc = None
    for j in range(heads):
        s, w = _index_tile(ki, qi_ref, w_ref, j)
        term = w * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return acc


# ---------------------------------------------------------------------------
# scores of one block of queries
# ---------------------------------------------------------------------------
def _scores_kernel(heads, length, q0_ref, qi_ref, ki_ref, w_ref, out_ref):
    kj = pl.program_id(1)
    bk, bq = out_ref.shape[1], out_ref.shape[2]
    q0 = q0_ref[0]

    @pl.when(kj * bk <= q0 + bq - 1)
    def _tile():
        acc = _index_scores(ki_ref[0], qi_ref, w_ref, heads)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
        keep = jnp.logical_and(kpos <= qpos, kpos < length)
        # -0.0 and 0.0 one value: the bisection compares bit patterns
        out_ref[0] = jnp.where(keep, acc + 0.0, _NEG)

    @pl.when(kj * bk > q0 + bq - 1)
    def _above():
        out_ref[0] = jnp.full((bk, bq), _NEG, jnp.float32)


def scores(q0, qi, ki, w, length, interpret=False):
    """``I^T`` (B, Lp, bq) f32 of the query block starting at ``q0`` (a
    traced int32): ``qi`` (B, J, bq, Dk) of the block, ``ki`` (B, Lp, Dk),
    ``w`` (B, J, bq) f32 of the block; ``_NEG`` where ``s > t`` or
    ``s >= length``."""
    b, heads, bq, dk = qi.shape
    lp = ki.shape[1]
    bk = bq
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, lp // bk),
        in_specs=[pl.BlockSpec((1, heads, bq, dk), lambda i, j, q0: (i, 0, 0, 0)),
                  pl.BlockSpec((1, bk, dk), lambda i, j, q0: (i, j, 0)),
                  pl.BlockSpec((1, heads, bq), lambda i, j, q0: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, bk, bq), lambda i, j, q0: (i, j, 0)))
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads, length),
        name="mx_dsa_scores", grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((b, lp, bq), jnp.float32),
        compiler_params=_PARAMS, interpret=interpret,
    )(jnp.reshape(q0, (1,)).astype(jnp.int32), qi, ki, w)


# ---------------------------------------------------------------------------
# the k-th largest score of each query, by bisection on the bits
# ---------------------------------------------------------------------------
_SIGN = -2 ** 31


def sortable(x):
    """int32 keys of f32 ``x`` in the order of the floats (-0.0 below 0.0:
    the scores add 0.0 first)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def _topk_kernel(k, q0_ref, s_ref, out_ref):
    c = pl.program_id(1)
    lp, cols = s_ref.shape[1], s_ref.shape[2]
    slab = min(_SLAB, lp)
    # key rows up to the block's last query: the rest are all _NEG
    slabs = jnp.minimum(
        (q0_ref[0] + (c + 1) * cols - 1) // slab + 1, lp // slab)

    def count(cand):
        def body(r, acc):
            key = sortable(s_ref[0, pl.ds(r * slab, slab), :])
            return acc + jnp.sum(jnp.where(key >= cand, 1, 0), axis=0,
                                 keepdims=True)
        # the rows past ``slabs`` hold _NEG: counted whole, not read
        rest = (lp // slab - slabs) * slab
        below = jnp.where(sortable(jnp.full((1, cols), _NEG)) >= cand, rest,
                          0)
        return jax.lax.fori_loop(0, slabs, body, below)

    def bit(i, prefix):
        cand = prefix | jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(count(cand ^ _SIGN) >= k, cand, prefix)

    prefix = jax.lax.fori_loop(0, 32, bit, jnp.zeros((1, cols), jnp.int32))
    key = prefix ^ _SIGN
    bits = jnp.where(key < 0, key ^ 0x7FFFFFFF, key)
    out_ref[0] = jax.lax.bitcast_convert_type(bits, jnp.float32)


def topk(q0, s, k, interpret=False):
    """(B, 1, bq) f32: each query's ``k``-th largest of ``s`` (B, Lp, bq),
    the scores of the query block starting at ``q0``."""
    b, lp, bq = s.shape
    cols = min(_COLS, bq)
    grid = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(b, bq // cols),
        in_specs=[pl.BlockSpec((1, lp, cols), lambda i, c, q0: (i, 0, c))],
        out_specs=pl.BlockSpec((1, 1, cols), lambda i, c, q0: (i, 0, c)))
    return pl.pallas_call(
        functools.partial(_topk_kernel, int(k)),
        name="mx_dsa_topk", grid_spec=grid,
        out_shape=jax.ShapeDtypeStruct((b, 1, bq), jnp.float32),
        compiler_params=_PARAMS, interpret=interpret,
    )(jnp.reshape(q0, (1,)).astype(jnp.int32), s)


# ---------------------------------------------------------------------------
# the indexer's loss against the heads' mean probability over the selection
# ---------------------------------------------------------------------------
def _mean_probs(k_ref, q_ref, lse_ref, heads, group, scale, mask):
    """The heads' mean probability of the (bk, bq) tile, 0 off the
    selection."""
    acc = None
    for h in range(heads):
        s = jax.lax.dot_general(k_ref[0, h // group], q_ref[0, h],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp(jnp.where(mask, s * scale, _NEG)
                    - lse_ref[0, pl.ds(h, 1), :])
        acc = p if acc is None else acc + p
    return jnp.where(mask, acc * (1.0 / heads), 0.0)


def _kl_fwd_kernel(heads, group, jheads, scale, q_ref, k_ref, lse_ref,
                   qi_ref, ki_ref, w_ref, bits_ref, st_ref, p_ref):
    qi, kj = pl.program_id(1), pl.program_id(2)
    bk, bq = ki_ref.shape[1], q_ref.shape[2]

    @pl.when(kj == 0)
    def _init():
        st_ref[0] = jnp.zeros(st_ref.shape[1:], jnp.float32)
        st_ref[0, 0:1, :] = jnp.full((1, bq), _NEG, jnp.float32)

    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _tile():
        mask = _unpack(bits_ref[0], bk)
        p = _mean_probs(k_ref, q_ref, lse_ref, heads, group, scale, mask)
        p_ref[0, 0] = p
        score = _index_scores(ki_ref[0], qi_ref, w_ref, jheads)
        m_old = st_ref[0, 0:1, :]
        m_new = jnp.maximum(m_old, jnp.max(jnp.where(mask, score, _NEG),
                                           axis=0, keepdims=True))
        e = jnp.where(mask, jnp.exp(score - m_new), 0.0)
        st_ref[0, 0:1, :] = m_new
        st_ref[0, 1:2, :] = st_ref[0, 1:2, :] * jnp.exp(m_old - m_new) \
            + jnp.sum(e, axis=0, keepdims=True)
        logp = jnp.log(jnp.where(p > 0.0, p, 1.0))
        st_ref[0, 2:3, :] += jnp.sum(p * logp, axis=0, keepdims=True)
        st_ref[0, 3:4, :] += jnp.sum(p * score, axis=0, keepdims=True)
        st_ref[0, 4:5, :] += jnp.sum(p, axis=0, keepdims=True)


def _kv(q, j):
    """The key block of grid step (q, j): past the diagonal, the
    diagonal's (the same block again is no copy; the step is skipped)."""
    return jnp.minimum(j, q)


def _kl_specs(jheads, dk, blk):
    """In-specs of qi, ki, w and the words over the grid (batch row, query
    block, key block)."""
    return [
        pl.BlockSpec((1, jheads, blk, dk), lambda i, q, j: (i, 0, q, 0)),
        pl.BlockSpec((1, blk, dk), lambda i, q, j: (i, _kv(q, j), 0)),
        pl.BlockSpec((1, jheads, blk), lambda i, q, j: (i, 0, q)),
        pl.BlockSpec((1, blk // 32, blk), lambda i, q, j: (i, _kv(q, j), q)),
    ]


def _target_spec(blk):
    """The target's tile of grid step (q, j): causal tiles packed by query
    block, tile ``q (q + 1) / 2 + j`` for ``j <= q``."""
    def tile(i, q, j):
        return i, q * (q + 1) // 2 + _kv(q, j), 0, 0

    return pl.BlockSpec((1, 1, blk, blk), tile)


def kl_fwd(q, k, lse, qi, ki, w, bits_t, interpret=False):
    """(st, p).  ``st`` (B, 8, Lp) f32 statistics a query, rows 0 to 4: the
    running max and sum of ``exp(I)`` over the selection, ``sum p log p``,
    ``sum p I`` and ``sum p``.  ``p`` (B, T, blk, blk) f32, the target: the
    heads' mean probability of each of the T = n (n + 1) / 2 causal tiles
    (``_target_spec``), transposed (keys as rows), 0 off the selection.
    q (B, H, Lp, D), k (B, Hkv, Lp, D), lse (B, H, Lp) f32 of the
    attention; qi (B, J, Lp, Dk), ki (B, Lp, Dk), w (B, J, Lp) f32 of the
    indexer; ``bits_t`` (B, Lp/32, Lp)."""
    b, heads, lp, d = q.shape
    hkv, jheads, dk = k.shape[1], qi.shape[1], qi.shape[-1]
    blk = min(512, lp)
    n = lp // blk
    return pl.pallas_call(
        functools.partial(_kl_fwd_kernel, heads, heads // hkv, jheads,
                          d ** -0.5),
        name="mx_dsa_kl_fwd", grid=(b, n, n),
        in_specs=[
            pl.BlockSpec((1, heads, blk, d), lambda i, q, j: (i, 0, q, 0)),
            pl.BlockSpec((1, hkv, blk, d),
                         lambda i, q, j: (i, 0, _kv(q, j), 0)),
            pl.BlockSpec((1, heads, blk), lambda i, q, j: (i, 0, q)),
        ] + _kl_specs(jheads, dk, blk),
        out_specs=[pl.BlockSpec((1, 8, blk), lambda i, q, j: (i, 0, q)),
                   _target_spec(blk)],
        out_shape=[jax.ShapeDtypeStruct((b, 8, lp), jnp.float32),
                   jax.ShapeDtypeStruct((b, n * (n + 1) // 2, blk, blk),
                                        jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(q, k, lse, qi, ki, w, bits_t)


def _kl_bwd_kernel(jheads, p_ref, qi_ref, ki_ref, w_ref, bits_ref, kit_ref,
                   row_ref, dqi_ref, dw_ref, dki_ref):
    qi, kj = pl.program_id(1), pl.program_id(2)
    bk, bq = ki_ref.shape[1], w_ref.shape[2]

    @pl.when(kj == 0)
    def _init_rows():
        dqi_ref[0] = jnp.zeros(dqi_ref.shape[1:], jnp.float32)
        dw_ref[0] = jnp.zeros(dw_ref.shape[1:], jnp.float32)

    @pl.when(jnp.logical_and(qi == 0, kj == 0))
    def _init_keys():
        dki_ref[0] = jnp.zeros(dki_ref.shape[1:], jnp.float32)

    @pl.when(kj * bk <= qi * bq + bq - 1)
    def _tile():
        mask = _unpack(bits_ref[0], bk)
        ki = ki_ref[0]
        score = _index_scores(ki, qi_ref, w_ref, jheads)
        lse_i, total, g = (row_ref[0, r:r + 1, :] for r in range(3))
        soft = jnp.where(mask, jnp.exp(score - lse_i), 0.0)
        ds = g * (total * soft - p_ref[0, 0])
        kit = kit_ref[0]
        dk = None
        for j in range(jheads):
            s, w = _index_tile(ki, qi_ref, w_ref, j)
            dw_ref[0, pl.ds(j, 1), :] += jnp.sum(ds * jnp.maximum(s, 0.0),
                                                 axis=0, keepdims=True)
            grad = jnp.where(s > 0.0, ds * w, 0.0).astype(ki.dtype)
            dqi_ref[0, j] += jax.lax.dot(kit, grad,
                                         preferred_element_type=jnp.float32)
            term = jax.lax.dot(grad, qi_ref[0, j],
                               preferred_element_type=jnp.float32)
            dk = term if dk is None else dk + term
        dki_ref[0, pl.ds(kj * bk, bk), :] += dk


def kl_bwd(p, qi, ki, w, bits_t, rows, interpret=False):
    """Gradients of ``sum_t g_t KL_t`` in qi, w and ki: (dqi^T (B, J, Dk,
    Lp), dw (B, J, Lp), dki (B, Lp, Dk)), all f32.  ``p`` the target as
    ``kl_fwd`` returns it; ``rows`` (B, 8, Lp): row 0 the selection's
    logsumexp of I, row 1 ``sum p``, row 2 ``g``."""
    b, jheads, lp, dk = qi.shape
    blk = min(512, lp)
    n = lp // blk
    specs = [_target_spec(blk)] + _kl_specs(jheads, dk, blk) + [
        pl.BlockSpec((1, dk, blk), lambda i, q, j: (i, 0, _kv(q, j))),
        pl.BlockSpec((1, 8, blk), lambda i, q, j: (i, 0, q))]
    return pl.pallas_call(
        functools.partial(_kl_bwd_kernel, jheads),
        name="mx_dsa_kl_bwd", grid=(b, n, n), in_specs=specs,
        out_specs=[
            pl.BlockSpec((1, jheads, dk, blk), lambda i, q, j: (i, 0, 0, q)),
            pl.BlockSpec((1, jheads, blk), lambda i, q, j: (i, 0, q)),
            pl.BlockSpec((1, lp, dk), lambda i, q, j: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, jheads, dk, lp), jnp.float32),
                   jax.ShapeDtypeStruct((b, jheads, lp), jnp.float32),
                   jax.ShapeDtypeStruct((b, lp, dk), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret,
    )(p, qi, ki, w, bits_t, jnp.swapaxes(ki, 1, 2), rows)
