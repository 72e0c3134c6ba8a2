"""FlashAttention-2 in Pallas (TPU).

Blockwise online-softmax attention: never materialises the (Lq, Lk) score
matrix in HBM.  Forward keeps a running (max, sum, acc) per q row; backward
is the standard two-kernel FA2 scheme (dq sweep over k blocks; dk/dv sweep
over q blocks) using the saved logsumexp.

Reference parity: supersedes src/operator/contrib/transformer.cc
(interleaved_matmul_selfatt_qk/valatt ~L1-300), which fused only the
attention matmuls and still materialised scores for a separate softmax op.

Shapes: q (N, Lq, D), k (N, Lk, D), v (N, Lk, Dv) with N = batch*heads; 4D
(B, H, L, D) inputs are reshaped.  The values may be narrower or wider than
the channels the scores are taken over (latent attention scores over a
head's plain and rotary channels and sums values of the plain width): the
output, the forward's accumulator and dV take Dv, everything else D.  The MXU takes its operands in the input
dtype and accumulates in f32; softmax statistics are f32.

Grouped-query heads: k/v may carry fewer heads than q, (B, Hkv, Lk, D) with
H a multiple of Hkv.  Query head h reads key-value head h // (H / Hkv) BY
INDEX (the block index maps divide), forward and backward; the repeat of K
and V is never materialised, and dK/dV of a key-value head accumulate over
its query heads inside the kernel.  Under ``causal`` the loops stop at the
diagonal: blocks that the mask would empty are not computed.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG = -1e30
_LANES = 128  # TPU lane width: per-row stats (lse/delta) carry a trailing
              # 128-lane dim so their blocks satisfy Mosaic tiling rules
              # (same trick as jax's in-tree flash kernel, MIN_BLOCK_SIZE)


class _Cfg(NamedTuple):
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    q_len: int     # unpadded
    kv_len: int    # unpadded
    interpret: bool
    group: int = 1  # query heads per key-value head


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(length: int, preferred: int) -> int:
    if length >= preferred:
        return preferred
    return _round_up(length, 8)


def _kv_mask(cfg: _Cfg, qi, kj, bq, bk):
    """Validity mask for a (bq, bk) score tile at q block qi / k block kj."""
    kpos = kj * cfg.block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < cfg.kv_len
    if cfg.causal:
        qpos = qi * cfg.block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        mask = jnp.logical_and(mask, qpos >= kpos)
    return mask


def _k_blocks(cfg: _Cfg, qi, nkb):
    """(whole, reached): the leading key blocks every row of query block qi
    sees whole (no mask needed), and the blocks it reaches at all (under
    ``causal`` the loops stop at the diagonal)."""
    whole = cfg.kv_len // cfg.block_k
    if not cfg.causal:
        return whole, nkb
    reached = jnp.minimum(nkb, ((qi + 1) * cfg.block_q + cfg.block_k - 1)
                          // cfg.block_k)
    return jnp.minimum(whole, (qi * cfg.block_q + 1) // cfg.block_k), reached


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, o_ref, lse_ref):
    qi = pl.program_id(1)
    bq, bk = cfg.block_q, cfg.block_k
    q = q_ref[0]                                             # (bq, D)
    nkb = k_ref.shape[1] // bk

    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)

    def body(masked, kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kj * bk, bk), :]
        v = v_ref[0, pl.ds(kj * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * cfg.sm_scale
        if masked:
            s = jnp.where(_kv_mask(cfg, qi, kj, bq, bk), s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    whole, reached = _k_blocks(cfg, qi, nkb)
    carry = jax.lax.fori_loop(0, whole, functools.partial(body, False),
                              (m0, l0, a0))
    m, l, acc = jax.lax.fori_loop(whole, reached,
                                  functools.partial(body, True), carry)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(safe_l), (bq, _LANES))


def _fwd(cfg: _Cfg, q, k, v):
    n, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[-1]
    nqb = lq // cfg.block_q
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        name="mx_flash_fwd",
        grid=(n, nqb),
        in_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, lk, d), lambda b, i: (b // cfg.group, 0, 0)),
            pl.BlockSpec((1, lk, dv), lambda b, i: (b // cfg.group, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((n, lq, _LANES), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(q, k, v)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward: dq kernel (parallel over q blocks), dkv kernel (over k blocks)
# ---------------------------------------------------------------------------
def _dq_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref):
    qi = pl.program_id(1)
    bq, bk = cfg.block_q, cfg.block_k
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0:1]
    delta = delta_ref[0, :, 0:1]
    nkb = k_ref.shape[1] // bk
    dq0 = jnp.zeros(q.shape, jnp.float32)

    def body(masked, kj, dq):
        k = k_ref[0, pl.ds(kj * bk, bk), :]
        v = v_ref[0, pl.ds(kj * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * cfg.sm_scale
        if masked:
            s = jnp.where(_kv_mask(cfg, qi, kj, bq, bk), s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    whole, reached = _k_blocks(cfg, qi, nkb)
    dq = jax.lax.fori_loop(0, whole, functools.partial(body, False), dq0)
    dq = jax.lax.fori_loop(whole, reached, functools.partial(body, True), dq)
    dq_ref[0] = (dq * cfg.sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref):
    """One key block of one key-value head against one of its query heads
    (grid axis 2, innermost): dK/dV accumulate in the f32 output block,
    which stays resident while that axis runs.  Scores are held transposed,
    (bk, bq), so that the per-query statistics are rows of lanes."""
    kj, g = pl.program_id(1), pl.program_id(2)
    bq, bk = cfg.block_q, cfg.block_k
    k = k_ref[0]
    v = v_ref[0]
    nqb = q_ref.shape[1] // bq
    # query blocks this key block reaches, and from which on every row sees
    # it whole; a key block that holds padding is masked throughout
    lo = (kj * bk) // bq if cfg.causal else 0
    whole = ((kj + 1) * bk + bq - 2) // bq if cfg.causal else 0
    whole = jnp.where((kj + 1) * bk <= cfg.kv_len, whole, nqb)
    zero = jnp.zeros(k.shape, jnp.float32)
    zero_v = zero if v.shape == k.shape else jnp.zeros(v.shape, jnp.float32)

    def body(masked, qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * bq, bq), :]
        do = do_ref[0, pl.ds(qi * bq, bq), :]
        lse = lse_ref[0, :, pl.ds(qi * bq, bq)]                # (1, bq)
        delta = delta_ref[0, :, pl.ds(qi * bq, bq)]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        st = st * cfg.sm_scale
        if masked:
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
            mask = kpos < cfg.kv_len
            if cfg.causal:
                qpos = qi * bq + jax.lax.broadcasted_iota(
                    jnp.int32, (bk, bq), 1)
                mask = jnp.logical_and(mask, qpos >= kpos)
            st = jnp.where(mask, st, _NEG)
        pt = jnp.exp(st - lse)
        dv = dv + jax.lax.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta) * cfg.sm_scale).astype(q.dtype)
        dk = dk + jax.lax.dot(dst, q, preferred_element_type=jnp.float32)
        return dk, dv

    whole = jnp.clip(whole, lo, nqb)
    carry = jax.lax.fori_loop(lo, whole, functools.partial(body, True),
                              (zero, zero_v))
    dk, dv = jax.lax.fori_loop(whole, nqb, functools.partial(body, False),
                               carry)

    @pl.when(g == 0)
    def _first():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(g > 0)
    def _more():
        dk_ref[0] += dk
        dv_ref[0] += dv


def _bwd_impl(cfg: _Cfg, q, k, v, out, lse, do):
    n, lq, d = q.shape
    nkv, lk, dv = k.shape[0], k.shape[1], v.shape[-1]
    grp = cfg.group
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # (n, lq)
    lse3 = jnp.broadcast_to(lse[..., None], (n, lq, _LANES))
    delta3 = jnp.broadcast_to(delta[..., None], (n, lq, _LANES))
    def q_blk(w):
        return pl.BlockSpec((1, cfg.block_q, w), lambda b, i: (b, i, 0))

    def kv_all(w):
        return pl.BlockSpec((1, lk, w), lambda b, i: (b // grp, 0, 0))

    stat_blk = pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        name="mx_flash_dq",
        grid=(n, lq // cfg.block_q),
        in_specs=[q_blk(d), kv_all(d), kv_all(dv), q_blk(dv), stat_blk,
                  stat_blk],
        out_specs=q_blk(d),
        out_shape=jax.ShapeDtypeStruct((n, lq, d), q.dtype),
        interpret=cfg.interpret,
    )(q, k, v, do, lse3, delta3)

    # grid (key-value head, key block, query head of the group)
    def q_all(w):
        return pl.BlockSpec((1, lq, w), lambda b, j, g: (b * grp + g, 0, 0))

    def kv_blk(w):
        return pl.BlockSpec((1, cfg.block_k, w), lambda b, j, g: (b, j, 0))

    stat_row = pl.BlockSpec((1, 1, lq), lambda b, j, g: (b * grp + g, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg),
        name="mx_flash_dkv",
        grid=(nkv, lk // cfg.block_k, grp),
        in_specs=[q_all(d), kv_blk(d), kv_blk(dv), q_all(dv), stat_row,
                  stat_row],
        out_specs=[kv_blk(d), kv_blk(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((nkv, lk, d), jnp.float32),
            jax.ShapeDtypeStruct((nkv, lk, dv), jnp.float32),
        ],
        interpret=cfg.interpret,
    )(q, k, v, do, lse[:, None, :], delta[:, None, :])
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Cfg, q, k, v):
    out, _ = _fwd(cfg, q, k, v)
    return out


def _flash_fwd(cfg: _Cfg, q, k, v):
    from ..recompute import kernel_out

    # both, or a recomputed layer runs the kernel again: the backward reads
    # lse, the layer's later ops read out
    out, lse = kernel_out(*_fwd(cfg, q, k, v))
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg: _Cfg, res, do):
    q, k, v, out, lse = res
    return _bwd_impl(cfg, q, k, v, out, lse, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    return_lse: bool = False):
    """Fused attention: softmax(q @ k^T * sm_scale [+ causal mask]) @ v.

    q: (N, Lq, D) or (B, H, Lq, D); k, v likewise with Lk (v of any width
    Dv: the output is Dv wide; `sm_scale` defaults to D^-0.5), or with fewer
    heads, (B, Hkv, Lk, D), H a multiple of Hkv (grouped-query attention: the
    module docstring).  Differentiable in q/k/v (FA2 backward).  Blocks of 512 x 512
    (shorter lengths take one block): at 8,192 positions and 128-wide heads
    on a v5e chip the forward takes 10.2 ms where 128 x 128 blocks took 50.5
    (PERF.md, PR 27: the softmax's elementwise work per tile, not the MXU,
    sets the pace).  `return_lse` additionally returns the row
    logsumexp (N, Lq) in f32 (not differentiable; used by ring attention).
    """
    q4 = q.ndim == 4
    group = 1
    if q4:
        b, h = q.shape[:2]
        hkv = k.shape[1]
        if h % hkv or v.shape[1] != hkv:
            raise ValueError(f"flash_attention: {h} query heads over "
                             f"{hkv} key and {v.shape[1]} value heads")
        group = h // hkv
        q = q.reshape(b * h, *q.shape[2:])
        k = k.reshape(b * hkv, *k.shape[2:])
        v = v.reshape(b * hkv, *v.shape[2:])
    n, lq, d = q.shape
    lk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    from . import interpret

    bq = _pick_block(lq, block_q)
    bk = _pick_block(lk, block_k)
    lq_p, lk_p = _round_up(lq, bq), _round_up(lk, bk)
    cfg = _Cfg(bool(causal), float(sm_scale), bq, bk, lq, lk, interpret(),
               group)
    pad = lambda x, L: jnp.pad(x, ((0, 0), (0, L - x.shape[1]), (0, 0)))
    qp, kp, vp = pad(q, lq_p), pad(k, lk_p), pad(v, lk_p)
    if return_lse:
        out, lse = _fwd(cfg, qp, kp, vp)
        out, lse = out[:, :lq], lse[:, :lq]
    else:
        out = _flash(cfg, qp, kp, vp)[:, :lq]
        lse = None
    if q4:
        out = out.reshape(b, h, lq, v.shape[-1])
        if lse is not None:
            lse = lse.reshape(b, h, lq)
    return (out, lse) if return_lse else out
