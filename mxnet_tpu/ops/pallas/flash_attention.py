"""FlashAttention-2 in Pallas (TPU).

Blockwise online-softmax attention: never materialises the (Lq, Lk) score
matrix in HBM.  Forward keeps a running (max, sum, acc) per q row; backward
is the standard two-kernel FA2 scheme (dq sweep over k blocks; dk/dv sweep
over q blocks) using the saved logsumexp.

Reference parity: supersedes src/operator/contrib/transformer.cc
(interleaved_matmul_selfatt_qk/valatt ~L1-300), which fused only the
attention matmuls and still materialised scores for a separate softmax op.

Shapes: q (N, Lq, D), k/v (N, Lk, D) with N = batch*heads; 4D
(B, H, L, D) inputs are reshaped.  Compute is f32 on the MXU regardless of
input dtype (bf16 inputs stay bf16 in HBM/VMEM).
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


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, o_ref, lse_ref):
    qi = pl.program_id(1)
    bq, bk = cfg.block_q, cfg.block_k
    q = q_ref[0].astype(jnp.float32) * cfg.sm_scale          # (bq, D)
    nkb = k_ref.shape[1] // bk

    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, q.shape[-1]), jnp.float32)

    def body(kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kj * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kj * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(_kv_mask(cfg, qi, kj, bq, bk), s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = jax.lax.fori_loop(0, nkb, body, (m0, l0, a0))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(safe_l), (bq, _LANES))


def _fwd(cfg: _Cfg, q, k, v):
    n, lq, d = q.shape
    lk = k.shape[1]
    nqb = lq // cfg.block_q
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        name="mx_flash_fwd",
        grid=(n, nqb),
        in_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, lk, d), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, lk, d), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, lq, d), q.dtype),
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
    q = q_ref[0].astype(jnp.float32) * cfg.sm_scale
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0:1]
    delta = delta_ref[0, :, 0:1]
    nkb = k_ref.shape[1] // bk
    dq0 = jnp.zeros_like(q)

    def body(kj, dq):
        k = k_ref[0, pl.ds(kj * bk, bk), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(kj * bk, bk), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(_kv_mask(cfg, qi, kj, bq, bk), s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nkb, body, dq0)
    dq_ref[0] = (dq * cfg.sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref):
    kj = pl.program_id(1)
    bq, bk = cfg.block_q, cfg.block_k
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    nqb = q_ref.shape[1] // bq
    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * bq, bq), :].astype(jnp.float32) * cfg.sm_scale
        do = do_ref[0, pl.ds(qi * bq, bq), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * bq, bq), 0:1]
        delta = delta_ref[0, pl.ds(qi * bq, bq), 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(_kv_mask(cfg, qi, kj, bq, bk), s, _NEG)
        p = jnp.exp(s - lse)                                   # (bq, bk)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(0, nqb, body, (dk0, dv0))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_impl(cfg: _Cfg, q, k, v, out, lse, do):
    n, lq, d = q.shape
    lk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # (n, lq)
    lse3 = jnp.broadcast_to(lse[..., None], (n, lq, _LANES))
    delta3 = jnp.broadcast_to(delta[..., None], (n, lq, _LANES))
    common = [
        pl.BlockSpec((1, lq, d), lambda b, i: (b, 0, 0)),      # q
        pl.BlockSpec((1, lk, d), lambda b, i: (b, 0, 0)),      # k
        pl.BlockSpec((1, lk, d), lambda b, i: (b, 0, 0)),      # v
        pl.BlockSpec((1, lq, d), lambda b, i: (b, 0, 0)),      # do
        pl.BlockSpec((1, lq, _LANES), lambda b, i: (b, 0, 0)),   # lse
        pl.BlockSpec((1, lq, _LANES), lambda b, i: (b, 0, 0)),   # delta
    ]
    dq_specs = list(common)
    dq_specs[0] = pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0))
    dq_specs[3] = pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0))
    dq_specs[4] = pl.BlockSpec((1, cfg.block_q, _LANES),
                               lambda b, i: (b, i, 0))
    dq_specs[5] = pl.BlockSpec((1, cfg.block_q, _LANES),
                               lambda b, i: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        name="mx_flash_dq",
        grid=(n, lq // cfg.block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, lq, d), q.dtype),
        interpret=cfg.interpret,
    )(q, k, v, do, lse3, delta3)

    dkv_specs = list(common)
    dkv_specs[1] = pl.BlockSpec((1, cfg.block_k, d), lambda b, j: (b, j, 0))
    dkv_specs[2] = pl.BlockSpec((1, cfg.block_k, d), lambda b, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg),
        name="mx_flash_dkv",
        grid=(n, lk // cfg.block_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, cfg.block_k, d), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, cfg.block_k, d), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, lk, d), k.dtype),
            jax.ShapeDtypeStruct((n, lk, d), v.dtype),
        ],
        interpret=cfg.interpret,
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Cfg, q, k, v):
    out, _ = _fwd(cfg, q, k, v)
    return out


def _flash_fwd(cfg: _Cfg, q, k, v):
    out, lse = _fwd(cfg, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_bwd(cfg: _Cfg, res, do):
    q, k, v, out, lse = res
    return _bwd_impl(cfg, q, k, v, out, lse, do)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    return_lse: bool = False):
    """Fused attention: softmax(q @ k^T * sm_scale [+ causal mask]) @ v.

    q: (N, Lq, D) or (B, H, Lq, D); k, v likewise with Lk.  Differentiable
    in q/k/v (FA2 backward).  `return_lse` additionally returns the row
    logsumexp (N, Lq) in f32 (not differentiable; used by ring attention).
    """
    q4 = q.ndim == 4
    if q4:
        b, h = q.shape[:2]
        q = q.reshape(b * h, *q.shape[2:])
        k = k.reshape(b * h, *k.shape[2:])
        v = v.reshape(b * h, *v.shape[2:])
    n, lq, d = q.shape
    lk = k.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    from . import interpret

    bq = _pick_block(lq, block_q)
    bk = _pick_block(lk, block_k)
    lq_p, lk_p = _round_up(lq, bq), _round_up(lk, bk)
    cfg = _Cfg(bool(causal), float(sm_scale), bq, bk, lq, lk, interpret())
    pad = lambda x, L: jnp.pad(x, ((0, 0), (0, L - x.shape[1]), (0, 0)))
    qp, kp, vp = pad(q, lq_p), pad(k, lk_p), pad(v, lk_p)
    if return_lse:
        out, lse = _fwd(cfg, qp, kp, vp)
        out, lse = out[:, :lq], lse[:, :lq]
    else:
        out = _flash(cfg, qp, kp, vp)[:, :lq]
        lse = None
    if q4:
        out = out.reshape(b, h, lq, d)
        if lse is not None:
            lse = lse.reshape(b, h, lq)
    return (out, lse) if return_lse else out
