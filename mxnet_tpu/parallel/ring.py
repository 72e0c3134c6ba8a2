"""Ring attention: exact attention over a sequence-sharded axis.

Long-context support the reference never had (SURVEY §5.7: absent —
bucketing and fused attention matmuls only).  Each device holds a length
L/sp slice of q, k, v.  K/V blocks rotate around the 'sp' mesh axis via
`ppermute` (ICI neighbour exchange); each step folds the visiting block
into a running online-softmax state, so the full (L, L) score matrix never
exists and per-device activation memory stays O((L/sp)^2).

Backward is a second ring pass: q/do/lse/delta stay resident while
(k, v, dk, dv) travel the ring; dk/dv arrive home after a full rotation.
Wrapped in jax.custom_vjp so the forward ring is not differentiated
through (which would save every rotation's intermediates).

Use under `shard_map` with the sequence axis sharded over 'sp'
(see `ring_self_attention` and tests/test_pallas.py).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "ring_self_attention", "ring_plan"]


def ring_plan(sp, dp=0, n_devices=None, rules=None, accum_steps=1):
    """Compat shim: ring-attention sequence parallelism as a
    :class:`~mxnet_tpu.parallel.plan.Plan` (docs/PERFORMANCE.md §Plan &
    planner) — the compiled step lowers fused-attention ops to the
    ppermute K/V rotation below."""
    from .plan import ring_plan as _rp

    return _rp(sp, dp=dp, n_devices=n_devices, rules=rules,
               accum_steps=accum_steps)

_NEG = -1e30


class _RCfg(NamedTuple):
    axis_name: str
    causal: bool
    sm_scale: float


def _block(cfg: _RCfg, q, k, v, q_off, k_off):
    """Scores of local q against one visiting k/v block (f32)."""
    s = jnp.einsum("nqd,nkd->nqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * cfg.sm_scale
    if cfg.causal:
        lq, lk = q.shape[1], k.shape[1]
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where(qpos[None] >= kpos[None], s, _NEG)
    return s


def _rotate(cfg: _RCfg, *xs):
    n = jax.lax.psum(1, cfg.axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    return tuple(jax.lax.ppermute(x, cfg.axis_name, perm) for x in xs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring(cfg: _RCfg, q, k, v):
    out, _ = _ring_fwd_impl(cfg, q, k, v)
    return out


def _ring_fwd_impl(cfg: _RCfg, q, k, v):
    n = jax.lax.psum(1, cfg.axis_name)
    idx = jax.lax.axis_index(cfg.axis_name)
    lq, lk = q.shape[1], k.shape[1]
    q_off = idx * lq

    m = jnp.full(q.shape[:2], _NEG, jnp.float32)
    l = jnp.zeros(q.shape[:2], jnp.float32)
    acc = jnp.zeros(q.shape, jnp.float32)

    def step(i, carry):
        m, l, acc, k, v = carry
        k_off = ((idx - i) % n) * lk
        s = _block(cfg, q, k, v, q_off, k_off)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "nqk,nkd->nqd", p, v.astype(jnp.float32))
        k, v = _rotate(cfg, k, v)
        return m_new, l, acc, k, v

    m, l, acc, k, v = jax.lax.fori_loop(0, n, step, (m, l, acc, k, v))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l[..., None]).astype(q.dtype)
    lse = m + jnp.log(safe_l)
    return out, lse


def _ring_fwd(cfg: _RCfg, q, k, v):
    out, lse = _ring_fwd_impl(cfg, q, k, v)
    return out, (q, k, v, out, lse)


def _ring_bwd(cfg: _RCfg, res, do):
    q, k, v, out, lse = res
    n = jax.lax.psum(1, cfg.axis_name)
    idx = jax.lax.axis_index(cfg.axis_name)
    lq, lk = q.shape[1], k.shape[1]
    q_off = idx * lq
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)   # (n_heads, lq)

    dq = jnp.zeros(q.shape, jnp.float32)
    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)

    def step(i, carry):
        dq, dk, dv, k, v = carry
        k_off = ((idx - i) % n) * lk
        s = _block(cfg, q, k, v, q_off, k_off)
        p = jnp.exp(s - lse[..., None])                       # (N, lq, lk)
        dv = dv + jnp.einsum("nqk,nqd->nkd", p, dof)
        dp = jnp.einsum("nqd,nkd->nqk", dof, v.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * cfg.sm_scale
        dq = dq + jnp.einsum("nqk,nkd->nqd", ds, k.astype(jnp.float32))
        dk = dk + jnp.einsum("nqk,nqd->nkd", ds, q.astype(jnp.float32))
        k, v, dk, dv = _rotate(cfg, k, v, dk, dv)
        return dq, dk, dv, k, v

    dq, dk, dv, k, v = jax.lax.fori_loop(0, n, step, (dq, dk, dv, k, v))
    # after n rotations dk/dv have returned to their home shard
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None):
    """Exact attention with k/v rotating around mesh axis `axis_name`.

    Call inside `shard_map` with q/k/v sequence-sharded over that axis.
    q: (N, Lq/sp, D), k/v: (N, Lk/sp, D) per device, N = batch*heads.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    cfg = _RCfg(axis_name, bool(causal), float(sm_scale))
    return _ring(cfg, q, k, v)


def ring_self_attention(mesh, q, k, v, causal: bool = False,
                        sm_scale: Optional[float] = None, axis: str = "sp",
                        batch_axes: Optional[tuple] = None):
    """Convenience: shard_map-wrapped ring attention over mesh axis `axis`.

    q/k/v are global (N, L, D) arrays; the sequence dim is sharded over
    `axis`, N sharded over `batch_axes` (replicated when None).  Returns
    the global (N, L, D) output.  The single shard_map wrapper — callers
    (incl. the _contrib_flash_attention ring route) go through here.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(tuple(batch_axes) if batch_axes else None, axis, None)
    fn = functools.partial(ring_attention, axis_name=axis, causal=causal,
                           sm_scale=sm_scale)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
