"""Ulysses-style all-to-all sequence parallelism (exact attention).

The second long-context mechanism SURVEY §5.7 calls for, complementing
the ring (parallel/ring.py): instead of rotating K/V blocks around the
'sp' axis, ONE all-to-all redistributes the sequence-sharded q/k/v so
each device holds ALL tokens for 1/sp of the heads, attention runs
locally (any kernel — here the dense composition XLA fuses; Pallas
flash drops in), and a second all-to-all restores sequence sharding.

Trade-off vs the ring: 2 all-to-alls of activation size per tensor
(constant collective count, bandwidth-bound, great on ICI's all-to-all)
vs sp-1 ppermute steps overlappable with compute; Ulysses caps sp at
the head count, the ring does not.  Differentiable via the built-in
all_to_all transpose rule.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..base import MXNetError

__all__ = ["ulysses_self_attention", "ulysses_plan"]


def ulysses_plan(sp, dp=0, n_devices=None, rules=None, accum_steps=1):
    """Compat shim: Ulysses all-to-all sequence parallelism as a
    :class:`~mxnet_tpu.parallel.plan.Plan` (docs/PERFORMANCE.md §Plan &
    planner) — the compiled step reshards heads through the all-to-all
    pair below."""
    from .plan import ulysses_plan as _up

    return _up(sp, dp=dp, n_devices=n_devices, rules=rules,
               accum_steps=accum_steps)


def _local_attn(q, k, v, causal, sm_scale):
    """Per-device attention after the head reshard: the Pallas flash
    kernel when enabled (no (L, L) score materialization — the point of
    SP for long sequences), else the shared dense composition."""
    from ..ops import pallas as _pk
    from ..ops.contrib_ops import _dense_attention

    if _pk.enabled() and _pk.use_compiled():
        return _pk.flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    return _dense_attention(q, k, v, causal, sm_scale)


def ulysses_self_attention(mesh, q, k, v, causal: bool = False,
                           sm_scale: Optional[float] = None,
                           axis: str = "sp",
                           batch_axes: Optional[tuple] = None):
    """Exact self-attention over q/k/v (N, L, D) with L sharded on `axis`.

    N (= batch*heads) must be divisible by the axis size: the all-to-all
    trades the sequence shard for a head shard.  Returns (N, L, D) with
    the input sharding.
    """
    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    if axis not in shape:
        raise MXNetError(f"mesh has no {axis!r} axis: {tuple(shape)}")
    S = shape[axis]
    # the all_to_all splits the PER-SHARD leading dim: account for any
    # batch_axes sharding of N before checking divisibility
    n_batch = 1
    for a in (batch_axes or ()):
        n_batch *= shape.get(a, 1)
    if q.shape[0] % max(n_batch, 1) or (q.shape[0] // max(n_batch, 1)) % S:
        raise MXNetError(
            f"Ulysses SP: local N={q.shape[0]}/{n_batch} heads*batch not "
            f"divisible by {axis}={S} (the all-to-all shards heads)")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])

    def fn(q_l, k_l, v_l):
        # (N, L/S, D) -> all-to-all -> (N/S, L, D): all tokens, 1/S heads
        def seq2head(x):
            return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=1,
                                      tiled=True)

        def head2seq(x):
            return jax.lax.all_to_all(x, axis, split_axis=1, concat_axis=0,
                                      tiled=True)

        qh, kh, vh = seq2head(q_l), seq2head(k_l), seq2head(v_l)
        out = _local_attn(qh, kh, vh, causal, sm_scale)
        return head2seq(out)

    spec = P(tuple(batch_axes) if batch_axes else None, axis, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec, check_vma=False)(q, k, v)
