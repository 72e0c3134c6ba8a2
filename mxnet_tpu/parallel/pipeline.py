"""Pipeline parallelism: a compiled GPipe-style schedule over the 'pp'
mesh axis.

The capability row the reference leaves empty (SURVEY §2.3: nearest
analog is group2ctx manual placement with no microbatching).  TPU-native
design: all pp ranks run ONE SPMD program; each holds its stage's layer
parameters (leading layer dim sharded over 'pp'), microbatch activations
hop stage-to-stage via `ppermute` (ICI neighbour exchange), and the
whole schedule — warmup bubble, steady state, drain — is a `lax.scan`
inside the surrounding jit, so XLA overlaps the permute with compute.

Uniform-stage restriction: every layer must share one apply function and
parameter structure (true of transformer/BERT encoders, the models this
targets).  Differentiable end-to-end: jax.grad through scan + ppermute
gives the standard 1F1B-equivalent backward bubble.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..base import MXNetError

__all__ = ["pipeline_apply", "pipeline_plan"]


def pipeline_plan(pp, microbatches=4, dp=0, n_devices=None, rules=None,
                  accum_steps=1):
    """Compat shim: the GPipe pipeline strategy as a
    :class:`~mxnet_tpu.parallel.plan.Plan` — stacked-encoder models
    route through :func:`pipeline_apply` when the compiled step
    activates the pp scope (docs/PERFORMANCE.md §Plan & planner)."""
    from .plan import pipeline_plan as _pp

    return _pp(pp, microbatches=microbatches, dp=dp, n_devices=n_devices,
               rules=rules, accum_steps=accum_steps)


def pipeline_apply(mesh, fn: Callable, stacked_params, x_micro,
                   axis: str = "pp", batch_axes=(), param_specs=None):
    """Run L stacked uniform layers as a pp-stage pipeline.

    mesh: jax Mesh with a size-S `axis`; L must be divisible by S.
    fn(params_slice, x) -> y with y.shape == x.shape (one layer).
    stacked_params: pytree whose leaves have leading dim L, sharded over
        `axis` (each stage owns L/S consecutive layers).
    x_micro: (M, b, ...) microbatches; dim 1 (the batch dim) may be
        sharded over `batch_axes` (e.g. ("dp",)) — dp×pp composition
        without the shard_map forcing a batch all-gather.
    param_specs: optional pytree of PartitionSpec matching stacked_params
        for tensor parallelism INSIDE the stage: leaves may shard extra
        dims over 'tp' (Megatron column/row splits), in which case `fn`
        runs on local shards and must psum its row-parallel outputs over
        'tp' itself.  Default: every leaf P(axis) (layer dim only).
    Returns (M, b, ...) outputs, same sharding (valid on every pp rank).

    Schedule: M + S - 1 clock ticks; at tick t, stage r processes
    microbatch t - r (its warmup/drain ticks compute discarded garbage —
    the classic GPipe bubble, fraction (S-1)/(M+S-1)).
    """
    from jax.sharding import PartitionSpec as P

    shape = dict(mesh.shape)
    if axis not in shape:
        raise MXNetError(f"mesh has no {axis!r} axis: {tuple(shape)}")
    batch_axes = tuple(a for a in batch_axes
                       if a in shape and shape[a] > 1 and a != axis)
    S = shape[axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if not leaves:
        raise MXNetError("stacked_params is empty")
    L = leaves[0].shape[0]
    if L % S:
        raise MXNetError(
            f"{L} stacked layers not divisible by {axis}={S} stages")
    M = int(x_micro.shape[0])

    def ranked(params_local, xm):
        # params_local leaves: (L/S, ...) — this rank's stage layers
        r = jax.lax.axis_index(axis)

        def stage(x):
            def body(c, pl):
                return fn(pl, c), None

            out, _ = jax.lax.scan(body, x, params_local)
            return out

        buf = jnp.zeros_like(xm)
        state = jnp.zeros(xm.shape[1:], xm.dtype)

        def tick(carry, t):
            buf, state = carry
            # stage 0 pulls microbatch t from the feed; others take the
            # neighbour's output received at the end of the previous tick
            feed = xm[jnp.clip(t, 0, M - 1)]
            inp = jnp.where(r == 0, feed, state)
            out = stage(inp)
            nxt = jax.lax.ppermute(out, axis,
                                   [(i, (i + 1) % S) for i in range(S)])
            # the LAST stage finished microbatch t-(S-1) this tick
            idx = t - (S - 1)
            valid = jnp.logical_and(r == S - 1,
                                    jnp.logical_and(idx >= 0, idx < M))
            upd = jax.lax.dynamic_update_index_in_dim(
                buf, out, jnp.clip(idx, 0, M - 1), 0)
            buf = jnp.where(valid, upd, buf)
            return (buf, nxt), None

        (buf, _), _ = jax.lax.scan(tick, (buf, state),
                                   jnp.arange(M + S - 1))
        # replicate the last stage's collected outputs to every rank
        return jax.lax.psum(
            jnp.where(r == S - 1, buf, jnp.zeros_like(buf)), axis)

    spec_p = (param_specs if param_specs is not None else
              jax.tree_util.tree_map(lambda _: P(axis), stacked_params))
    spec_x = P(None, batch_axes if len(batch_axes) > 1 else
               (batch_axes[0] if batch_axes else None))
    if not any(isinstance(l, jax.core.Tracer)
               for l in leaves + [x_micro]):
        # eager call: operands are committed to single devices; lay them
        # out on the mesh first (inside a jit the shardings are already
        # the caller's concern — DataParallelStep's rules)
        from jax.sharding import NamedSharding

        stacked_params = jax.tree_util.tree_map(
            lambda l, sp: jax.device_put(l, NamedSharding(mesh, sp)),
            stacked_params, spec_p)
        x_micro = jax.device_put(x_micro, NamedSharding(mesh, spec_x))
    fn_sm = jax.shard_map(ranked, mesh=mesh, in_specs=(spec_p, spec_x),
                          out_specs=spec_x, check_vma=False)
    return fn_sm(stacked_params, x_micro)
