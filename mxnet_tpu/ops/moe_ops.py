"""Sparse expert (mixture-of-experts) operators: routing over all experts,
and the part of the result that the experts HELD HERE give.

No reference counterpart.  A chip of an expert-parallel job holds
``experts_held = (first, count)`` of the ``n_routed`` experts of a layer.
The router keeps its published width: every token chooses its ``top_k``
among all experts and its weights are normalised over all its choices.  The
chip computes only the terms of its own experts; what the absent experts
would add is left out (on one chip the layer runs without its exchange).

No (token, choice) pair that lands on a held expert is ever dropped, at any
skew: the pairs are sorted by expert (the absent experts' last) and worked
through in chunks of as many sorted rows as there are tokens, as many chunks
as the landed pairs fill (a loop with a run-time trip count: one chunk unless
the held experts draw more pairs than there are tokens, 2.7 times the even
load at 8 of 128 experts and 6 choices; the worst case, ``tokens *
min(top_k, count)`` rows, only sizes the index arrays).  A chunk gathers its
tokens' rows, runs them through one grouped matrix product per projection
(megablox ``gmm`` on a TPU, ``jax.lax.ragged_dot`` elsewhere) and adds its
weighted results to its tokens' rows.  The work follows the landed pairs a
chunk at a time; inside a chunk every row runs, the rows past the landed
pairs as zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .registry import register

#: m, k and n tile of the grouped products on the chip (VMEM: about 10 MB)
GMM_TILING = (512, 1024, 1024)


@register("_contrib_moe_route")
def moe_route(data, weight, correction_bias, top_k=1, scaling=1.0,
              norm_topk_prob=True):
    """Sigmoid top-k routing over all experts.

    data (T, d); weight (E, d); correction_bias (E,).  ``s = sigmoid(data
    weight^T)`` in f32; the choices are the ``top_k`` of ``s +
    correction_bias``; a choice's weight is its ``s``, over the choices' sum
    where ``norm_topk_prob``, times ``scaling``.  Returns (experts (T, k)
    int32, weights (T, k) f32)."""
    with jax.named_scope("mx_moe_route"):
        logits = jnp.einsum("td,ed->te", data, weight,
                            preferred_element_type=jnp.float32)
        s = jax.nn.sigmoid(logits)
        biased = s + jax.lax.stop_gradient(
            correction_bias.astype(jnp.float32))
        _top, experts = jax.lax.top_k(biased, int(top_k))
        w = jnp.take_along_axis(s, experts, axis=-1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
        return experts.astype(jnp.int32), w * scaling


def _grouped_dot(lhs, rhs, group_sizes):
    """Rows of ``lhs`` (m, k), sorted by group, times their group's
    ``rhs[g]`` (k, n) -> (m, n) in lhs's type.  ``group_sizes`` sum to m."""
    from . import pallas as _pk

    if _pk.enabled() and _pk.use_compiled() \
            and lhs.shape[0] % GMM_TILING[0] == 0:
        from jax.experimental.pallas.ops.tpu.megablox import ops as _mb

        return _mb.gmm(lhs, rhs, group_sizes, lhs.dtype, GMM_TILING)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


def _chunk_part(data, flat_w, up, down, lo, order, starts, ends, k, chunk):
    """What sorted rows ``lo .. lo + chunk - 1`` add to every token's result
    (a (tokens, d) f32 array, zero but for those rows' tokens)."""
    landed = ends[-1]
    pairs = jax.lax.dynamic_slice(order, (lo,), (chunk,))
    token = pairs // k
    live = lo + jnp.arange(chunk) < landed
    here = jnp.clip(jnp.minimum(ends, lo + chunk) - jnp.maximum(starts, lo),
                    0, None)
    # a chunk runs whole: the rows past the landed pairs are zero rows, given
    # to the last held expert, so that a step's time does not follow the
    # router's draw from chunk to chunk (docs/NEMOTRON_H.md has the numbers)
    here = here.at[-1].add(chunk - jnp.sum(here))
    rows = jnp.where(live[:, None], data[token], 0)
    with jax.named_scope("mx_moe_experts"):
        h = _grouped_dot(rows, up, here)
        r = jnp.maximum(h, 0)
        y = _grouped_dot(r * r, down, here)
    w = jnp.where(live, flat_w[pairs], 0)
    return jnp.zeros(data.shape, jnp.float32).at[token].add(
        y.astype(jnp.float32) * w[:, None])


def _chunks_to_run(ends, chunk):
    return (ends[-1] + chunk - 1) // chunk


# The loop over chunks runs as many times as landed pairs need, so it cannot
# be differentiated through; forward and backward are written out.  The
# backward keeps nothing of the forward but its inputs: a chunk's rows are
# gathered and multiplied again where its gradient is taken.
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _all_chunks(data, flat_w, up, down, order, starts, ends, k, chunk):
    def body(i, out):
        return out + _chunk_part(data, flat_w, up, down, i * chunk, order,
                                 starts, ends, k, chunk)

    return jax.lax.fori_loop(0, _chunks_to_run(ends, chunk), body,
                             jnp.zeros(data.shape, jnp.float32))


def _all_chunks_fwd(data, flat_w, up, down, order, starts, ends, k, chunk):
    out = _all_chunks(data, flat_w, up, down, order, starts, ends, k, chunk)
    return out, (data, flat_w, up, down, order, starts, ends)


def _all_chunks_bwd(k, chunk, res, d_out):
    data, flat_w, up, down, order, starts, ends = res
    f32 = jnp.float32

    def body(i, acc):
        _out, pull = jax.vjp(
            lambda *a: _chunk_part(*a, i * chunk, order, starts, ends, k,
                                   chunk), data, flat_w, up, down)
        return tuple(a + g.astype(f32) for a, g in zip(acc, pull(d_out)))

    acc = jax.lax.fori_loop(
        0, _chunks_to_run(ends, chunk), body,
        tuple(jnp.zeros(a.shape, f32) for a in (data, flat_w, up, down)))
    grads = tuple(g.astype(a.dtype)
                  for g, a in zip(acc, (data, flat_w, up, down)))
    return grads + (None, None, None)


_all_chunks.defvjp(_all_chunks_fwd, _all_chunks_bwd)


@register("_contrib_moe_experts")
def moe_experts(data, experts, weights, up_weight, down_weight, first=0):
    """What the held experts add: ``sum_{e held} w_e relu2(u W_up,e)
    W_down,e`` for every token whose choices name them.

    data (T, d); experts, weights (T, k) from ``_contrib_moe_route``;
    up_weight (count, d, f) and down_weight (count, f, d) are experts
    ``first .. first + count - 1``.  Returns (out (T, d), pairs landed on
    each held expert (count,) int32)."""
    tokens, k = experts.shape
    count = up_weight.shape[0]
    tm = GMM_TILING[0]
    worst = tokens * min(k, count)
    chunk = -(-min(tokens, worst) // tm) * tm    # as many rows as tokens
    n_chunks = -(-worst // chunk)
    local = experts - int(first)
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)      # absent ones last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, max(0, n_chunks * chunk - order.shape[0])))
    sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), 0)
    ends = jnp.cumsum(sizes)
    out = _all_chunks(data, weights.astype(jnp.float32).reshape(-1),
                      up_weight, down_weight, order, ends - sizes, ends, k,
                      chunk)
    return out.astype(data.dtype), sizes
