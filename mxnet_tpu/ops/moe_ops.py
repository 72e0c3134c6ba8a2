"""Sparse expert (mixture-of-experts) operators: routing over all experts,
and the part of the result that the experts HELD HERE give.

No reference counterpart.  A chip of an expert-parallel job holds
``experts_held = (first, count)`` of the ``n_routed`` experts of a layer.
The router keeps its published width: every token chooses its ``top_k``
among all experts (by sigmoid scores of a linear router, or by the softmax
of logits a router block has made).  The chip computes only the terms of its
own experts; what the absent experts would add is left out (on one chip the
layer runs without its exchange).  An expert is ``relu2(u W_up) W_down`` or
the gated ``(silu(u W_gate) * (u W_up)) W_down``.

No (token, choice) pair that lands on a held expert is ever dropped, at any
skew: the pairs are sorted by expert (the absent experts' last) and worked
through in chunks of as many sorted rows as there are tokens, as many chunks
as the landed pairs fill (a loop with a run-time trip count: one chunk unless
the held experts draw more pairs than there are tokens, 2.7 times the even
load at 8 of 128 experts and 6 choices; the worst case, ``tokens *
min(top_k, count)`` rows, only sizes the index arrays; where that is one
chunk, as with one choice a token, the chunk runs once with no loop).  A
chunk gathers its tokens' rows, runs them through one grouped matrix product
per projection (megablox ``gmm`` on a TPU, ``jax.lax.ragged_dot`` elsewhere)
and adds its weighted results to its tokens' rows of the loop's f32 carry
(one Mosaic call on a TPU, ``ops/pallas/moe_rows.py``; a scatter-add
elsewhere).  The work follows the landed pairs a chunk at a time; inside a
chunk every row runs, the rows past the landed pairs with no weight.

megablox visits a tile of rows once for EVERY group with rows in it, each
visit a whole tile's product with the store masked, and fetches a group's
matrix block again at every step unless the whole contraction is one tile.
So the products' tiles follow their shapes (``grouped_tiling``, by the cost
written out in ``grouped_cost``): row tiles small where boundaries are many
beside the rows, the contraction whole wherever a block of it fits VMEM, so
that a group's matrix stays there across its row tiles.  Telemetry is told
each pick at trace time and, at ``drain``, the rows run over the rows had.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from . import pallas as _pk
from .pallas import moe_rows
from .registry import register

# What a grouped product costs on the chip, as far as its shapes tell: the
# matrix units' pace in bf16, the rate HBM feeds VMEM, what a grid step costs
# whatever it does, what ``tgmm`` spends an element of its row blocks to mask
# and turn them (in f32, on the vector units), and the VMEM a Mosaic call gets
# unasked less room for the kernel's own temporaries.  Fitted to one sweep on
# a v5e (tools/grouped_tiles.py measures the candidates beside this estimate;
# docs/ZAYA.md has its table): within 10% on the three models' shapes where
# the widths are whole lanes
MXU_FLOPS, HBM_BYTES, STEP_S, VMEM_BYTES = 197e12, 819e9, 0.35e-6, 14 << 20
MASK_S = 0.76e-12
ROW_TILES = (128, 256, 512)


def _tile_sizes(width):
    """``width`` whole, its even splits into 2 to 8 tiles of whole lanes (the
    last tile may hang over: what hangs over is masked and still runs), and
    1,024 and 512, which fit VMEM at any width."""
    split = {-(-width // (j * 128)) * 128 for j in range(2, 9)} | {1024, 512}
    return [width] + sorted((t for t in split if 256 <= t < width),
                            reverse=True)


def grouped_vmem(kind, tiling, itemsize, carry=False):
    """Bytes of VMEM a grouped product holds at ``tiling``: two buffers of
    every block, the f32 accumulator and, where an f32 ``carry`` is added to,
    its block in and out."""
    tm, tk, tn = tiling
    if kind == "gmm":
        return 2 * (tm * tk + tk * tn + tm * tn) * itemsize + tm * tn * 4
    out = 2 * tk * tn * (2 * 4 if carry else itemsize)
    return 2 * tm * (tk + tn) * itemsize + out + tk * tn * 4


def grouped_cost(kind, tiling, m, k, n, groups, itemsize, carry=False):
    """Seconds a grouped product takes at ``tiling`` by the worst case of its
    grid: a row tile is visited once by every group with rows in it, ``m / tm
    + groups - 1`` visits of a whole tile's work; a grid step takes the
    longer of its matrix-unit time and the fetch of the blocks whose index
    changed, and a fixed cost (``tgmm``'s grows with the rows it masks).
    ``gmm`` (rows (m, k) times (groups, k, n)) fetches a group's matrix block
    once a (group, n tile) where the whole contraction is one tile, and every
    step otherwise; ``tgmm`` (the matrices' gradient (groups, k, n), m
    contracted) writes a block, and reads the ``carry``'s, where the group
    changes."""
    tm, tk, tn = tiling
    visits = -(-m // tm) + groups - 1
    tiles_k, tiles_n = -(-k // tk), -(-n // tn)
    steps = visits * tiles_k * tiles_n
    mxu, step = 2 * tm * tk * tn / MXU_FLOPS, STEP_S
    if kind == "gmm":
        rows, matrix = tm * (tk + tn / tiles_k) * itemsize, tk * tn * itemsize
        changes = groups * tiles_n if tiles_k == 1 else steps
    else:
        rows = tm * (tk + tn) * itemsize
        matrix = tk * tn * (2 * 4 if carry else itemsize)
        changes = groups * tiles_k * tiles_n
        step += MASK_S * tm * (tk + tn)
    plain = max(mxu, rows / HBM_BYTES)
    # a carry's f32 block comes and goes beside the row blocks, not after
    change = (max(plain, matrix / HBM_BYTES) if carry
              else max(mxu, (rows + matrix) / HBM_BYTES))
    return (steps - changes) * plain + changes * change + steps * step


def grouped_candidates(kind, m, k, n, itemsize, carry=False,
                       vmem=VMEM_BYTES):
    """The tilings a grouped product may take: row tiles that divide ``m``
    where it is whole lanes of rows, ``tk`` and ``tn`` the width whole or a
    split of it (``tgmm``'s k and n are its result's: it has no contraction
    tile to mask), inside ``vmem`` by ``grouped_vmem``'s count."""
    tms = [t for t in ROW_TILES if m % t == 0] if m % 128 == 0 else ROW_TILES
    return [t for t in itertools.product(tms, _tile_sizes(k), _tile_sizes(n))
            if grouped_vmem(kind, t, itemsize, carry) <= vmem]


@functools.lru_cache(maxsize=None)
def grouped_tiling(kind, m, k, n, groups, itemsize, carry=False):
    """The (tm, tk, tn) a grouped product runs at on the chip: the cheapest
    of ``grouped_candidates`` by ``grouped_cost``, from the shapes alone."""
    return min(grouped_candidates(kind, m, k, n, itemsize, carry),
               key=lambda t: (grouped_cost(kind, t, m, k, n, groups,
                                           itemsize, carry), t))


def _choose(scores, bias, top_k, norm, scaling):
    """The ``top_k`` of ``scores + bias`` (the bias takes no gradient) and
    each choice's own score, over the choices' sum where ``norm``, times
    ``scaling`` -> (experts (T, k) int32, weights (T, k) f32)."""
    biased = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _top, experts = jax.lax.top_k(biased, int(top_k))
    w = jnp.take_along_axis(scores, experts, axis=-1)
    if norm:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return experts.astype(jnp.int32), w * scaling


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
        return _choose(jax.nn.sigmoid(logits), correction_bias, top_k,
                       norm_topk_prob, scaling)


@register("_contrib_moe_route_softmax")
def moe_route_softmax(logits, balance_bias, top_k=1):
    """Routing from a router's own logits (T, E): ``p = softmax(logits)`` in
    f32; the choices are the ``top_k`` of ``p + balance_bias``; a choice's
    weight is its ``p``, not renormalised.  Returns (experts (T, k) int32,
    weights (T, k) f32)."""
    with jax.named_scope("mx_moe_route"):
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return _choose(p, balance_bias, top_k, False, 1.0)


def row_tiles_visited(kind, group_sizes, tm):
    """Grid steps along the rows that megablox makes of sorted rows in
    groups of ``group_sizes``: every group's own run of tiles of ``tm`` rows,
    so a tile that holds two groups' rows counts for both; ``tgmm`` also
    visits an empty group once, to write its zeros."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes)
    tiles = np.where(sizes > 0, -(-ends // tm) - (ends - sizes) // tm,
                     kind == "tgmm")
    return int(tiles.sum())


def rows_run_over_rows(landed, tokens, top_k, tilings):
    """The row tiles that an expert layer's grouped products visited in one
    step, times their ``tm``, over the rows they had: 1 is the least, and a
    boundary between two experts inside a tile adds a tile's rows.  From
    ``landed`` (pairs on each held expert, what ``moe_experts`` returns), the
    layer's ``tokens`` and ``top_k``, and ``tilings``, the rows of
    ``telemetry.grouped_tiles()``: those of this layer's chunk count, each
    as often as it was traced.  None where no product ran as a kernel."""
    landed = np.asarray(landed, np.int64)
    worst = tokens * min(top_k, len(landed))
    rows = min(tokens, worst)
    mine = [t for t in tilings if t["groups"] == len(landed)
            and rows <= t["m"] < rows + max(ROW_TILES)]
    if not mine:
        return None
    chunk = mine[0]["m"]
    ends = np.cumsum(landed)
    starts = ends - landed
    n_chunks = 1 if worst == rows else -(-int(ends[-1]) // chunk)
    run = had = 0
    for lo in range(0, n_chunks * chunk, chunk):    # as _chunk_index has it
        here = np.clip(np.minimum(ends, lo + chunk) - np.maximum(starts, lo),
                       0, None)
        here[-1] += chunk - here.sum()
        for t in mine:
            tm = t["tiling"][0]
            run += t["sites"] * tm * row_tiles_visited(t["kind"], here, tm)
            had += t["sites"] * chunk
    return run / had if had else None


def _kernels(rows, tm):
    """True where a chunk of ``rows`` sorted rows runs as Mosaic calls: on a
    TPU, in per-device code, at whole row tiles of ``tm``.  Elsewhere (the
    CPU, a step that GSPMD partitions, other row counts) the same chunk runs
    as XLA ops."""
    return _pk.enabled() and _pk.use_compiled() and rows % tm == 0


def _tiling(kind, lhs, k, n, groups, carry=False):
    """``grouped_tiling`` for ``lhs``'s rows and type, told to telemetry
    (trace time: once a call site)."""
    shape = (lhs.shape[0], int(k), int(n), int(groups))
    tiling = grouped_tiling(kind, *shape, lhs.dtype.itemsize, carry)
    if _kernels(lhs.shape[0], tiling[0]):
        telemetry.record_grouped_tiles(kind, *shape, carry, tiling)
        return tiling
    return None


def _grouped_dot(lhs, rhs, group_sizes, transpose_rhs=False):
    """Rows of ``lhs`` (m, k), sorted by group, times their group's
    ``rhs[g]`` (k, n), or its transpose (rhs (groups, n, k)) -> (m, n) in
    lhs's type.  ``group_sizes`` sum to m."""
    tiling = _tiling("gmm", lhs, lhs.shape[1],
                     rhs.shape[1 if transpose_rhs else 2], rhs.shape[0])
    if tiling:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                   transpose_rhs=transpose_rhs, interpret=_pk.interpret())
    if transpose_rhs:
        rhs = rhs.swapaxes(1, 2)
    return jax.lax.ragged_dot(
        lhs, rhs, group_sizes,
        preferred_element_type=jnp.float32).astype(lhs.dtype)


_ROWS_CONTRACTED = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _grouped_dot_weights_grad(acc, lhs, d_out, group_sizes):
    """``acc`` (groups, k, n) f32 plus every group's ``lhs_g^T d_out_g``:
    what ``_grouped_dot(lhs, rhs, group_sizes)``'s gradient gives ``rhs``,
    summed in f32 into what is there (megablox ``tgmm`` adds to
    ``existing_out`` and aliases it).  With ``acc`` None (the only chunk of
    a loop that needs none) the sum alone, rounded once to ``lhs``'s type."""
    tiling = _tiling("tgmm", lhs, lhs.shape[1], d_out.shape[1],
                     group_sizes.shape[0], acc is not None)
    if tiling:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

        if acc is None:
            return tgmm(lhs.swapaxes(0, 1), d_out, group_sizes, lhs.dtype,
                        tiling, interpret=_pk.interpret())
        return tgmm(lhs.swapaxes(0, 1), d_out, group_sizes, jnp.float32,
                    tiling, existing_out=acc, interpret=_pk.interpret())
    grad = jax.lax.ragged_dot_general(
        lhs, d_out, group_sizes, _ROWS_CONTRACTED,
        preferred_element_type=jnp.float32)
    return grad.astype(lhs.dtype) if acc is None else acc + grad


def _add_rows(acc, rows, token, scale, here, n_live, fresh):
    """``acc`` (tokens, d) f32 plus ``rows[i] * scale[i]`` at ``token[i]``
    for the landed rows ``i < n_live``, multiplied and summed in f32.
    ``fresh`` says that ``acc`` is still all zero (the loop's first chunk).
    On the chip one Mosaic call that writes every row of ``acc`` once and
    does not read a fresh one (``ops/pallas/moe_rows.py``); elsewhere a
    scatter-add into ``acc``."""
    if _kernels(rows.shape[0], moe_rows.SLAB) and moe_rows.fits(
            acc.shape[0], *rows.shape, here.shape[0], rows.dtype.itemsize):
        return moe_rows.combine(acc, rows, token, scale, here, n_live, fresh,
                                interpret=_pk.interpret())
    live = jnp.arange(rows.shape[0]) < n_live
    return acc.at[token].add(
        rows.astype(jnp.float32) * jnp.where(live, scale, 0)[:, None])


def _chunk_index(i, order, flat_w, starts, ends, k, chunk):
    """Sorted rows ``i * chunk .. (i + 1) * chunk - 1``: their (token,
    choice) pairs and tokens, how many of them are landed pairs (they come
    first), their weights (0 past the landed pairs) and how many fall to each
    held expert."""
    lo = i * chunk
    pairs = jax.lax.dynamic_slice(order, (lo,), (chunk,))
    n_live = jnp.clip(ends[-1] - lo, 0, chunk)
    here = jnp.clip(jnp.minimum(ends, lo + chunk) - jnp.maximum(starts, lo),
                    0, None)
    # a chunk runs whole, so that a step's time does not follow the router's
    # draw from chunk to chunk (docs/NEMOTRON_H.md has the numbers): the rows
    # past the landed pairs go to the last held expert.  They are the rows of
    # whatever tokens their pairs name and weigh nothing: what they make is
    # added nowhere, and their gradient is 0 times a finite number
    here = here.at[-1].add(chunk - jnp.sum(here))
    w = jnp.where(jnp.arange(chunk) < n_live, flat_w[pairs], 0)
    return pairs, pairs // k, n_live, w, here


ACTIVATIONS = {"relu2": 2, "swiglu": 3}     # matrices an expert has


def _row_tile(rows, d, f, groups, itemsize, carry):
    """The rows a chunk of about ``rows`` is rounded up to whole multiples
    of: the largest row tile of its grouped products, forward (d -> f, f ->
    d) and backward (the same two shapes for the rows' gradients, both
    matrices' for the weights')."""
    return max(grouped_tiling(kind, rows, k, n, groups, itemsize, c)[0]
               for k, n in ((d, f), (f, d))
               for kind, c in (("gmm", False), ("tgmm", carry)))


def _activate(pre, activation):
    """An expert's hidden row from its row's products with the first
    matrices: ``relu2``: ``max(h, 0)^2`` of one; ``swiglu``: ``silu(g) * u``
    of two (gate, up), taken in f32 and rounded once."""
    if activation == "relu2":
        r = jnp.maximum(pre[0], 0)
        return r * r
    g, u = (t.astype(jnp.float32) for t in pre)
    return (jax.nn.silu(g) * u).astype(pre[0].dtype)


def _activate_bwd(pre, d_a, activation):
    """The products' cotangents from the hidden row's."""
    if activation == "relu2":
        d_r = d_a * jnp.maximum(pre[0], 0)
        return (jnp.where(pre[0] > 0, d_r + d_r, 0),)
    g, u, d = (t.astype(jnp.float32) for t in (*pre, d_a))
    sig = jax.nn.sigmoid(g)
    d_g = d * u * sig * (1 + g * (1 - sig))
    return d_g.astype(d_a.dtype), (d * g * sig).astype(d_a.dtype)


def _chunk_products(data, mats, token, here, activation):
    """A chunk's rows of ``data`` and what the held experts make of them:
    the rows' products with every matrix but the last, the hidden rows, and
    their product with the last."""
    rows = data[token]
    with jax.named_scope("mx_moe_experts"):
        pre = tuple(_grouped_dot(rows, m, here) for m in mats[:-1])
        a = _activate(pre, activation)
        y = _grouped_dot(a, mats[-1], here)
    return rows, pre, a, y


def _one_chunk(order, chunk):
    """The worst case is one chunk (``top_k`` 1): no loop, no carry."""
    return order.shape[0] == chunk


def _over_chunks(order, ends, chunk, body, init):
    """``body(i, carry)`` over the chunks that hold landed pairs: a loop
    with a run-time trip count; where the worst case is one chunk, that
    chunk once and no loop."""
    if _one_chunk(order, chunk):
        return body(0, init)
    return jax.lax.fori_loop(0, (ends[-1] + chunk - 1) // chunk, body, init)


# The loop over chunks runs as many times as landed pairs need, so it cannot
# be differentiated through; forward and backward are written out, once, for
# both back ends and both forms of expert (``mats`` is (up, down) or (gate,
# up, down)).  Every accumulator (the tokens' result; the gradients of the
# tokens, the weights and every matrix) is the loop's f32 carry and a chunk
# adds to it in place.  The backward keeps nothing of the forward but its
# inputs: a chunk's rows are gathered and multiplied again where its gradient
# is taken.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _all_chunks(data, flat_w, mats, order, starts, ends, k, chunk,
                activation):
    def body(i, out):
        _pairs, token, n_live, w, here = _chunk_index(
            i, order, flat_w, starts, ends, k, chunk)
        y = _chunk_products(data, mats, token, here, activation)[-1]
        return _add_rows(out, y, token, w, here, n_live, fresh=i == 0)

    out = _over_chunks(order, ends, chunk, body,
                       jnp.zeros(data.shape, jnp.float32))
    # rounded here, so that the gradient comes back in data's type and its
    # rows are gathered at that width (as exact: the chunks widen them)
    return out.astype(data.dtype)


def _all_chunks_fwd(data, flat_w, mats, order, starts, ends, k, chunk,
                    activation):
    out = _all_chunks(data, flat_w, mats, order, starts, ends, k, chunk,
                      activation)
    return out, (data, flat_w, mats, order, starts, ends)


def _all_chunks_bwd(k, chunk, activation, res, d_out):
    data, flat_w, mats, order, starts, ends = res
    f32 = jnp.float32

    def body(i, acc):
        d_data, d_flat_w, d_mats = acc
        pairs, token, n_live, w, here = _chunk_index(
            i, order, flat_w, starts, ends, k, chunk)
        live = jnp.arange(chunk) < n_live
        rows, pre, a, y = _chunk_products(data, mats, token, here,
                                          activation)
        # out[token] += w y: d_out's rows in f32, rounded once after the
        # multiplication by w; a weight gets its row's product with y
        taken = d_out[token].astype(f32)
        d_y = (taken * w[:, None]).astype(y.dtype)
        d_w = jnp.sum(taken * y.astype(f32), -1)
        d_flat_w = d_flat_w.at[pairs].add(jnp.where(live, d_w, 0))
        with jax.named_scope("mx_moe_experts"):
            d_a = _grouped_dot(d_y, mats[-1], here, transpose_rhs=True)
            d_last = _grouped_dot_weights_grad(d_mats[-1], a, d_y, here)
            d_pre = _activate_bwd(pre, d_a, activation)
            d_rows = [_grouped_dot(d, m, here, transpose_rhs=True)
                      for d, m in zip(d_pre, mats)]
            d_first = tuple(_grouped_dot_weights_grad(dm, rows, d, here)
                            for dm, d in zip(d_mats, d_pre))
        if len(d_rows) > 1:     # two products' rows, summed before rounding
            d_rows = [sum(d.astype(f32) for d in d_rows).astype(rows.dtype)]
        d_data = _add_rows(d_data, d_rows[0], token, live.astype(f32), here,
                           n_live, fresh=i == 0)
        return d_data, d_flat_w, d_first + (d_last,)

    def zeros(t):
        return jnp.zeros(t.shape, f32)

    # one chunk adds to nothing: its weight gradients need no f32 carry
    d_mats = tuple(None if _one_chunk(order, chunk) else zeros(m)
                   for m in mats)
    d_data, d_flat_w, d_mats = _over_chunks(
        order, ends, chunk, body, (zeros(data), zeros(flat_w), d_mats))
    return (d_data.astype(data.dtype), d_flat_w.astype(flat_w.dtype),
            tuple(g.astype(m.dtype) for g, m in zip(d_mats, mats)),
            None, None, None)


_all_chunks.defvjp(_all_chunks_fwd, _all_chunks_bwd)


@register("_contrib_moe_experts")
def moe_experts(data, experts, weights, up_weight, down_weight,
                gate_weight=None, first=0, activation="relu2"):
    """What the held experts add: ``sum_{e held} w_e f_e(u) W_down,e`` for
    every token whose choices name them, with ``f_e(u) = relu2(u W_up,e)``
    (``activation="relu2"``) or ``silu(u W_gate,e) * (u W_up,e)``
    (``"swiglu"``, which takes ``gate_weight``).

    data (T, d); experts, weights (T, k) from a routing op; up_weight,
    gate_weight (count, d, f) and down_weight (count, f, d) are experts
    ``first .. first + count - 1``.  Returns (out (T, d), pairs landed on
    each held expert (count,) int32)."""
    if activation not in ACTIVATIONS or (
            (gate_weight is None) != (ACTIVATIONS[activation] == 2)):
        raise ValueError(f"moe_experts: activation {activation!r} with "
                         f"{2 + (gate_weight is not None)} matrices")
    mats = (up_weight, down_weight) if gate_weight is None else (
        gate_weight, up_weight, down_weight)
    tokens, k = experts.shape
    count = up_weight.shape[0]
    worst = tokens * min(k, count)
    rows = min(tokens, worst)                    # as many rows as tokens
    tm = _row_tile(rows, *up_weight.shape[1:], count, data.dtype.itemsize,
                   carry=worst > rows)
    chunk = -(-rows // tm) * tm
    n_chunks = -(-worst // chunk)
    local = experts - int(first)
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)      # absent ones last
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    order = jnp.pad(order, (0, max(0, n_chunks * chunk - order.shape[0])))
    sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), 0)
    ends = jnp.cumsum(sizes)
    out = _all_chunks(data, weights.astype(jnp.float32).reshape(-1), mats,
                      order, ends - sizes, ends, k, chunk, activation)
    return out, sizes
