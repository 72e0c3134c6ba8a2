"""The Mamba-2 chunked scan (SSD form) in Pallas (TPU), forward and backward.

Same equations and the same arithmetic as ``ops/ssm_ops.py`` ``_ssd_scan``
(decays, prefix sums and the carried state in f32; matrix products take
their operands in x's type and accumulate in f32).  Where ``_ssd_scan``
casts x.dt to x's type and scales that again for the state's product, XLA on
the chip keeps the f32 product between the two casts
(``xla_allow_excess_precision``), so each operand is rounded once; the kernels
do the same, which is what keeps their y within a bf16 rounding of the einsum
form's on 99.9% of elements (PERF.md, PR 28).  Laid out for the chip:

* the operands are read where the projections left them: x and y as
  (B, L, H x P), B and C as (B, L, G x N), dt as (B, L, H).  A grid step is
  one chunk of positions by one group's heads; nothing is moved to a
  head-major layout and no f32 copy of x or y is made in HBM;
* a sequence's chunks are the innermost, sequential grid axis.  The
  (heads of the group x P, N) state lives in f32 VMEM scratch; in one visit
  of a chunk the kernel takes softplus and the log-decay prefix sum over the
  chunk's positions, C.B^T once for the group, then per head the masked
  decay, its product with C.B^T, the three products with x.dt (inside the
  chunk, into the state, out of the carried state) and the state's update;
* the backward walks the chunks in reverse with the state's cotangent in
  VMEM and rebuilds each chunk's decay from dt.  The only scan-sized
  residual is the state at each chunk's start, (B, chunks, G, H / G, P, N)
  in f32.

Per-position scalars (dt, the prefix sum, its exponentials) are computed
with the positions along the lanes, (heads, chunk): one vector register a
quantity.  Where a row of x has to be scaled by its position's scalar, the
MXU lays the scalars out, not the lane-permute unit (which was the busiest
unit of a first version that broadcast (chunk, 1) columns): each f32 value is
split into three bf16 pieces that add up to it, and one product with a 0/1
matrix puts head r's value on head r's lanes, exactly.  The backward's sums
over a head's lanes go the same way, transposed, so they arrive as (heads,
chunk) rows for the reverse prefix sum.

Heads narrower than the 128 lanes are worked ``128 / P`` at a time on one
aligned slab of x's columns; a lane mask keeps each head's columns apart, so
no operand is ever sliced inside a lane tile.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
_LANES = 128


class _Cfg(NamedTuple):
    chunk: int
    length: int    # unpadded
    rep: int       # heads a group
    p: int
    n: int
    interpret: bool

    @property
    def slab_heads(self) -> int:
        """Heads worked together on one lane-aligned slab of x's columns."""
        return max(1, min(self.rep, _LANES // self.p))

    @property
    def slab(self) -> int:
        return self.slab_heads * self.p


def supported(x, b, chunk) -> bool:
    """Shapes the compiled kernels take: chunk and state whole lane tiles,
    head dim whole sublane tiles that pack into (or are made of) lane tiles
    and no wider than a chunk, heads a multiple of groups."""
    heads, p = x.shape[2], x.shape[3]
    groups, n = b.shape[2], b.shape[3]
    if chunk % _LANES or n % _LANES or p % 8 or heads % groups:
        return False
    rep = heads // groups
    if p < _LANES:
        return _LANES % p == 0 and rep % (_LANES // p) == 0
    return p % _LANES == 0 and p <= chunk


def _dot(a, b, dims=None, precision=None):
    if dims is None:
        return jax.lax.dot(a, b, precision=precision,
                           preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=_F32)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _sum_all(v):
    return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)


class _Pre(NamedTuple):
    """A chunk's per-position scalars for one group, positions along the
    lanes ((rep, chunk) f32 each), and what lays them along the sublanes
    through the MXU."""
    z: jax.Array        # dt + dt_bias, before the softplus
    dt: jax.Array       # softplus(z), 0 past the length
    acum: jax.Array     # inclusive prefix sum of dt * a
    a: jax.Array        # (rep, 1)
    end: jax.Array      # (rep, 1): acum at the chunk's end
    live: object        # (1, chunk) bool, or None when nothing is padded
    pieces: jax.Array   # (chunk, K): [dt | acum] as three bf16 pieces a
    #                     value, transposed
    expand_ref: object
    lanes: int          # rep P: the width of x's block

    def _spread(self, lo, width):
        # a value's pieces (at most one non-zero product each) add up to it
        # exactly in the MXU's f32 accumulator
        return _dot(self.pieces, self.expand_ref[:, lo:lo + width])

    def dt_over(self, at, width):
        """(chunk, width) f32: every head's dt_t over its own P lanes of x,
        columns ``at .. at + width``."""
        return self._spread(at, width)

    def acum_of(self, r):
        """(chunk, chunk) f32: head r's acum_t along every row t."""
        q = self.dt.shape[1]
        return self._spread(self.lanes + r * q, q)

    def last_of(self, r, width):
        """exp(acum) at the chunk's end for head r, over (1, width) lanes
        (Mosaic broadcasts along one of lanes and sublanes at a time)."""
        return jnp.broadcast_to(jnp.exp(self.end[r:r + 1, :]), (1, width))


def _pieces_rows(cfg: _Cfg) -> int:
    """Rows of the expansion matrix: three pieces of two quantities a head,
    up to whole lane tiles."""
    return -(-6 * cfg.rep // _LANES) * _LANES


def _expander(cfg: _Cfg, dtype):
    """The 0/1 matrix (K, rep P + rep chunk) that ``_Pre`` spreads with: row
    ``piece * 2 rep + quantity * rep + r`` feeds head r's lanes of dt's
    expansion (P lanes a head) or acum's (chunk lanes a head).  Made where
    the call is traced: a constant."""
    import numpy as np

    rep, p, q = cfg.rep, cfg.p, cfg.chunk
    out = np.zeros((_pieces_rows(cfg), rep * p + rep * q), np.float32)
    for quantity, lo, width in ((0, 0, p), (1, rep * p, q)):
        for piece in range(3):
            for r in range(rep):
                out[piece * 2 * rep + quantity * rep + r,
                    lo + r * width:lo + (r + 1) * width] = 1.0
    return jnp.asarray(out, dtype)


def _prelude(cfg: _Cfg, ci, dt_ref, alog_ref, bias_ref, expand_ref) -> _Pre:
    q, rep = cfg.chunk, cfg.rep
    heads = dt_ref.shape[2]
    raw = dt_ref[0]                                           # (q, H)
    if heads == rep:
        sel = (_iota((rep, heads), 0) == _iota((rep, heads), 1))
    else:
        sel = (_iota((rep, heads), 1)
               == _iota((rep, heads), 0) + pl.program_id(1) * rep)
    # this group's heads, transposed: a 0/1 product moves values unchanged
    # (one pass when dt is bf16, the f32 passes otherwise)
    if raw.dtype == jnp.bfloat16:
        dt_rows = _dot(sel.astype(raw.dtype), raw, _NT)
    else:
        dt_rows = _dot(sel.astype(_F32), raw.astype(_F32), _NT, _HIGHEST)
    z = dt_rows + bias_ref[0]                                 # (rep, q)
    dt = jax.nn.softplus(z)
    live = None
    if cfg.length % q:
        live = ci * q + _iota((1, q), 1) < cfg.length
        dt = jnp.where(live, dt, 0.0)
    a = -jnp.exp(alog_ref[0])                                 # (rep, 1)
    upper = (_iota((q, q), 0) <= _iota((q, q), 1)).astype(_F32)
    acum = _dot(dt * a, upper, precision=_HIGHEST)            # prefix sum
    end = jnp.sum(jnp.where(_iota((1, q), 1) == q - 1, acum, 0.0), axis=1,
                  keepdims=True)
    rows = jnp.concatenate([dt, acum], axis=0)
    # three bf16 pieces that add up to the f32 value, then one product with
    # the identity turns (pieces, chunk) into (chunk, pieces), unchanged
    bf16 = jnp.bfloat16
    first = rows.astype(bf16)
    rest = rows - first.astype(_F32)
    second = rest.astype(bf16)
    third = (rest - second.astype(_F32)).astype(bf16)
    pad = _pieces_rows(cfg) - 6 * rep
    stack = jnp.concatenate(
        [first.astype(_F32), second.astype(_F32), third.astype(_F32)]
        + ([jnp.zeros((pad, q), _F32)] if pad else []), axis=0).astype(bf16)
    eye = (_iota((q, q), 0) == _iota((q, q), 1)).astype(bf16)
    pieces = _dot(eye, stack, _NT).astype(bf16)               # (q, K)
    if expand_ref.dtype != bf16:            # f32 operands: the f32 passes
        pieces = pieces.astype(_F32)
    return _Pre(z, dt, acum, a, end, live, pieces, expand_ref, rep * cfg.p)


def _head_masks(cfg: _Cfg, width):
    """One (1, width) lane mask a head of a slab, or [None] when a slab is
    one head."""
    if cfg.slab_heads == 1:
        return [None]
    lane = _iota((1, width), 1) // cfg.p
    return [lane == k for k in range(cfg.slab_heads)]


def _only(mask, v):
    return v if mask is None else jnp.where(mask, v, jnp.zeros_like(v))


def _by_head(masks, parts):
    """One (chunk, slab width) array that holds, on head k's lanes,
    ``parts[k]``."""
    out = parts[0]
    for mask, part in zip(masks[1:], parts[1:]):
        out = jnp.where(mask, part, out)
    return out


def _decay(acum_rows, pre: _Pre, r, tri):
    """exp(acum_t - acum_s) for s <= t, 0 above the diagonal."""
    return jnp.exp(jnp.where(tri, acum_rows - pre.acum[r:r + 1, :], -jnp.inf))


class _Slab(NamedTuple):
    """What the heads of one slab share: acum_t along the rows for each
    (chunk, chunk), and over x's lanes exp(acum_t) and exp(end - acum_t)."""
    acum_rows: list
    exp: jax.Array
    to_end: jax.Array


def _slab(cfg: _Cfg, pre: _Pre, slab, masks) -> _Slab:
    w, first = cfg.slab, slab * cfg.slab_heads
    acum_rows = [pre.acum_of(first + k) for k in range(cfg.slab_heads)]
    over = acum_rows[0][:, :w]
    end = jnp.broadcast_to(pre.end[first:first + 1, :], (1, w))
    for k in range(1, cfg.slab_heads):
        over = jnp.where(masks[k], acum_rows[k][:, :w], over)
        end = jnp.where(masks[k], jnp.broadcast_to(
            pre.end[first + k:first + k + 1, :], (1, w)), end)
    return _Slab(acum_rows, jnp.exp(over), jnp.exp(end - over))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(cfg: _Cfg, x_ref, dt_ref, b_ref, c_ref, alog_ref, bias_ref,
                d_ref, expand_ref, y_ref, st_ref, h_ref):
    ci = pl.program_id(2)
    q, p, w = cfg.chunk, cfg.p, cfg.slab

    @pl.when(ci == 0)
    def _start():
        h_ref[...] = jnp.zeros_like(h_ref)

    pre = _prelude(cfg, ci, dt_ref, alog_ref, bias_ref, expand_ref)
    bm, cm = b_ref[0], c_ref[0]                               # (q, n)
    dtype = bm.dtype
    cb = _dot(cm, bm, _NT)                                    # (q, q)
    tri = _iota((q, q), 0) >= _iota((q, q), 1)
    masks = _head_masks(cfg, w)
    for slab in range(cfg.rep // cfg.slab_heads):
        at = slab * w
        xf = x_ref[0, :, at:at + w].astype(_F32)
        of = _slab(cfg, pre, slab, masks)
        # x.dt is rounded once for each product it enters, from f32 (as XLA
        # compiles the einsum form on the chip: it keeps the f32 product
        # between the two casts)
        xs = xf * pre.dt_over(at, w)
        xdt = xs.astype(dtype)
        xw = (xs * of.to_end).astype(dtype)
        h0 = h_ref[at:at + w, :]                              # (w, n)
        y = _dot(cm, h0.astype(dtype), _NT) * of.exp
        y = y + xf * d_ref[0, :, at:at + w]
        inside = []
        for k in range(cfg.slab_heads):
            r = slab * cfg.slab_heads + k
            m = (cb * _decay(of.acum_rows[k], pre, r, tri)).astype(dtype)
            inside.append(_dot(m, xdt))
            rows = slice(at + k * p, at + (k + 1) * p)
            st_ref[0, 0, 0, r] = h_ref[rows, :]
        y_ref[0, :, at:at + w] = (y + _by_head(masks, inside)).astype(
            y_ref.dtype)
        local = _dot(xw, bm, _TN)                             # (w, n)
        for k in range(cfg.slab_heads):
            r = slab * cfg.slab_heads + k
            rows = slice(at + k * p, at + (k + 1) * p)
            h_ref[rows, :] = (h_ref[rows, :] * pre.last_of(r, cfg.n)
                              + local[k * p:(k + 1) * p])


def _specs(cfg: _Cfg, groups, order):
    """Block specs of one chunk of x / B or C / dt / a (rep, 1) parameter /
    D over x's lanes / the expansion matrix / the chunk's state, with
    ``order`` mapping the grid's chunk index to the chunk visited."""
    q, rep, p, n = cfg.chunk, cfg.rep, cfg.p, cfg.n
    heads = groups * rep
    wide = pl.BlockSpec((1, q, rep * p), lambda b, g, c: (b, order(c), g))
    state = pl.BlockSpec((1, q, n), lambda b, g, c: (b, order(c), g))
    dt = pl.BlockSpec((1, q, heads), lambda b, g, c: (b, order(c), 0))
    param = pl.BlockSpec((1, rep, 1), lambda b, g, c: (g, 0, 0))
    skip = pl.BlockSpec((1, 1, rep * p), lambda b, g, c: (g, 0, 0))
    expand = pl.BlockSpec((_pieces_rows(cfg), rep * p + rep * q),
                          lambda b, g, c: (0, 0))
    starts = pl.BlockSpec((1, 1, 1, rep, p, n),
                          lambda b, g, c: (b, order(c), g, 0, 0, 0))
    return wide, state, dt, param, skip, expand, starts


def _params(cfg: _Cfg, groups, x, a_log, dt_bias, d):
    col = lambda v: v.astype(_F32).reshape(groups, cfg.rep, 1)
    skip = jnp.repeat(d.astype(_F32), cfg.p).reshape(groups, 1,
                                                     cfg.rep * cfg.p)
    exact = jnp.bfloat16 if x.dtype == jnp.bfloat16 else _F32
    return col(a_log), col(dt_bias), skip, _expander(cfg, exact)


_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# jitted so that a model's layers share one trace and one Mosaic lowering of
# each kernel, inside a step and in the eager forward that resolves shapes
@functools.partial(jax.jit, static_argnums=0)
def _fwd(cfg: _Cfg, x, dt, a_log, b, c, d, dt_bias):
    """x (B, L, H P), dt (B, L, H), b and c (B, L, G N), L whole chunks ->
    y like x, and the state at every chunk's start."""
    bsz, length, _ = x.shape
    groups = b.shape[2] // cfg.n
    nc = length // cfg.chunk
    wide, state, dts, param, skip, expand, starts = _specs(
        cfg, groups, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        name="mx_ssd_fwd",
        grid=(bsz, groups, nc),
        in_specs=[wide, dts, state, state, param, param, skip, expand],
        out_specs=[wide, starts],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bsz, nc, groups, cfg.rep, cfg.p, cfg.n),
                                 _F32),
        ],
        scratch_shapes=[pltpu.VMEM((cfg.rep * cfg.p, cfg.n), _F32)],
        compiler_params=_SEMANTICS,
        interpret=cfg.interpret,
    )(x, dt, b, c, *_params(cfg, groups, x, a_log, dt_bias, d))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _put_row(rows, at, v):
    return jnp.where(_iota((rows.shape[0], 1), 0) == at, v, rows)


def _head_rows(cfg: _Cfg, slab, v, dtype, pieces=2):
    """(rows, chunk) f32: row r the sum of the f32 ``v`` (chunk, slab width)
    over head r's lanes, for the heads of ``slab`` (0 elsewhere); rows is
    rep up to whole bf16 tiles.  Through the MXU, the sum in f32: with bf16
    operands v goes as ``pieces`` bf16 pieces (two keep sixteen bits of a
    term, twice what the products around it keep; three add up to it: the
    sums that meet in d acum cancel to a small part of their terms, so those
    terms go in whole), with f32 operands in the f32 passes."""
    rows = -(-cfg.rep // 16) * 16
    w = v.shape[1]
    pick = (_iota((rows, w), 0)
            == _iota((rows, w), 1) // cfg.p + slab * cfg.slab_heads)
    if dtype != jnp.bfloat16:
        return _dot(pick.astype(_F32), v, _NT, _HIGHEST)
    pick = pick.astype(dtype)
    out = 0.0
    for _ in range(pieces):
        piece = v.astype(dtype)
        out = out + _dot(pick, piece, _NT)
        v = v - piece.astype(_F32)
    return out


def _bwd_kernel(cfg: _Cfg, x_ref, dt_ref, b_ref, c_ref, alog_ref, bias_ref,
                d_ref, expand_ref, st_ref, dy_ref, dx_ref, db_ref, dc_ref,
                ddt_ref, da_ref, dd_ref, dh_ref):
    step = pl.program_id(2)
    ci = pl.num_programs(2) - 1 - step
    q, p, w, rep = cfg.chunk, cfg.p, cfg.slab, cfg.rep

    @pl.when(step == 0)
    def _start():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    pre = _prelude(cfg, ci, dt_ref, alog_ref, bias_ref, expand_ref)
    bm, cm = b_ref[0], c_ref[0]
    dtype = bm.dtype
    cb = _dot(cm, bm, _NT)
    tri = _iota((q, q), 0) >= _iota((q, q), 1)
    masks = _head_masks(cfg, w)

    dcb = jnp.zeros((q, q), _F32)
    dbm = jnp.zeros(bm.shape, _F32)
    dcm = jnp.zeros(cm.shape, _F32)
    # per-position sums a head, (rows, chunk): d acum but for what goes
    # into the state, that, dt's own, D's
    sum_acum = sum_end = sum_dt = sum_d = 0.0
    dlast = jnp.zeros((rep, 1), _F32)
    for slab in range(rep // cfg.slab_heads):
        at = slab * w
        xf = x_ref[0, :, at:at + w].astype(_F32)
        dy = dy_ref[0, :, at:at + w]
        dyf = dy.astype(_F32)
        of = _slab(cfg, pre, slab, masks)
        dtx = pre.dt_over(at, w)
        wx = of.to_end
        xs = xf * dtx
        xdt = xs.astype(dtype)
        xw = (xs * wx).astype(dtype)
        h0 = jnp.concatenate(
            [st_ref[0, 0, 0, slab * cfg.slab_heads + k]
             for k in range(cfg.slab_heads)], axis=0)         # (w, n)
        h0b = h0.astype(dtype)
        dh1 = dh_ref[at:at + w, :]
        dh1b = dh1.astype(dtype)

        # out of the carried state: y += (C h0^T) exp(acum)
        y = _dot(cm, h0b, _NT) * of.exp       # y less D's term, rebuilt
        dyeb = (dyf * of.exp).astype(dtype)
        dcm = dcm + _dot(dyeb, h0b)
        dh0 = _dot(dyeb, cm, _TN)                             # (w, n)
        # into the state: local = xw^T B
        to_state = _dot(bm, dh1b, _NT) * wx                   # (q, w)
        dbm = dbm + _dot(xw, dh1b)
        # inside the chunk, a head at a time
        inside, dinside = [], []
        for k, mask in enumerate(masks):
            r = slab * cfg.slab_heads + k
            decay = _decay(of.acum_rows[k], pre, r, tri)
            m = (cb * decay).astype(dtype)
            dcb = dcb + _dot(_only(mask, dy), xdt, _NT) * decay
            inside.append(_dot(m, xdt))
            dinside.append(_dot(m, dy, _TN))
            rows = slice(k * p, (k + 1) * p)
            dlast = _put_row(dlast, r, pre.last_of(r, 1)
                             * _sum_all(dh1[rows] * h0[rows]))
            dh_ref[at + k * p:at + (k + 1) * p, :] = (
                dh0[rows] + dh1[rows] * pre.last_of(r, cfg.n))
        y = y + _by_head(masks, inside)
        inside = _by_head(masks, dinside)
        dxdt = to_state + inside
        dx_ref[0, :, at:at + w] = (
            dxdt * dtx + dyf * d_ref[0, :, at:at + w]).astype(dx_ref.dtype)
        # every term of y_t carries exp(acum_t): d acum_t += dy_t . y_t;
        # every term that xdt_s feeds carries exp(-acum_s): d acum_s -=
        # dxdt_s . xdt_s.  What goes into the state is summed apart: the
        # chunk's end gets back exactly what its positions give up
        sum_acum = sum_acum + _head_rows(
            cfg, slab, dyf * y - inside * xdt.astype(_F32), dtype, pieces=3)
        sum_end = sum_end + _head_rows(cfg, slab, to_state * xs, dtype,
                                       pieces=3)
        sum_dt = sum_dt + _head_rows(cfg, slab, dxdt * xf, dtype)
        sum_d = sum_d + _head_rows(cfg, slab, dyf * xf, dtype)

    dcbb = dcb.astype(dtype)
    dc_ref[0] = (dcm + _dot(dcbb, bm)).astype(dc_ref.dtype)
    db_ref[0] = (dbm + _dot(dcbb, cm, _TN)).astype(db_ref.dtype)

    # back through the prefix sum
    sum_end = sum_end[:rep]
    dlast = dlast + jnp.sum(sum_end, axis=1, keepdims=True)
    dacum = sum_acum[:rep] - sum_end
    dacum = dacum + jnp.where(_iota((1, q), 1) == q - 1, dlast, 0.0)
    lower = (_iota((q, q), 0) >= _iota((q, q), 1)).astype(_F32)
    dla = _dot(dacum, lower, precision=_HIGHEST)              # sum over t >= s
    ddt = (sum_dt[:rep] + dla * pre.a) * jax.nn.sigmoid(pre.z)
    if pre.live is not None:
        ddt = jnp.where(pre.live, ddt, 0.0)
    ddt_ref[0, 0, 0] = ddt
    da_ref[0, 0, 0] = dla * pre.dt
    dd_ref[0, 0, 0] = sum_d[:rep]


@functools.partial(jax.jit, static_argnums=0)
def _bwd(cfg: _Cfg, x, dt, a_log, b, c, d, dt_bias, starts, dy):
    bsz, length, _ = x.shape
    groups = b.shape[2] // cfg.n
    nc = length // cfg.chunk
    wide, state, dts, param, skip, expand, start = _specs(
        cfg, groups, lambda c: nc - 1 - c)
    rows = pl.BlockSpec((1, 1, 1, cfg.rep, cfg.chunk),
                        lambda b, g, c: (b, nc - 1 - c, g, 0, 0))
    small = jax.ShapeDtypeStruct((bsz, nc, groups, cfg.rep, cfg.chunk), _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cfg),
        name="mx_ssd_bwd",
        grid=(bsz, groups, nc),
        in_specs=[wide, dts, state, state, param, param, skip, expand,
                  start, wide],
        out_specs=[wide, state, state, rows, rows, rows],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(b.shape, b.dtype),
            jax.ShapeDtypeStruct(c.shape, c.dtype),
            small, small, small,
        ],
        scratch_shapes=[pltpu.VMEM((cfg.rep * cfg.p, cfg.n), _F32)],
        compiler_params=_SEMANTICS,
        interpret=cfg.interpret,
    )(x, dt, b, c, *_params(cfg, groups, x, a_log, dt_bias, d), starts, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(cfg: _Cfg, x, dt, a_log, b, c, d, dt_bias):
    return _fwd(cfg, x, dt, a_log, b, c, d, dt_bias)[0]


def _scan_fwd(cfg: _Cfg, x, dt, a_log, b, c, d, dt_bias):
    # NOT named for ``ops/recompute.py``: a recomputed layer runs this a
    # second time.  At 2 x 8,192 positions that is 1.70 ms, and keeping y
    # (134 MB) and the f32 chunk states (268 MB) instead costs 0.40 GB a
    # layer: 4 ms saved a GB kept, where flash attention's results give 73
    # and a product with a weight 16 (PERF.md section 6, PR 32).
    y, starts = _fwd(cfg, x, dt, a_log, b, c, d, dt_bias)
    return y, (x, dt, a_log, b, c, d, dt_bias, starts)


def _scan_bwd(cfg: _Cfg, res, dy):
    x, dt, a_log, b, c, d, dt_bias, starts = res
    dx, db, dc, ddt, da, dd = _bwd(cfg, x, dt, a_log, b, c, d, dt_bias,
                                   starts, dy)
    # (B, chunks, G, rep, chunk) -> (B, L, H); the parameters' sums
    heads = a_log.shape[0]
    per_head = lambda v: jnp.sum(v, axis=(0, 1, 4)).reshape(heads)
    dbias = per_head(ddt)
    ddt = jnp.moveaxis(ddt, 4, 2).reshape(dt.shape)
    da_log = per_head(da) * -jnp.exp(a_log.astype(_F32))
    return (dx, ddt.astype(dt.dtype), da_log.astype(a_log.dtype), db, dc,
            per_head(dd).astype(d.dtype), dbias.astype(dt_bias.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _config(x, b, chunk) -> _Cfg:
    from . import interpret

    return _Cfg(int(chunk), x.shape[1], x.shape[2] // b.shape[2],
                x.shape[3], b.shape[3], interpret())


def _flat(cfg: _Cfg, x, dt, b, c):
    """The kernels' operands: the last two axes of x, b and c merged (a
    view), the length padded to whole chunks."""
    bsz, length = x.shape[:2]
    pad = (-length) % cfg.chunk
    flat = lambda v: jnp.pad(v.reshape(bsz, length, -1),
                             ((0, 0), (0, pad), (0, 0)))
    return flat(x), flat(dt), flat(b), flat(c)


def ssd_scan(x, dt, a_log, b, c, d, dt_bias, chunk=128):
    """``ops/ssm_ops.py`` ``ssd_scan`` as two Mosaic kernels (``mx_ssd_fwd``,
    ``mx_ssd_bwd``): x (B, L, H, P); dt (B, L, H) before its softplus;
    a_log, d, dt_bias (H,); b, c (B, L, G, N).  Returns y like x.  Lengths
    that are no multiple of ``chunk`` are padded (``dt = 0`` past the
    length, set in the kernel)."""
    cfg = _config(x, b, chunk)
    xs, dts, bs, cs = _flat(cfg, x, dt, b, c)
    y = _scan(cfg, xs, dts, a_log, bs, cs, d, dt_bias)
    return y[:, :x.shape[1]].reshape(x.shape)


def chunk_states(x, dt, a_log, b, c, dt_bias, chunk=128):
    """The state at the start of every chunk, (B, chunks, G, H / G, P, N)
    f32: what the forward leaves for the backward."""
    cfg = _config(x, b, chunk)
    xs, dts, bs, cs = _flat(cfg, x, dt, b, c)
    return _fwd(cfg, xs, dts, a_log, bs, cs, jnp.zeros_like(a_log),
                dt_bias)[1]
