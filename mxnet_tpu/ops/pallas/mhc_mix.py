"""A hyper-connected sublayer's stream mix in Pallas (TPU), forward and
backward: ``ops/hc_ops.py``'s three ops as kernels that hold a tile of
tokens' streams on chip.

Same equations as the jax form there (f32 sums, one rounding where it rounds
once, the same clamp, ``eps`` and iteration count).  Two ``custom_vjp``s
make a sublayer:

* ``coefficients_pre(X, gain, phi, a, b) -> (C, u, X)``.  Forward
  ``mx_mhc_coef`` (one pass over a tile of ``X``: the sum of squares, the ``n
  d`` by ``2 n + n^2`` product on the MXU with the norm's per-token factor
  taken out of it, the sigmoids, the clamp, ``exp`` and Sinkhorn's iterations
  with tokens on the LANES) then ``mx_mhc_pre`` (``u = sum_i H_pre[i]
  X_i``).  ``C`` and the product before its scalars, ``S = [r p | r]``, are
  named for ``ops/recompute.py``: a recomputed layer runs ``mx_mhc_pre``
  alone and Sinkhorn runs once a sublayer a step.  The third result is ``X``
  itself: ``post`` reads the streams through it, so that its cotangent, the
  ``H_res``-transposed part of ``gX``, arrives in this op's backward and is
  added to there, in the same pass, not by a fusion of XLA's over three
  stream tensors.
* ``post(X, y, C) -> X'`` (``mx_mhc_post``).  Its backward,
  ``mx_mhc_post_bwd``, writes ``gy``, that part of ``gX`` and the 20 sums a
  token (``dH_post``, ``dH_res``) as lane reductions of the tiles it holds.

``mx_mhc_coef_pre_bwd`` closes the sublayer: ``dH_pre`` from ``gu`` and the
resident tile, back through the sigmoids and through Sinkhorn (the iterates
made again on chip, never stored to HBM), through ``a``/``b``, the product
and the norm; it writes the whole ``gX``, the per-token ``dproj`` and the
sums over tokens for ``phi`` and the gain in ONE block that stays on chip
across the grid.  The chunk loops inside the kernels are ``fori_loop``s over
column slices, not unrolled code: a step of ten sublayers holds 65 calls,
each compiled into the program where it is called.

Per-token scalars live in two layouts: tokens on the lanes, ``(k, tile)``,
for everything 24 wide (a ``(tile, 24)`` block would fill 24 of 128 lanes
and Sinkhorn's 16 entries one lane each), and tokens on the sublanes,
``(tile, 1)`` columns spread along the lanes, where a row of the streams is
scaled.  The MXU moves between the two: a value's three bf16 pieces times the
identity, exactly.

VMEM: a call takes no more than it gets unasked (16 MB): what a call may
take, XLA cannot keep there across it (``ops/pallas/moe_rows.py``, PR 30).
The token tile of every kernel follows the streams' width.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
LANES = 128
#: columns of ``S``: the product before its scalars, then the norm's factor
S_WIDTH = 32
#: what a call's blocks and scratch may take of the 16 MB it gets unasked
VMEM_BUDGET = 15 * 2 ** 20
_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=16 * 2 ** 20)


class _Cfg(NamedTuple):
    n: int
    iters: int
    eps: float
    clamp_min: float
    clamp_max: float
    rms_eps: float
    interpret: bool

    @property
    def k(self) -> int:
        return 2 * self.n + self.n * self.n


# ---------------------------------------------------------------------------
# shapes and tiles
# ---------------------------------------------------------------------------
def _fixed(width, k):
    """Bytes of a call's operands that do not follow the tile: ``phi`` (as
    f32, the widest it comes) and the gain, two buffers each, rows padded to
    whole sublane tiles."""
    return 2 * width * (-(-k // 16) * 16 + 8) * 4


def _row_bytes(width, n, item):
    """Bytes a token of the tile takes in every kernel: two buffers a block
    and the coefficients' f32 scratch; ``mx_mhc_post_bwd`` half as much
    again, for what Mosaic puts on its stack there (20 MB at 64 tokens of 4
    x 3,584 bf16 columns, compiled for the chip)."""
    d = width // n
    return {
        "coef": 2 * width * item,
        "pre": 2 * (width + d) * item,
        "post": 2 * (2 * width + d) * item + 24 * LANES * 4,
        "post_bwd": 3 * (3 * width + 2 * d) * item + 48 * LANES * 4,
        "coef_pre_bwd": 2 * (3 * width + d) * item,
    }


def _tiles(tokens, width, n, k, item):
    """Tokens of a tile for every kernel: the most of 128 .. 16 that stay in
    the budget (at 4 x 3,584 bf16 columns: 128 for the forward pair, 64 for
    ``mx_mhc_post``, 32 for the two backward kernels), no more than the
    tokens rounded up to 16; None where even 16 do not fit."""
    fixed = _fixed(width, k)
    most = -(-tokens // 16) * 16
    out = {}
    for name, row in _row_bytes(width, n, item).items():
        base = fixed if name in ("coef", "coef_pre_bwd") else 0
        if name == "coef_pre_bwd":      # dphi and dgain blocks, f32
            base += 2 * width * (-(-k // 8) * 8 + 8) * 4
        tile = next((t for t in (128, 64, 32, 16)
                     if base + t * row <= VMEM_BUDGET), None)
        if tile is None:
            return None
        out[name] = min(tile, most)
    return out


def supported(streams, n, k=None) -> bool:
    """Shapes and types the compiled kernels take: bf16 or f32 streams, each
    a whole number of lane tiles wide, ``2 n + n^2`` no wider than ``S``'s
    columns leave, and a token tile that fits (any token count: the last
    tile is padded)."""
    width = streams.shape[-1]
    k = 2 * n + n * n if k is None else k
    if streams.dtype not in (jnp.bfloat16, jnp.float32) or streams.ndim < 2:
        return False
    if width % n or (width // n) % LANES or k >= S_WIDTH:
        return False
    tokens = int(np.prod(streams.shape[:-1]))
    item = streams.dtype.itemsize
    return tokens > 0 and _tiles(tokens, width, n, k, item) is not None


# ---------------------------------------------------------------------------
# pieces of the kernels
# ---------------------------------------------------------------------------
def _dot(a, b, dims=None):
    if dims is None:
        return jax.lax.dot(a, b, preferred_element_type=_F32)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _eye(size):
    rows = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    return (rows == cols).astype(jnp.bfloat16)


def _pieces(v):
    """f32 ``v`` as three bf16 arrays that add up to it exactly (3 x 8
    bits of mantissa)."""
    hi = v.astype(jnp.bfloat16)
    rest = v - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(_F32)).astype(jnp.bfloat16)


def _to_sublanes(eye, v):
    """(k, tile) -> (tile, k), exactly: each piece's product with the
    identity has one non-zero term, and the pieces add up in f32 without a
    rounding: three one-pass bf16 products where an f32 product at
    ``HIGHEST`` precision takes six."""
    return sum(_dot(eye, p, _NT) for p in _pieces(v))


def _to_lanes(eye, v):
    """(tile, k) -> (k, tile), exactly."""
    return sum(_dot(p, eye, _TN) for p in _pieces(v))


def _cols(at, width):
    """``width`` columns from the (traced) multiple of 128 ``at``."""
    return pl.ds(pl.multiple_of(at, LANES), width)


def _fold_lanes(v):
    """(tile, w) -> (tile, 128): the lane tiles of ``v`` added up."""
    return sum(v[:, q:q + LANES] for q in range(0, v.shape[1], LANES))


def _spread(col, width=LANES):
    return jnp.broadcast_to(col, (col.shape[0], width))


def _sinkhorn(cfg: _Cfg, m, kept_ref=None):
    """``m``: the matrix as ``n`` slabs ``(n, tile)``, slab ``i`` row ``j``
    the entry ``[i, j]`` of every token of the tile.  Rows over their sums,
    then columns, ``iters`` times, as ONE loop (traced and compiled once, not
    once an iteration).  ``kept_ref`` (2 iters + 1, n, n, tile) takes the
    start and every half step's result, for the way back."""
    def keep(at, m):
        if kept_ref is not None:
            for i, mi in enumerate(m):
                kept_ref[at, i] = mi

    def step(t, m):
        m = [mi / (jnp.sum(mi, axis=0, keepdims=True) + cfg.eps) for mi in m]
        keep(2 * t + 1, m)
        cols = sum(m) + cfg.eps
        m = [mi / cols for mi in m]
        keep(2 * t + 2, m)
        return tuple(m)

    keep(0, m)
    return list(jax.lax.fori_loop(0, cfg.iters, step, tuple(m)))


def _sinkhorn_bwd(cfg: _Cfg, kept_ref, dm):
    """The cotangent of the start (``exp`` of the clamped entries) from
    ``dm``, the cotangent of the last iterate.  For ``y = m / (sum_g m +
    eps)``: ``dm = (dy - sum_g(dy y)) / (sum_g m + eps)``."""
    at = lambda t: [kept_ref[t, i] for i in range(cfg.n)]

    def step(back, dm):
        t = cfg.iters - 1 - back
        start, rows, cols = at(2 * t), at(2 * t + 1), at(2 * t + 2)
        # columns: over the slabs
        inner = sum(d * y for d, y in zip(dm, cols))
        den = sum(rows) + cfg.eps
        dm = [(d - inner) / den for d in dm]
        # rows: over a slab's sublanes
        return tuple(
            (d - jnp.sum(d * y, axis=0, keepdims=True))
            / (jnp.sum(b, axis=0, keepdims=True) + cfg.eps)
            for d, y, b in zip(dm, rows, start))

    return list(jax.lax.fori_loop(0, cfg.iters, step, tuple(dm)))


def _activations(cfg: _Cfg, proj):
    """``proj`` (k, tile) -> ``H_pre``, ``H_post`` (n, tile) each, the
    clamped entries' ``exp`` as slabs and the clamp's mask as slabs."""
    n = cfg.n
    h_pre = jax.nn.sigmoid(proj[:n])
    h_post = 2 * jax.nn.sigmoid(proj[n:2 * n])
    raw = [proj[2 * n + i * n:2 * n + (i + 1) * n] for i in range(n)]
    start = [jnp.exp(jnp.clip(r, cfg.clamp_min, cfg.clamp_max)) for r in raw]
    inside = [((r >= cfg.clamp_min) & (r <= cfg.clamp_max)).astype(_F32)
              for r in raw]
    return h_pre, h_post, start, inside


def _rows(pieces, pad_to=None):
    out = jnp.concatenate(pieces, axis=0)
    if pad_to is not None and out.shape[0] < pad_to:
        out = jnp.concatenate(
            [out, jnp.zeros((pad_to - out.shape[0], out.shape[1]), _F32)], 0)
    return out


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
_CHUNK = 512          # columns of the streams worked at a time, at most


def _chunk(d):
    return next(c for c in (_CHUNK, 256, LANES) if d % c == 0)


def _coef_kernel(cfg: _Cfg, x_ref, gain_ref, phi_ref, scale_ref, b_ref,
                 c_ref, s_ref):
    tile, width = x_ref.shape
    chunk = _chunk(width)

    def one_chunk(j, carry):
        sq, prod = carry
        cols = _cols(j * chunk, chunk)
        x = x_ref[:, cols].astype(_F32)
        z = (x * gain_ref[:, cols]).astype(phi_ref.dtype)
        return (sq + _fold_lanes(x * x),
                prod + _dot(phi_ref[:, cols], z, _NT))

    sq, prod = jax.lax.fori_loop(
        0, width // chunk, one_chunk,
        (jnp.zeros((tile, LANES), _F32), jnp.zeros((cfg.k, tile), _F32)))
    eye = _eye(tile)
    r_col = jax.lax.rsqrt(jnp.sum(sq, axis=1, keepdims=True) / width
                          + cfg.rms_eps)                       # (tile, 1)
    r = _to_lanes(eye, r_col)                                  # (1, tile)
    proj0 = prod * r
    h_pre, h_post, start, _ = _activations(
        cfg, proj0 * scale_ref[...] + b_ref[...])
    c_ref[...] = _to_sublanes(eye, _rows([h_pre, h_post]
                                         + _sinkhorn(cfg, start)))
    s_ref[...] = _to_sublanes(eye, _rows([proj0, r], S_WIDTH))


def _pre_kernel(cfg: _Cfg, x_ref, c_ref, u_ref):
    d = u_ref.shape[1]
    h = [_spread(c_ref[:, i:i + 1]) for i in range(cfg.n)]

    def one_tile(j, carry):
        u = sum(h[i] * x_ref[:, _cols(i * d + j * LANES, LANES)].astype(_F32)
                for i in range(cfg.n))
        u_ref[:, _cols(j * LANES, LANES)] = u.astype(u_ref.dtype)
        return carry

    jax.lax.fori_loop(0, d // LANES, one_tile, 0)


def _post_kernel(cfg: _Cfg, x_ref, y_ref, c_ref, o_ref, h_ref):
    n, d = cfg.n, y_ref.shape[1]
    for k in range(n, cfg.k):
        h_ref[k - n] = _spread(c_ref[:, k:k + 1])

    def one_tile(t, carry):
        at = t * LANES
        y = y_ref[:, _cols(at, LANES)].astype(_F32)
        xs = [x_ref[:, _cols(j * d + at, LANES)].astype(_F32)
              for j in range(n)]
        for i in range(n):
            row = h_ref[i] * y
            for j in range(n):
                row = row + h_ref[n + i * n + j] * xs[j]
            o_ref[:, _cols(i * d + at, LANES)] = row.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, d // LANES, one_tile, 0)


def _block(tile, width):
    return pl.BlockSpec((None, tile, width), lambda i: (0, i, 0))


#: a parameter whole in VMEM before the call starts, not one more stream of
#: the call's pipeline (``_in_hbm`` says why the streams are kept few)
_RESIDENT = pl.BlockSpec(memory_space=pltpu.VMEM)


def _whole(rows, width):
    return pl.BlockSpec((rows, width), lambda i: (0, 0))


def _result(cfg: _Cfg, shape, dtype):
    """A result's shape, held to HBM where Mosaic compiles the call
    (``_in_hbm``; the interpreter takes no memory space)."""
    if cfg.interpret:
        return jax.ShapeDtypeStruct(shape, dtype)
    return pltpu.HBM(shape, dtype)


def _in_hbm(cfg: _Cfg, *operands):
    """The operands that stream through a call, held to HBM: left to itself
    XLA keeps what fits on chip between ops (a whole stream tensor is
    exactly the 112 MiB a 16 MiB call leaves of VMEM) and a call then reads
    it in place.  With that, and with thirteen operands and results
    streaming through ``mx_mhc_coef_pre_bwd``, the Xing cell's step hung on
    the chip in its first step while two layers of it and every kernel
    alone ran; with seven streams (``_RESIDENT``) and these held to HBM it
    runs.  The constraint alone did not cure it and was not tried without
    (PERF.md section 6, PR 34)."""
    if cfg.interpret:
        return operands
    return tuple(pltpu.with_memory_space_constraint(v, pltpu.HBM)
                 for v in operands)


def _leaves(cfg: _Cfg, gain, phi, a, b):
    """The parameters as the kernels take them: the gain (1, n d) f32,
    ``phi`` as it is, ``a``'s three scalars over their rows and ``b``, (k,
    1) f32 each."""
    n = cfg.n
    a = a.astype(_F32)
    scale = jnp.concatenate([jnp.broadcast_to(a[i], (rows, 1))
                             for i, rows in enumerate((n, n, n * n))])
    return (gain.astype(_F32).reshape(1, -1), phi, scale,
            b.astype(_F32).reshape(cfg.k, 1))


# jitted so that a model's sublayers share one trace and one Mosaic lowering
# of each kernel (PR 28: re-tracing a kernel for every layer cost 5 s of
# ``setup_s``)
@functools.partial(jax.jit, static_argnums=(0, 1))
def _coef(cfg: _Cfg, tile, x, gain, phi, a, b):
    """x (1, tokens, n d), tokens whole tiles -> C (1, tokens, k), S (1,
    tokens, 32), both f32."""
    _, tokens, width = x.shape
    return pl.pallas_call(
        functools.partial(_coef_kernel, cfg),
        name="mx_mhc_coef",
        grid=(tokens // tile,),
        in_specs=[_block(tile, width)] + [_RESIDENT] * 4,
        out_specs=[_block(tile, cfg.k), _block(tile, S_WIDTH)],
        out_shape=[_result(cfg, (1, tokens, cfg.k), _F32),
                   _result(cfg, (1, tokens, S_WIDTH), _F32)],
        compiler_params=_PARAMS,
        interpret=cfg.interpret,
    )(*_in_hbm(cfg, x), *_leaves(cfg, gain, phi, a, b))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pre(cfg: _Cfg, tile, x, c):
    _, tokens, width = x.shape
    d = width // cfg.n
    return pl.pallas_call(
        functools.partial(_pre_kernel, cfg),
        name="mx_mhc_pre",
        grid=(tokens // tile,),
        in_specs=[_block(tile, width), _block(tile, cfg.k)],
        out_specs=_block(tile, d),
        out_shape=_result(cfg, (1, tokens, d), x.dtype),
        compiler_params=_PARAMS,
        interpret=cfg.interpret,
    )(*_in_hbm(cfg, x, c))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _post(cfg: _Cfg, tile, x, y, c):
    _, tokens, width = x.shape
    d = width // cfg.n
    return pl.pallas_call(
        functools.partial(_post_kernel, cfg),
        name="mx_mhc_post",
        grid=(tokens // tile,),
        in_specs=[_block(tile, width), _block(tile, d), _block(tile, cfg.k)],
        out_specs=_block(tile, width),
        out_shape=_result(cfg, (1, tokens, width), x.dtype),
        scratch_shapes=[pltpu.VMEM((cfg.k - cfg.n, tile, LANES), _F32)],
        compiler_params=_PARAMS,
        interpret=cfg.interpret,
    )(*_in_hbm(cfg, x, y, c))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _post_bwd_kernel(cfg: _Cfg, g_ref, x_ref, y_ref, c_ref, gy_ref, gx_ref,
                     dc_ref, h_ref, acc_ref):
    n, d = cfg.n, y_ref.shape[1]
    for k in range(n, cfg.k):
        h_ref[k - n] = _spread(c_ref[:, k:k + 1])
    acc_ref[...] = jnp.zeros(acc_ref.shape, _F32)

    def one_tile(t, carry):
        at = t * LANES
        y = y_ref[:, _cols(at, LANES)].astype(_F32)
        gs = [g_ref[:, _cols(i * d + at, LANES)].astype(_F32)
              for i in range(n)]
        gy = sum(h_ref[i] * gs[i] for i in range(n))
        gy_ref[:, _cols(at, LANES)] = gy.astype(gy_ref.dtype)
        for i in range(n):
            acc_ref[i] += gs[i] * y
        for j in range(n):
            x = x_ref[:, _cols(j * d + at, LANES)].astype(_F32)
            gx = sum(h_ref[n + i * n + j] * gs[i] for i in range(n))
            gx_ref[:, _cols(j * d + at, LANES)] = gx.astype(gx_ref.dtype)
            for i in range(n):
                acc_ref[n + i * n + j] += gs[i] * x
        return carry

    jax.lax.fori_loop(0, d // LANES, one_tile, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, dc_ref.shape, 1)
    dc = jnp.zeros(dc_ref.shape, _F32)
    for k in range(n, cfg.k):
        dc = jnp.where(lane == k,
                       jnp.sum(acc_ref[k - n], axis=1, keepdims=True), dc)
    dc_ref[...] = dc


@functools.partial(jax.jit, static_argnums=(0, 1))
def _post_bwd(cfg: _Cfg, tile, g, x, y, c):
    """-> gy, the ``H_res``-transposed part of gX, dC (1, tokens, k) f32
    with zeros where ``H_pre`` is."""
    _, tokens, width = x.shape
    d = width // cfg.n
    sums = pltpu.VMEM((cfg.k - cfg.n, tile, LANES), _F32)
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, cfg),
        name="mx_mhc_post_bwd",
        grid=(tokens // tile,),
        in_specs=[_block(tile, width), _block(tile, width), _block(tile, d),
                  _block(tile, cfg.k)],
        out_specs=[_block(tile, d), _block(tile, width),
                   _block(tile, cfg.k)],
        out_shape=[_result(cfg, (1, tokens, d), y.dtype),
                   _result(cfg, (1, tokens, width), x.dtype),
                   _result(cfg, (1, tokens, cfg.k), _F32)],
        scratch_shapes=[sums, sums],
        compiler_params=_PARAMS,
        interpret=cfg.interpret,
    )(*_in_hbm(cfg, g, x, y, c))


def _coef_pre_bwd_kernel(cfg: _Cfg, gu_ref, x_ref, sg_ref, gxp_ref,
                         gain_ref, phi_ref, scale_ref, b_ref, gx_ref, dp_ref,
                         sums_ref, kept_ref):
    n, k = cfg.n, cfg.k
    dphi_ref, dgain_ref = sums_ref.at[:k], sums_ref.at[k:]
    tile, width = x_ref.shape
    d = width // n
    chunk = _chunk(d)

    @pl.when(pl.program_id(0) == 0)
    def _():
        sums_ref[...] = jnp.zeros(sums_ref.shape, _F32)

    # dH_pre[i] = sum_c gu X_i, beside what came for C from elsewhere
    def sums(j, acc):
        gu = gu_ref[:, _cols(j * chunk, chunk)].astype(_F32)
        return tuple(
            acc[i] + _fold_lanes(gu * x_ref[
                :, _cols(i * d + j * chunk, chunk)].astype(_F32))
            for i in range(n))

    acc = jax.lax.fori_loop(
        0, d // chunk, sums,
        tuple(jnp.zeros((tile, LANES), _F32) for _ in range(n)))
    lane = jax.lax.broadcasted_iota(jnp.int32, sg_ref.shape, 1)
    sg = sg_ref[...]                        # [S | gC], (tile, 32 + k)
    for i in range(n):
        sg = sg + jnp.where(lane == S_WIDTH + i,
                            jnp.sum(acc[i], axis=1, keepdims=True), 0.0)

    # tokens on the lanes: back through the sigmoids, Sinkhorn, a and b
    eye = _eye(tile)
    sg = _to_lanes(eye, sg)                                    # (32 + k, tile)
    proj0, r, dc = sg[:k], sg[k:k + 1], sg[S_WIDTH:]
    h_pre, h_post, start, inside = _activations(
        cfg, proj0 * scale_ref[...] + b_ref[...])
    _sinkhorn(cfg, start, kept_ref)
    dm = _sinkhorn_bwd(cfg, kept_ref, [dc[2 * n + i * n:2 * n + (i + 1) * n]
                                       for i in range(n)])
    dproj = _rows([dc[:n] * h_pre * (1 - h_pre),
                   dc[n:2 * n] * h_post * (1 - 0.5 * h_post)]
                  + [g * m * w for g, m, w in zip(dm, start, inside)])
    dproj0 = dproj * scale_ref[...]
    # proj0 = r p: dp = r dproj0; r = (mean x^2 + eps)^-1/2 gives x the
    # factor -(sum_k dproj0 proj0) r^2 / width
    to_x = -jnp.sum(dproj0 * proj0, axis=0, keepdims=True) * r * r / width
    dp = dproj0 * r
    cols = _to_sublanes(eye, _rows([dp, h_pre, to_x], S_WIDTH))
    dp_ref[...] = _to_sublanes(eye, dproj)
    dp_t = cols[:, :k].astype(phi_ref.dtype)                   # (tile, k)
    dp_l = dp.astype(phi_ref.dtype)                            # (k, tile)
    h = [_spread(cols[:, k + i:k + i + 1], chunk) for i in range(n)]
    to_x = _spread(cols[:, k + n:k + n + 1], chunk)

    def one_chunk(j, carry):
        gu = gu_ref[:, _cols(j * chunk, chunk)].astype(_F32)
        for i in range(n):
            at_i = _cols(i * d + j * chunk, chunk)
            x = x_ref[:, at_i].astype(_F32)
            gain = gain_ref[:, at_i]
            dz = _dot(dp_t, phi_ref[:, at_i])                  # (tile, chunk)
            gx = (gxp_ref[:, at_i].astype(_F32) + h[i] * gu + gain * dz
                  + to_x * x)
            gx_ref[:, at_i] = gx.astype(gx_ref.dtype)
            dgain_ref[:, at_i] += (dz * x).reshape(
                tile // 8, 8, chunk).sum(0)
            dphi_ref[:, at_i] += _dot(dp_l,
                                      (x * gain).astype(phi_ref.dtype))
        return carry

    jax.lax.fori_loop(0, d // chunk, one_chunk, 0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _coef_pre_bwd(cfg: _Cfg, tile, gu, x, s, gc, gxp, gain, phi, a, b):
    """-> gX (whole), dproj (1, tokens, k) f32, and (k + 8, n d) f32: dphi,
    then the gain's gradient as 8 partial rows.  Four operands and three
    results stream through the call; the parameters are resident."""
    _, tokens, width = x.shape
    d = width // cfg.n
    return pl.pallas_call(
        functools.partial(_coef_pre_bwd_kernel, cfg),
        name="mx_mhc_coef_pre_bwd",
        grid=(tokens // tile,),
        in_specs=[_block(tile, d), _block(tile, width),
                  _block(tile, S_WIDTH + cfg.k), _block(tile, width)]
        + [_RESIDENT] * 4,
        out_specs=[_block(tile, width), _block(tile, cfg.k),
                   _whole(cfg.k + 8, width)],
        out_shape=[_result(cfg, (1, tokens, width), x.dtype),
                   _result(cfg, (1, tokens, cfg.k), _F32),
                   _result(cfg, (cfg.k + 8, width), _F32)],
        scratch_shapes=[pltpu.VMEM((2 * cfg.iters + 1, cfg.n, cfg.n, tile),
                                   _F32)],
        compiler_params=_PARAMS,
        interpret=cfg.interpret,
    )(*_in_hbm(cfg, gu, x, jnp.concatenate([s, gc], -1), gxp),
      *_leaves(cfg, gain, phi, a, b))


# ---------------------------------------------------------------------------
# the two differentiable ops of a sublayer
# ---------------------------------------------------------------------------
def _padded(tile, *arrays):
    """Each (1, tokens, w) array with its tokens padded to whole tiles."""
    pad = (-arrays[0].shape[1]) % tile
    if not pad:
        return arrays
    return tuple(jnp.pad(v, ((0, 0), (0, pad), (0, 0))) for v in arrays)


def _tiles_of(cfg: _Cfg, x):
    return _tiles(x.shape[1], x.shape[2], cfg.n, cfg.k, x.dtype.itemsize)


def _forward(cfg: _Cfg, x, gain, phi, a, b):
    from ..recompute import kernel_out

    tiles, tokens = _tiles_of(cfg, x), x.shape[1]
    (xp,) = _padded(tiles["coef"], x)
    c, s = _coef(cfg, tiles["coef"], xp, gain, phi, a, b)
    # both kept by a recomputed layer (0.39 + 0.52 MB a sublayer at 4,096
    # tokens): its second forward then runs ``mx_mhc_pre`` alone
    c, s = kernel_out(c[:, :tokens], s[:, :tokens])
    xp, cp = _padded(tiles["pre"], x, c)
    u = _pre(cfg, tiles["pre"], xp, cp)[:, :tokens]
    return c, s, u


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def coefficients_pre(cfg: _Cfg, x, gain, phi, a, b):
    """x (1, tokens, n d) -> C (1, tokens, k) f32, u (1, tokens, d) and x
    again, for ``post`` to read the streams through."""
    c, _, u = _forward(cfg, x, gain, phi, a, b)
    return c, u, x


def _coefficients_pre_fwd(cfg: _Cfg, x, gain, phi, a, b):
    c, s, u = _forward(cfg, x, gain, phi, a, b)
    return (c, u, x), (x, s, gain, phi, a, b)


def _coefficients_pre_bwd(cfg: _Cfg, res, cts):
    x, s, gain, phi, a, b = res
    gc, gu, gxp = cts
    tile, tokens = _tiles_of(cfg, x)["coef_pre_bwd"], x.shape[1]
    gx, dproj, sums = _coef_pre_bwd(
        cfg, tile, *_padded(tile, gu, x, s, gc, gxp), gain, phi, a, b)
    n = cfg.n
    dphi, dgain = sums[:cfg.k], sums[cfg.k:]
    dproj = dproj[0, :tokens]
    by_row = jnp.sum(dproj * s[0, :, :cfg.k], axis=0)
    da = jnp.stack([by_row[:n].sum(), by_row[n:2 * n].sum(),
                    by_row[2 * n:].sum()])
    return (gx[:, :tokens], dgain.sum(0).astype(gain.dtype),
            dphi.astype(phi.dtype), da.astype(a.dtype),
            dproj.sum(0).astype(b.dtype))


coefficients_pre.defvjp(_coefficients_pre_fwd, _coefficients_pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def post(cfg: _Cfg, x, y, c):
    """x (1, tokens, n d), y (1, tokens, d), C (1, tokens, k) -> X'."""
    tile, tokens = _tiles_of(cfg, x)["post"], x.shape[1]
    return _post(cfg, tile, *_padded(tile, x, y, c))[:, :tokens]


def _post_fwd(cfg: _Cfg, x, y, c):
    # X' is NOT named for ``ops/recompute.py``: 117 MB a sublayer
    return post(cfg, x, y, c), (x, y, c)


def _post_vjp(cfg: _Cfg, res, g):
    x, y, c = res
    tile, tokens = _tiles_of(cfg, x)["post_bwd"], x.shape[1]
    gy, gx, dc = _post_bwd(cfg, tile, *_padded(tile, g, x, y, c))
    return gx[:, :tokens], gy[:, :tokens], dc[:, :tokens]


post.defvjp(_post_fwd, _post_vjp)


def config(n, iters=0, eps=0.0, clamp_min=0.0, clamp_max=0.0,
           rms_eps=0.0) -> _Cfg:
    """``post`` reads ``n`` alone."""
    from . import interpret

    return _Cfg(int(n), int(iters), float(eps), float(clamp_min),
                float(clamp_max), float(rms_eps), interpret())
