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

Block diffusion (``block_diffusion=b``): the stream is a noisy copy and a
clean copy of one sequence side by side, ``[x_t | x_0]``, 2L positions.
Position ``i`` has stream ``s = i >= L``, position ``p = i - s L`` and block
``j = p // b``; a noisy query sees its own block of the noisy copy and the
clean copy's earlier blocks, a clean query the clean copy's blocks up to its
own (``bd_allowed``).  For a query tile the key tiles it leaves whole run
unmasked, those it empties are not visited (``_k_segments``,
``_q_segments``).  Where the copies meet on a tile boundary (L a multiple
of both tiles) a masked run holds one copy, and a tile's mask compares its
row index with one threshold a column, made from the column's block index
(``_bd_mask``, ``_bd_mask_t``): one compare a score, two for a noisy block's
range.  Otherwise a tile may hold both copies or padding and takes the
general form, a vector of its rows' block indices against one of its
columns' (``_bd_general``).  Rows past 2L (padding) count as clean queries
of later blocks.

Selection (``sparse_flash_attention``): each query attends to the keys a
selection outside the kernel chose for it (a learned indexer's top-k,
``ops/dsa_ops.py``), one selection for all heads of a batch row, handed as
packed bits.  The loops visit every causal tile and mask each by its bits
(``_unpack``).  Words are laid out so that a tile's mask needs no lane
shuffle: in ``bits`` (B, Lq/32, Lk) query row ``r = 256 a + 8 j + s`` of a
key column is bit ``j`` of word row ``8 a + s``; ``bits_t`` (B, Lk/32, Lq) is
the same of the transposed selection (key rows, for the dkv kernel).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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
    bd_block: int = 0  # block length of a block-diffusion stream (0: none)
    select: int = 0  # query heads a batch row under a selection (0: none)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(length: int, preferred: int) -> int:
    if length >= preferred:
        return preferred
    return _round_up(length, 8)


def bd_allowed(half, block, qpos, kpos):
    """The block-diffusion predicate over a stream of ``2 half`` positions
    (module docstring), elementwise over integer positions; ``block`` is a
    power of two.  Clean keys: ``j_k < j_q + s_q``; noisy keys: a noisy
    query of the same block (logical ops and integer selects only: Mosaic
    does not select between boolean vectors)."""
    shift = block.bit_length() - 1
    sq, sk = qpos >= half, kpos >= half
    jq = jnp.right_shift(qpos - jnp.where(sq, half, 0), shift)
    jk = jnp.right_shift(kpos - jnp.where(sk, half, 0), shift)
    clean = jnp.logical_and(sk, jk < jq + jnp.where(sq, 1, 0))
    noisy = jnp.logical_and(jnp.logical_not(jnp.logical_or(sq, sk)),
                            jk == jq)
    return jnp.logical_or(clean, noisy)


def check_block_diffusion(lq, lk, block, causal):
    """Refuse a block-diffusion call the mask does not describe."""
    if (causal or lq != lk or lq % 2 or (lq // 2) % block
            or block & (block - 1)):
        raise ValueError(
            f"block_diffusion={block}: needs one stream of 2L positions "
            f"(got {lq} queries over {lk} keys), L a multiple of the block, "
            f"the block a power of two, and no causal mask")


#: rows a group of packed words covers: 8 sublanes of 32 bits
SELECT_GROUP = 256
#: scoped VMEM of a call under a selection: K and V of a whole row (4 MB each
#: at 16,384 positions) and a row of words, each double-buffered
_SELECT_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2 ** 20)


def select_block(length):
    """The tile of a call under a selection: 512, or 256 for a short one
    (a tile's rows are whole groups of packed words)."""
    return 512 if length >= 512 else SELECT_GROUP


def check_selection(q_shape, k_shape, bits, bits_t):
    """Refuse a call under a selection whose bits do not describe it:
    self-attention of (B, H, L, D) queries over (B, Hkv, L, D) keys, and
    int32 words ``bits`` (B, Lp/32, Lp), ``bits_t`` the same, Lp the length
    padded to the tile (``select_block``)."""
    b, _h, lq = q_shape[:3]
    lk = k_shape[2]
    lp = _round_up(lq, select_block(lq))
    want = (b, lp // 32, lp)
    for name, x in (("bits", bits), ("bits_t", bits_t)):
        if tuple(x.shape) != want or x.dtype != jnp.int32:
            raise ValueError(
                f"selection: {name} {tuple(x.shape)} {x.dtype}, want int32 "
                f"{want} for {lq} queries")
    if lk != lq or len(q_shape) != 4 or k_shape[0] != b:
        raise ValueError(f"selection: self-attention of (B, H, L, D) only, "
                         f"got queries {tuple(q_shape)}, keys {tuple(k_shape)}")


def _unpack(words, rows):
    """The (rows, C) bool mask of a tile from its (rows / 32, C) words: row
    ``256 a + 8 j + s`` is bit ``j`` of word row ``8 a + s`` (sublane groups
    repeated, one shift a score)."""
    c = words.shape[-1]
    g = rows // SELECT_GROUP
    w = jnp.broadcast_to(words.reshape(g, 1, 8, c), (g, 32, 8, c))
    shift = jnp.right_shift(
        jax.lax.broadcasted_iota(jnp.int32, (rows, c), 0), 3) & 31
    return (jnp.right_shift(w.reshape(rows, c), shift) & 1) != 0


def _kv_mask(cfg: _Cfg, qi, kj, bq, bk):
    """Validity mask for a (bq, bk) score tile at q block qi / k block kj
    (full or causal)."""
    kpos = kj * cfg.block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < cfg.kv_len
    if cfg.causal:
        qpos = qi * cfg.block_q + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bk), 0)
        mask = jnp.logical_and(mask, qpos >= kpos)
    return mask


# A masked run of the block-diffusion tiles is "noisy" or "clean" when all
# its keys (in the dkv kernel: its query rows) are of that copy, True when
# its tiles may hold both copies or padding.
def _bd_aligned(cfg: _Cfg):
    half = cfg.q_len // 2
    return half % cfg.block_q == 0 and half % cfg.block_k == 0


def _bd_rows(cfg: _Cfg, qpos):
    """(bound, noisy) of query positions: a clean key of block j is allowed
    where ``j < bound``, a noisy key where ``j == noisy`` (-1 for a clean
    row, which no noisy key matches)."""
    half, shift = cfg.q_len // 2, cfg.bd_block.bit_length() - 1
    sq = qpos >= half
    jq = jnp.right_shift(qpos - jnp.where(sq, half, 0), shift)
    return jq + jnp.where(sq, 1, 0), jnp.where(sq, -1, jq)


def _bd_general(cfg: _Cfg, qpos, kpos):
    """The mask from a vector of query positions and one of key positions
    (broadcast against each other), exact for any tile: both compares, with
    sentinels that fail them for the other copy's keys and for padding."""
    half, shift = cfg.q_len // 2, cfg.bd_block.bit_length() - 1
    bound, noisy = _bd_rows(cfg, qpos)
    sk = kpos >= half
    jk = jnp.right_shift(kpos - jnp.where(sk, half, 0), shift)
    clean = jnp.where(jnp.logical_and(sk, kpos < cfg.kv_len), jk, 1 << 30)
    return jnp.logical_or(clean < bound, jnp.where(sk, -2, jk) == noisy)


def _bd_mask(cfg: _Cfg, qi, kj, copy):
    """The block-diffusion mask of the forward's and dq's (bq, bk) tile at
    query block qi, key block kj.  For a run of one key copy (the query tile
    is then of one copy too) the row index r is compared with a threshold a
    key: clean keys ``r >= first`` (``j_k < j_q + s_q``), noisy keys ``first
    <= r < first + b`` (a noisy row of the key's block)."""
    bq, bk = cfg.block_q, cfg.block_k
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    if copy is True:
        return _bd_general(cfg, qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, 1), 0), kpos)
    half, b = cfg.q_len // 2, cfg.bd_block
    shift = b.bit_length() - 1
    sq = (qi * bq >= half).astype(jnp.int32)
    p0 = qi * bq - sq * half            # the tile's first row in its copy
    r = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    if copy == "clean":
        jk = jnp.right_shift(kpos - half, shift)
        return r >= jnp.left_shift(jk - sq + 1, shift) - p0
    first = jnp.left_shift(jnp.right_shift(kpos, shift), shift) - p0
    return jnp.logical_and(r >= jnp.maximum(first, sq * bq), r < first + b)


def _bd_mask_t(cfg: _Cfg, kj, qi, copy):
    """The same for the dkv kernel's transposed (bk, bq) tile; a run names
    its ROWS' copy, and the key row index r is compared with a threshold a
    query.  Clean rows see clean keys only: ``r < (bound << shift) - (k0 -
    half)``; noisy rows take the key block's copy, a noisy block the rows of
    its block, a clean one the rows of later blocks."""
    bq, bk = cfg.block_q, cfg.block_k
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (1, bq), 1)
    if copy is True:
        return _bd_general(cfg, qpos, kj * bk + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0))
    half, b = cfg.q_len // 2, cfg.bd_block
    shift = b.bit_length() - 1
    k0 = kj * bk
    bound, noisy = _bd_rows(cfg, qpos)
    r = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    clean_end = jnp.left_shift(bound, shift) - (k0 - half)
    if copy == "clean":
        return r < clean_end
    nk = (k0 < half).astype(jnp.int32)  # the key block is noisy
    first = nk * (jnp.left_shift(noisy, shift) - k0)
    end = clean_end + nk * (first + b - clean_end)
    return jnp.logical_and(r >= first, r < end)


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


def _segments(reach, whole, blk, xp, start=0):
    """The tiles of ``blk`` positions that the position range ``reach`` (lo,
    hi) touches, from tile ``start`` on, as (first, last + 1, masked) runs:
    masked, then those inside ``whole`` (every pair allowed), then masked
    -> (runs, the tile after the last)."""
    lo, hi = reach
    a = xp.maximum(lo // blk, start)
    c = xp.maximum(xp.where(hi > lo, (hi + blk - 1) // blk, 0), a)
    w0 = xp.clip((whole[0] + blk - 1) // blk, a, c)
    w1 = xp.clip(whole[1] // blk, w0, c)
    return [(a, w0, True), (w0, w1, False), (w1, c, True)], c


def _bd_runs(noisy, noisy_whole, clean, clean_whole, blk, xp, aligned):
    runs, end = _segments(noisy, noisy_whole, blk, xp)
    more, _ = _segments(clean, clean_whole, blk, xp, start=end)
    if aligned:  # no tile holds both copies: a run's copy is known here
        runs = [(a, c, m and "noisy") for a, c, m in runs]
        more = [(a, c, m and "clean") for a, c, m in more]
    return runs + more


def _k_segments(cfg: _Cfg, qi, nkb, xp=jnp):
    """(first, last + 1, masked) runs of the key blocks query block qi
    visits: ``_k_blocks``' two for a full or causal mask; under block
    diffusion the noisy copy's blocks its rows share, then the clean copy's
    earlier blocks, each split into masked and whole runs (a masked run
    names its keys' copy where the copies meet on a tile boundary)."""
    if cfg.select:
        return [(0, _k_blocks(cfg, qi, nkb)[1], "select")]
    if not cfg.bd_block:
        whole, reached = _k_blocks(cfg, qi, nkb)
        return [(0, whole, False), (whole, reached, True)]
    half, b = cfg.q_len // 2, cfg.bd_block
    q0 = qi * cfg.block_q
    q1 = q0 + cfg.block_q
    has_n, has_c = q0 < half, q1 > half
    jlo, jhi = q0 // b, (xp.minimum(q1, half) - 1) // b
    jlo_c = (xp.maximum(q0, half) - half) // b
    jhi_c = (q1 - 1 - half) // b
    noisy = (xp.where(has_n, jlo * b, 0),
             xp.where(has_n, xp.minimum((jhi + 1) * b, half), 0))
    # noisy keys are whole only to noisy rows of one block
    one = xp.logical_and(has_n, xp.logical_and(xp.logical_not(has_c),
                                               jlo == jhi))
    noisy_whole = (xp.where(one, jlo * b, 0),
                   xp.where(one, xp.minimum((jlo + 1) * b, half), 0))
    reach = xp.maximum(xp.where(has_n, jhi * b, 0), xp.where(
        has_c, xp.minimum((jhi_c + 1) * b, half), 0))
    width = xp.minimum(xp.where(has_n, jlo * b, half), xp.where(
        has_c, xp.minimum((jlo_c + 1) * b, half), half))
    return _bd_runs(noisy, noisy_whole, (half, half + reach),
                    (half, half + width), cfg.block_k, xp, _bd_aligned(cfg))


def _q_segments(cfg: _Cfg, kj, nqb, xp=jnp):
    """(first, last + 1, masked) runs of the query blocks that key block kj
    reaches under block diffusion (the dkv kernel's loops): the noisy rows
    that see its keys, then the clean rows; a key block that holds padding
    or both copies is masked throughout.  A masked run names its rows' copy
    where the copies meet on a tile boundary."""
    bq, bk = cfg.block_q, cfg.block_k
    half, b = cfg.q_len // 2, cfg.bd_block
    k0 = kj * bk
    k1 = xp.minimum(k0 + bk, cfg.kv_len)
    has_n, has_c = k0 < half, k1 > half
    jlo, jhi = k0 // b, (xp.minimum(k1, half) - 1) // b
    jlo_c = (xp.maximum(k0, half) - half) // b
    jhi_c = (k1 - 1 - half) // b
    noisy = (xp.minimum(xp.where(has_n, jlo * b, half), xp.where(
                 has_c, xp.minimum((jlo_c + 1) * b, half), half)),
             xp.maximum(xp.where(has_n, xp.minimum((jhi + 1) * b, half), 0),
                        xp.where(has_c, half, 0)))
    clean = (xp.where(has_c, half + jlo_c * b, 0),
             xp.where(has_c, nqb * bq, 0))
    # whole runs only for a key block of one copy and no padding: noisy
    # keys to the noisy rows of their one block, clean keys to the noisy
    # rows of later blocks and the clean rows of their last block on
    pure = k0 + bk <= cfg.kv_len
    one = xp.logical_and(pure, xp.logical_and(
        xp.logical_not(has_c), jlo == jhi))
    tail = xp.logical_and(pure, xp.logical_not(has_n))
    noisy_whole = (
        xp.where(one, jlo * b, xp.where(tail, xp.minimum((jhi_c + 1) * b,
                                                         half), 0)),
        xp.where(one, xp.minimum((jlo + 1) * b, half),
                 xp.where(tail, half, 0)))
    clean_whole = (xp.where(tail, half + jhi_c * b, 0),
                   xp.where(tail, nqb * bq, 0))
    return _bd_runs(noisy, noisy_whole, clean, clean_whole, bq, xp,
                    _bd_aligned(cfg))


def bd_tiles(length, block, block_q=512, block_k=512):
    """How the kernels visit the tiles of a block-diffusion stream of
    ``length`` (2L) positions: {"visited", "whole", "masked", "square",
    "per_copy", "general"} of the forward's and ``dq``'s (query block, key
    block) tiles, counted from the same runs the kernels loop over (numpy,
    no trace); ``masked`` = ``per_copy`` (keys of one copy: one compare a
    score) + ``general`` (both compares)."""
    import numpy as np

    bq, bk = _pick_block(length, block_q), _pick_block(length, block_k)
    lp_q, lp_k = _round_up(length, bq), _round_up(length, bk)
    cfg = _Cfg(False, 1.0, bq, bk, length, length, True, 1, block)
    nqb, nkb = lp_q // bq, lp_k // bk
    n = {False: 0, True: 0, "noisy": 0, "clean": 0}
    for qi in range(nqb):
        for lo, hi, m in _k_segments(cfg, np.int64(qi), nkb, xp=np):
            n[m] += max(int(hi) - int(lo), 0)
    per_copy = n["noisy"] + n["clean"]
    return {"visited": sum(n.values()), "whole": n[False],
            "masked": per_copy + n[True], "square": nqb * nkb,
            "per_copy": per_copy, "general": n[True]}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, *refs):
    bits_ref, (o_ref, lse_ref) = _split_bits(cfg, refs)
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
        if masked == "select":
            s = jnp.where(_unpack(bits_ref[0, :, pl.ds(kj * bk, bk)], bq), s,
                          _NEG)
        elif masked:
            mask = (_bd_mask(cfg, qi, kj, masked) if cfg.bd_block
                    else _kv_mask(cfg, qi, kj, bq, bk))
            s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (m0, l0, a0)
    for first, end, masked in _k_segments(cfg, qi, nkb):
        carry = jax.lax.fori_loop(first, end,
                                  functools.partial(body, masked), carry)
    m, l, acc = carry
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(safe_l), (bq, _LANES))


def _split_bits(cfg: _Cfg, refs):
    """(the words' ref or None, the other refs) of a kernel's trailing
    refs: under a selection the words come first."""
    return (refs[0], refs[1:]) if cfg.select else (None, refs)


def _select_extras(cfg: _Cfg, words, rows_blk, index_map):
    """(in_specs, operands, pallas_call keywords) a kernel adds under a
    selection: the words of its rows' block, every column."""
    if not cfg.select:
        return [], [], {}
    spec = pl.BlockSpec((1, rows_blk // 32, words.shape[-1]), index_map)
    return [spec], [words], {"compiler_params": _SELECT_PARAMS}


def _fwd(cfg: _Cfg, q, k, v, bits=None):
    n, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[-1]
    nqb = lq // cfg.block_q
    specs, extra, kw = _select_extras(
        cfg, bits, cfg.block_q, lambda b, i: (b // max(cfg.select, 1), i, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        name="mx_flash_fwd",
        grid=(n, nqb),
        in_specs=[
            pl.BlockSpec((1, cfg.block_q, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, lk, d), lambda b, i: (b // cfg.group, 0, 0)),
            pl.BlockSpec((1, lk, dv), lambda b, i: (b // cfg.group, 0, 0)),
        ] + specs,
        out_specs=[
            pl.BlockSpec((1, cfg.block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, cfg.block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, lq, dv), q.dtype),
            jax.ShapeDtypeStruct((n, lq, _LANES), jnp.float32),
        ],
        interpret=cfg.interpret, **kw,
    )(q, k, v, *extra)
    return out, lse[..., 0]


# ---------------------------------------------------------------------------
# backward: dq kernel (parallel over q blocks), dkv kernel (over k blocks)
# ---------------------------------------------------------------------------
def _dq_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               *refs):
    bits_ref, (dq_ref,) = _split_bits(cfg, refs)
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
        if masked == "select":
            s = jnp.where(_unpack(bits_ref[0, :, pl.ds(kj * bk, bk)], bq), s,
                          _NEG)
        elif masked:
            mask = (_bd_mask(cfg, qi, kj, masked) if cfg.bd_block
                    else _kv_mask(cfg, qi, kj, bq, bk))
            s = jnp.where(mask, s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = dq0
    for first, end, masked in _k_segments(cfg, qi, nkb):
        dq = jax.lax.fori_loop(first, end, functools.partial(body, masked),
                               dq)
    dq_ref[0] = (dq * cfg.sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *refs):
    """One key block of one key-value head against one of its query heads
    (grid axis 2, innermost): dK/dV accumulate in the f32 output block,
    which stays resident while that axis runs.  Scores are held transposed,
    (bk, bq), so that the per-query statistics are rows of lanes."""
    bits_ref, (dk_ref, dv_ref) = _split_bits(cfg, refs)
    kj, g = pl.program_id(1), pl.program_id(2)
    bq, bk = cfg.block_q, cfg.block_k
    k = k_ref[0]
    v = v_ref[0]
    nqb = q_ref.shape[1] // bq
    if not cfg.bd_block:
        # query blocks this key block reaches, and from which on every row
        # sees it whole; a key block that holds padding is masked throughout
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
        if masked == "select":
            st = jnp.where(_unpack(bits_ref[0, :, pl.ds(qi * bq, bq)], bk),
                           st, _NEG)
        elif masked and cfg.bd_block:
            st = jnp.where(_bd_mask_t(cfg, kj, qi, masked), st, _NEG)
        elif masked:
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

    if cfg.select:
        runs = [((kj * bk) // bq, nqb, "select")]
    elif cfg.bd_block:
        runs = _q_segments(cfg, kj, nqb)
    else:
        whole = jnp.clip(whole, lo, nqb)
        runs = [(lo, whole, True), (whole, nqb, False)]
    carry = (zero, zero_v)
    for first, end, masked in runs:
        carry = jax.lax.fori_loop(first, end,
                                  functools.partial(body, masked), carry)
    dk, dv = carry

    @pl.when(g == 0)
    def _first():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(g > 0)
    def _more():
        dk_ref[0] += dk
        dv_ref[0] += dv


def _bwd_impl(cfg: _Cfg, q, k, v, out, lse, do, bits=None, bits_t=None):
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
    specs, extra, kw = _select_extras(
        cfg, bits, cfg.block_q, lambda b, i: (b // max(cfg.select, 1), i, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        name="mx_flash_dq",
        grid=(n, lq // cfg.block_q),
        in_specs=[q_blk(d), kv_all(d), kv_all(dv), q_blk(dv), stat_blk,
                  stat_blk] + specs,
        out_specs=q_blk(d),
        out_shape=jax.ShapeDtypeStruct((n, lq, d), q.dtype),
        interpret=cfg.interpret, **kw,
    )(q, k, v, do, lse3, delta3, *extra)

    # grid (key-value head, key block, query head of the group)
    def q_all(w):
        return pl.BlockSpec((1, lq, w), lambda b, j, g: (b * grp + g, 0, 0))

    def kv_blk(w):
        return pl.BlockSpec((1, cfg.block_k, w), lambda b, j, g: (b, j, 0))

    stat_row = pl.BlockSpec((1, 1, lq), lambda b, j, g: (b * grp + g, 0, 0))
    kv_rows = max(cfg.select // grp, 1)     # key-value heads a batch row
    specs, extra, kw = _select_extras(
        cfg, bits_t, cfg.block_k, lambda b, j, g: (b // kv_rows, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg),
        name="mx_flash_dkv",
        grid=(nkv, lk // cfg.block_k, grp),
        in_specs=[q_all(d), kv_blk(d), kv_blk(dv), q_all(dv), stat_row,
                  stat_row] + specs,
        out_specs=[kv_blk(d), kv_blk(dv)],
        out_shape=[
            jax.ShapeDtypeStruct((nkv, lk, d), jnp.float32),
            jax.ShapeDtypeStruct((nkv, lk, dv), jnp.float32),
        ],
        interpret=cfg.interpret, **kw,
    )(q, k, v, do, lse[:, None, :], delta[:, None, :], *extra)
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_select(cfg: _Cfg, q, k, v, bits, bits_t):
    return _fwd(cfg, q, k, v, bits)


def _flash_select_fwd(cfg: _Cfg, q, k, v, bits, bits_t):
    from ..recompute import kernel_out

    out, lse = kernel_out(*_fwd(cfg, q, k, v, bits))
    return (out, lse), (q, k, v, out, lse, bits, bits_t)


def _flash_select_bwd(cfg: _Cfg, res, cts):
    import numpy as np

    q, k, v, out, lse, bits, bits_t = res
    dq, dk, dv = _bwd_impl(cfg, q, k, v, out, lse, cts[0], bits, bits_t)
    none = [np.zeros(x.shape, jax.dtypes.float0) for x in (bits, bits_t)]
    return (dq, dk, dv, *none)


_flash_select.defvjp(_flash_select_fwd, _flash_select_bwd)


def sparse_flash_attention(q, k, v, bits, bits_t, sm_scale=None):
    """Causal attention of each query over the keys a selection chose for it
    (module docstring): q (B, H, L, D), k and v (B, Hkv, L, D) grouped-query
    heads, ``bits`` / ``bits_t`` the packed selection of every batch row
    (``check_selection``; ``ops/dsa_ops.py`` ``pack_selection`` makes
    them).  Returns (out (B, H, L, Dv), lse (B, H, L) f32); differentiable in
    q, k and v, the row logsumexp is not."""
    check_selection(q.shape, k.shape, bits, bits_t)
    b, h, lq, d = q.shape
    hkv = k.shape[1]
    if h % hkv or v.shape[1] != hkv:
        raise ValueError(f"selection: {h} query heads over {hkv} key and "
                         f"{v.shape[1]} value heads")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    from . import interpret
    from ... import telemetry

    blk = select_block(lq)
    lp = _round_up(lq, blk)
    n = lp // blk
    telemetry.record_dsa_tiles(lq, blk, {"visited": n * (n + 1) // 2,
                                         "square": n * n})
    cfg = _Cfg(True, float(sm_scale), blk, blk, lq, lq, interpret(),
               h // hkv, 0, h)

    def pad(x):
        x = x.reshape(-1, *x.shape[2:])
        return jnp.pad(x, ((0, 0), (0, lp - lq), (0, 0)))

    out, lse = _flash_select(cfg, pad(q), pad(k), pad(v), bits, bits_t)
    return (out[:, :lq].reshape(b, h, lq, v.shape[-1]),
            lse[:, :lq].reshape(b, h, lq))


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    return_lse: bool = False, block_diffusion: int = 0):
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
    bd = int(block_diffusion)
    if bd:
        check_block_diffusion(lq, lk, bd, causal)
        from ... import telemetry

        telemetry.record_bd_tiles(lq, bd, bq, bk,
                                  bd_tiles(lq, bd, block_q, block_k))
    cfg = _Cfg(bool(causal), float(sm_scale), bq, bk, lq, lk, interpret(),
               group, bd)
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
