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
its query heads inside the kernel.

Mask modes: a call's mask is one object (``MaskMode``) that the kernels
call without knowing which mode they have.  The forward and ``dq`` kernels
loop over ``k_runs(qi, nkb)``, runs ``(first, end, form)`` of the key blocks
query block qi visits, the ``dkv`` kernel over ``q_runs(kj, nqb)``, those of
the query blocks key block kj visits; blocks no run holds are not computed.
A falsy ``form`` runs whole, another masks each score by ``tile(form, qi,
kj, words)`` (``tile_t(form, kj, qi, words)``: the ``dkv`` kernel's
transposed tile).  ``extras`` are the operands a mode adds (their refs are
the ``words``), ``allowed(qpos, kpos, words)`` the predicate every tile
agrees with (keys past Lk aside), ``tiles()`` the tiles the runs visit,
counted in numpy.  A new mode is one class of these; no kernel changes.

Causal or full (``Causal``): under ``causal`` the loops stop at the
diagonal, the tiles below it run whole; keys past Lk (padding) are masked.

Block diffusion (``BlockDiffusion``, ``block_diffusion=b``): the stream is a
noisy copy and a clean copy of one sequence side by side, ``[x_t | x_0]``,
2L positions.  Position ``i`` has stream ``s = i >= L``, position ``p = i -
s L`` and block ``j = p // b``; a noisy query sees its own block of the
noisy copy and the clean copy's earlier blocks, a clean query the clean
copy's blocks up to its own (``bd_allowed``).  Where the copies meet on a
tile boundary (L a multiple of both tiles) a masked run holds one copy and
names it as its form, and a tile's mask compares its row index with one
threshold a column, made from the column's block index: one compare a
score, two for a noisy block's range.  Otherwise a tile may hold both
copies or padding and takes the general form, a vector of its rows' block
indices against one of its columns' (``_general``).  Rows past 2L
(padding) count as clean queries of later blocks.

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

import collections
import dataclasses
import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry

_NEG = -1e30
_LANES = 128  # TPU lane width: per-row stats (lse/delta) carry a trailing
              # 128-lane dim so their blocks satisfy Mosaic tiling rules
              # (same trick as jax's in-tree flash kernel, MIN_BLOCK_SIZE)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _pick_block(length: int, preferred: int) -> int:
    if length >= preferred:
        return preferred
    return _round_up(length, 8)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


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


# ---------------------------------------------------------------------------
# mask modes
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MaskMode:
    """A mask mode (module docstring) over (block_q, block_k) tiles of q_len
    queries and kv_len keys (unpadded); hashable, as a static argument of
    ``custom_vjp``.  Run bounds are numpy under ``xp=np``."""
    block_q: int
    block_k: int
    q_len: int
    kv_len: int

    def q_reach(self, kj, nqb, xp=jnp):
        """What ``q_runs`` reads that the ``dkv`` kernel traces before its
        accumulators (None: nothing)."""
        return None

    def extras(self, words, group, transposed=False):
        """(in_specs, operands, pallas_call keywords) the mode adds to a
        kernel (``transposed``: the ``dkv`` kernel) from its ``words``."""
        return [], [], {}

    def _by_form(self):
        nqb = -(-self.q_len // self.block_q)
        nkb = -(-self.kv_len // self.block_k)
        n = collections.Counter()
        for qi in range(nqb):
            for lo, hi, form in self.k_runs(np.int64(qi), nkb, np):
                n[form] += max(int(hi) - int(lo), 0)
        return n, nqb * nkb

    def tiles(self):
        """{"visited", "whole", "masked", "square"} of the forward's and
        ``dq``'s (query block, key block) tiles, counted from the runs the
        kernels loop over (numpy, no trace)."""
        n, square = self._by_form()
        visited = sum(n.values())
        return {"visited": visited, "whole": n[False],
                "masked": visited - n[False], "square": square}


@dataclasses.dataclass(frozen=True)
class Causal(MaskMode):
    """Full attention, or under ``causal`` each query up to its position."""
    causal: bool

    def k_runs(self, qi, nkb, xp=jnp):
        # the leading key blocks every row sees whole, then those it reaches
        whole = self.kv_len // self.block_k
        if not self.causal:
            return [(0, whole, False), (whole, nkb, True)]
        reached = xp.minimum(nkb, ((qi + 1) * self.block_q + self.block_k - 1)
                             // self.block_k)
        whole = xp.minimum(whole, (qi * self.block_q + 1) // self.block_k)
        return [(0, whole, False), (whole, reached, True)]

    def q_reach(self, kj, nqb, xp=jnp):
        # the first query block key block kj reaches, and from which on every
        # row sees it whole; a key block with padding is masked throughout
        bq, bk = self.block_q, self.block_k
        lo = (kj * bk) // bq if self.causal else 0
        whole = ((kj + 1) * bk + bq - 2) // bq if self.causal else 0
        return lo, xp.where((kj + 1) * bk <= self.kv_len, whole, nqb)

    def q_runs(self, kj, nqb, xp=jnp, reach=None):
        lo, whole = self.q_reach(kj, nqb, xp) if reach is None else reach
        whole = xp.clip(whole, lo, nqb)
        return [(lo, whole, True), (whole, nqb, False)]

    def _keep(self, qi, kj, shape, q_axis):
        kpos = kj * self.block_k + _iota(shape, 1 - q_axis)
        keep = kpos < self.kv_len
        if self.causal:
            qpos = qi * self.block_q + _iota(shape, q_axis)
            keep = jnp.logical_and(keep, qpos >= kpos)
        return keep

    def tile(self, form, qi, kj, words=()):
        return self._keep(qi, kj, (self.block_q, self.block_k), 0)

    def tile_t(self, form, kj, qi, words=()):
        return self._keep(qi, kj, (self.block_k, self.block_q), 1)

    def allowed(self, qpos, kpos, words=()):
        return jnp.logical_or(qpos >= kpos, not self.causal)


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


@dataclasses.dataclass(frozen=True)
class BlockDiffusion(MaskMode):
    """The block-diffusion mask of a stream of q_len = 2L positions in
    blocks of ``block`` (module docstring).  A masked run's form is
    "noisy" or "clean" when all its keys (in the ``dkv`` kernel: its query
    rows) are of that copy, True when its tiles may hold both copies or
    padding."""
    block: int

    def _span(self, p0, p1, xp):
        """Whether positions [p0, p1) hold noisy and clean ones, and the
        first and last block of each copy they hold."""
        half, b = self.q_len // 2, self.block
        return (p0 < half, p1 > half, p0 // b, (xp.minimum(p1, half) - 1) // b,
                (xp.maximum(p0, half) - half) // b, (p1 - 1 - half) // b)

    def _runs(self, noisy, noisy_whole, clean, clean_whole, blk, xp):
        runs, end = _segments(noisy, noisy_whole, blk, xp)
        more, _ = _segments(clean, clean_whole, blk, xp, start=end)
        half = self.q_len // 2
        if half % self.block_q == 0 and half % self.block_k == 0:
            # no tile holds both copies: a run's copy is known here
            runs = [(a, c, m and "noisy") for a, c, m in runs]
            more = [(a, c, m and "clean") for a, c, m in more]
        return runs + more

    def k_runs(self, qi, nkb, xp=jnp):
        # the noisy copy's blocks the query tile's rows share, then the
        # clean copy's earlier blocks, each split into masked and whole runs
        half, b = self.q_len // 2, self.block
        q0 = qi * self.block_q
        has_n, has_c, jlo, jhi, jlo_c, jhi_c = self._span(
            q0, q0 + self.block_q, xp)
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
        return self._runs(noisy, noisy_whole, (half, half + reach),
                          (half, half + width), self.block_k, xp)

    def q_runs(self, kj, nqb, xp=jnp, reach=None):
        # the noisy rows that see the key tile's keys, then the clean rows;
        # a key block that holds padding or both copies is masked throughout
        bq, bk = self.block_q, self.block_k
        half, b = self.q_len // 2, self.block
        k0 = kj * bk
        has_n, has_c, jlo, jhi, jlo_c, jhi_c = self._span(
            k0, xp.minimum(k0 + bk, self.kv_len), xp)
        noisy = (xp.minimum(xp.where(has_n, jlo * b, half), xp.where(
                     has_c, xp.minimum((jlo_c + 1) * b, half), half)),
                 xp.maximum(xp.where(has_n, xp.minimum((jhi + 1) * b, half),
                                     0), xp.where(has_c, half, 0)))
        clean = (xp.where(has_c, half + jlo_c * b, 0),
                 xp.where(has_c, nqb * bq, 0))
        # whole runs only for a key block of one copy and no padding: noisy
        # keys to the noisy rows of their one block, clean keys to the noisy
        # rows of later blocks and the clean rows of their last block on
        pure = k0 + bk <= self.kv_len
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
        return self._runs(noisy, noisy_whole, clean, clean_whole, bq, xp)

    def _rows(self, qpos):
        """(bound, noisy) of query positions: a clean key of block j is
        allowed where ``j < bound``, a noisy key where ``j == noisy`` (-1 for
        a clean row, which no noisy key matches)."""
        half, shift = self.q_len // 2, self.block.bit_length() - 1
        sq = qpos >= half
        jq = jnp.right_shift(qpos - jnp.where(sq, half, 0), shift)
        return jq + jnp.where(sq, 1, 0), jnp.where(sq, -1, jq)

    def _general(self, qpos, kpos):
        """The mask from a vector of query positions and one of key
        positions (broadcast against each other), exact for any tile: both
        compares, with sentinels that fail them for the other copy's keys
        and for padding."""
        half, shift = self.q_len // 2, self.block.bit_length() - 1
        bound, noisy = self._rows(qpos)
        sk = kpos >= half
        jk = jnp.right_shift(kpos - jnp.where(sk, half, 0), shift)
        clean = jnp.where(jnp.logical_and(sk, kpos < self.kv_len), jk,
                          1 << 30)
        return jnp.logical_or(clean < bound, jnp.where(sk, -2, jk) == noisy)

    def tile(self, form, qi, kj, words=()):
        # a run of one key copy (the query tile is then of one copy too)
        # compares the row index r with a threshold a key: clean keys ``r >=
        # first`` (``j_k < j_q + s_q``), noisy keys ``first <= r < first +
        # b`` (a noisy row of the key's block)
        bq, bk = self.block_q, self.block_k
        kpos = kj * bk + _iota((1, bk), 1)
        if form is True:
            return self._general(qi * bq + _iota((bq, 1), 0), kpos)
        half, b = self.q_len // 2, self.block
        shift = b.bit_length() - 1
        sq = (qi * bq >= half).astype(jnp.int32)
        p0 = qi * bq - sq * half            # the tile's first row in its copy
        r = _iota((bq, bk), 0)
        if form == "clean":
            jk = jnp.right_shift(kpos - half, shift)
            return r >= jnp.left_shift(jk - sq + 1, shift) - p0
        first = jnp.left_shift(jnp.right_shift(kpos, shift), shift) - p0
        return jnp.logical_and(r >= jnp.maximum(first, sq * bq),
                               r < first + b)

    def tile_t(self, form, kj, qi, words=()):
        # the form names the ROWS' copy, and the key row index r is compared
        # with a threshold a query.  Clean rows see clean keys only: ``r <
        # (bound << shift) - (k0 - half)``; noisy rows take the key block's
        # copy, a noisy block the rows of its block, a clean one the rows of
        # later blocks
        bq, bk = self.block_q, self.block_k
        qpos = qi * bq + _iota((1, bq), 1)
        if form is True:
            return self._general(qpos, kj * bk + _iota((bk, 1), 0))
        half, b = self.q_len // 2, self.block
        shift = b.bit_length() - 1
        k0 = kj * bk
        bound, noisy = self._rows(qpos)
        r = _iota((bk, bq), 0)
        clean_end = jnp.left_shift(bound, shift) - (k0 - half)
        if form == "clean":
            return r < clean_end
        nk = (k0 < half).astype(jnp.int32)  # the key block is noisy
        first = nk * (jnp.left_shift(noisy, shift) - k0)
        end = clean_end + nk * (first + b - clean_end)
        return jnp.logical_and(r >= first, r < end)

    def allowed(self, qpos, kpos, words=()):
        return bd_allowed(self.q_len // 2, self.block, qpos, kpos)

    def tiles(self):
        """``MaskMode.tiles`` and ``masked`` = ``per_copy`` (keys of one
        copy: one compare a score) + ``general`` (both compares)."""
        n, _square = self._by_form()
        return dict(super().tiles(), per_copy=n["noisy"] + n["clean"],
                    general=n[True])


@dataclasses.dataclass(frozen=True)
class Selection(Causal):
    """The causal tiles, each masked by the bits of a selection: the words
    ``(bits, bits_t)`` (module docstring), one for the ``heads`` query heads
    of a batch row."""
    causal: bool = dataclasses.field(default=True, init=False)
    heads: int

    def k_runs(self, qi, nkb, xp=jnp):
        return [(0, super().k_runs(qi, nkb, xp)[-1][1], True)]

    def q_runs(self, kj, nqb, xp=jnp, reach=None):
        # the causal reach (q_reach) is traced and not read: it is part of
        # the dkv body that tests/test_sdar.py pins byte for byte
        return [((kj * self.block_k) // self.block_q, nqb, True)]

    def tile(self, form, qi, kj, words=()):
        return _unpack(words[0][0, :, pl.ds(kj * self.block_k,
                                            self.block_k)], self.block_q)

    def tile_t(self, form, kj, qi, words=()):
        return _unpack(words[0][0, :, pl.ds(qi * self.block_q,
                                            self.block_q)], self.block_k)

    def allowed(self, qpos, kpos, words=()):
        return _unpack(words[0], 32 * len(words[0]))[qpos, kpos]

    def extras(self, words, group, transposed=False):
        # the words of the kernel's row block, every column: ``bits`` by query
        # blocks of a row's heads, ``bits_t`` by key blocks of its kv heads
        rows, blk, w = ((self.heads // group, self.block_k, words[1])
                        if transposed else
                        (self.heads, self.block_q, words[0]))
        spec = pl.BlockSpec((1, blk // 32, w.shape[-1]),
                            lambda b, i, *_g: (b // rows, i, 0))
        return [spec], [w], {"compiler_params": _SELECT_PARAMS}

    def tiles(self):
        """``visited`` of the ``square``'s: every visited tile is masked."""
        t = super().tiles()
        return {"visited": t["visited"], "square": t["square"]}


def bd_tiles(length, block, block_q=512, block_k=512):
    """How the kernels visit the tiles of a block-diffusion stream of
    ``length`` (2L) positions: ``BlockDiffusion.tiles``."""
    return BlockDiffusion(_pick_block(length, block_q), _pick_block(
        length, block_k), length, length, block).tiles()


class _Cfg(NamedTuple):
    mask: MaskMode
    sm_scale: float
    interpret: bool
    group: int  # query heads per key-value head


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, *refs):
    *words, o_ref, lse_ref = refs
    qi = pl.program_id(1)
    bq, bk = cfg.mask.block_q, cfg.mask.block_k
    q = q_ref[0]                                             # (bq, D)
    nkb = k_ref.shape[1] // bk

    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, v_ref.shape[-1]), jnp.float32)

    def body(form, kj, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(kj * bk, bk), :]
        v = v_ref[0, pl.ds(kj * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * cfg.sm_scale
        if form:
            s = jnp.where(cfg.mask.tile(form, qi, kj, words), s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    carry = (m0, l0, a0)
    for first, end, form in cfg.mask.k_runs(qi, nkb):
        carry = jax.lax.fori_loop(first, end,
                                  functools.partial(body, form), carry)
    m, l, acc = carry
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / safe_l).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(m + jnp.log(safe_l), (bq, _LANES))


def _fwd(cfg: _Cfg, q, k, v, *words):
    n, lq, d = q.shape
    lk, dv = k.shape[1], v.shape[-1]
    bq = cfg.mask.block_q
    specs, extra, kw = cfg.mask.extras(words, cfg.group)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        name="mx_flash_fwd",
        grid=(n, lq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, lk, d), lambda b, i: (b // cfg.group, 0, 0)),
            pl.BlockSpec((1, lk, dv), lambda b, i: (b // cfg.group, 0, 0)),
        ] + specs,
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, _LANES), lambda b, i: (b, i, 0)),
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
    *words, dq_ref = refs
    qi = pl.program_id(1)
    bk = cfg.mask.block_k
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0:1]
    delta = delta_ref[0, :, 0:1]
    nkb = k_ref.shape[1] // bk

    def body(form, kj, dq):
        k = k_ref[0, pl.ds(kj * bk, bk), :]
        v = v_ref[0, pl.ds(kj * bk, bk), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * cfg.sm_scale
        if form:
            s = jnp.where(cfg.mask.tile(form, qi, kj, words), s, _NEG)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq + jax.lax.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jnp.zeros(q.shape, jnp.float32)
    for first, end, form in cfg.mask.k_runs(qi, nkb):
        dq = jax.lax.fori_loop(first, end, functools.partial(body, form), dq)
    dq_ref[0] = (dq * cfg.sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(cfg: _Cfg, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                *refs):
    """One key block of one key-value head against one of its query heads
    (grid axis 2, innermost): dK/dV accumulate in the f32 output block,
    which stays resident while that axis runs.  Scores are held transposed,
    (bk, bq), so that the per-query statistics are rows of lanes."""
    *words, dk_ref, dv_ref = refs
    kj, g = pl.program_id(1), pl.program_id(2)
    bq = cfg.mask.block_q
    k = k_ref[0]
    v = v_ref[0]
    nqb = q_ref.shape[1] // bq
    reach = cfg.mask.q_reach(kj, nqb)
    zero = jnp.zeros(k.shape, jnp.float32)
    zero_v = zero if v.shape == k.shape else jnp.zeros(v.shape, jnp.float32)

    def body(form, qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * bq, bq), :]
        do = do_ref[0, pl.ds(qi * bq, bq), :]
        lse = lse_ref[0, :, pl.ds(qi * bq, bq)]                # (1, bq)
        delta = delta_ref[0, :, pl.ds(qi * bq, bq)]
        st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        st = st * cfg.sm_scale
        if form:
            st = jnp.where(cfg.mask.tile_t(form, kj, qi, words), st, _NEG)
        pt = jnp.exp(st - lse)
        dv = dv + jax.lax.dot(pt.astype(do.dtype), do,
                              preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta) * cfg.sm_scale).astype(q.dtype)
        dk = dk + jax.lax.dot(dst, q, preferred_element_type=jnp.float32)
        return dk, dv

    carry = (zero, zero_v)
    for first, end, form in cfg.mask.q_runs(kj, nqb, reach=reach):
        carry = jax.lax.fori_loop(first, end,
                                  functools.partial(body, form), carry)
    dk, dv = carry

    @pl.when(g == 0)
    def _first():
        dk_ref[0] = dk
        dv_ref[0] = dv

    @pl.when(g > 0)
    def _more():
        dk_ref[0] += dk
        dv_ref[0] += dv


def _bwd_impl(cfg: _Cfg, q, k, v, out, lse, do, *words):
    n, lq, d = q.shape
    nkv, lk, dv = k.shape[0], k.shape[1], v.shape[-1]
    grp = cfg.group
    bq, bk = cfg.mask.block_q, cfg.mask.block_k
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # (n, lq)
    lse3 = jnp.broadcast_to(lse[..., None], (n, lq, _LANES))
    delta3 = jnp.broadcast_to(delta[..., None], (n, lq, _LANES))
    def q_blk(w):
        return pl.BlockSpec((1, bq, w), lambda b, i: (b, i, 0))

    def kv_all(w):
        return pl.BlockSpec((1, lk, w), lambda b, i: (b // grp, 0, 0))

    stat_blk = pl.BlockSpec((1, bq, _LANES), lambda b, i: (b, i, 0))
    specs, extra, kw = cfg.mask.extras(words, grp)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        name="mx_flash_dq",
        grid=(n, lq // bq),
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
        return pl.BlockSpec((1, bk, w), lambda b, j, g: (b, j, 0))

    stat_row = pl.BlockSpec((1, 1, lq), lambda b, j, g: (b * grp + g, 0, 0))
    specs, extra, kw = cfg.mask.extras(words, grp, transposed=True)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, cfg),
        name="mx_flash_dkv",
        grid=(nkv, lk // bk, grp),
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


_flash = jax.custom_vjp(_fwd, nondiff_argnums=(0,))


def _flash_fwd(cfg: _Cfg, q, k, v, *words):
    from ..recompute import kernel_out

    # both, or a recomputed layer runs the kernel again: the backward reads
    # lse, the layer's later ops read out
    out, lse = kernel_out(*_fwd(cfg, q, k, v, *words))
    return (out, lse), (q, k, v, out, lse, words)


def _flash_bwd(cfg: _Cfg, res, cts):
    q, k, v, out, lse, words = res
    dq, dk, dv = _bwd_impl(cfg, q, k, v, out, lse, cts[0], *words)
    # the mode's operands (a selection's words) have no gradient
    return (dq, dk, dv,
            *(np.zeros(w.shape, jax.dtypes.float0) for w in words))


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attend(mask, q, k, v, sm_scale, *words):
    """(out, lse f32) of q (N, Lq, D) or (B, H, Lq, D) over k and v (the
    same, or (B, Hkv, Lk, D), H a multiple of Hkv) under ``mask``."""
    rows = q.shape[:-1]
    group = 1
    if q.ndim == 4:
        h, hkv = q.shape[1], k.shape[1]
        if h % hkv or v.shape[1] != hkv:
            raise ValueError(f"flash_attention: {h} query heads over "
                             f"{hkv} key and {v.shape[1]} value heads")
        group = h // hkv
        q, k, v = (x.reshape(-1, *x.shape[2:]) for x in (q, k, v))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    from . import interpret

    cfg = _Cfg(mask, float(sm_scale), interpret(), group)
    pad = lambda x, blk: jnp.pad(x, ((0, 0), (0, -x.shape[1] % blk), (0, 0)))
    out, lse = _flash(cfg, pad(q, mask.block_q), pad(k, mask.block_k),
                      pad(v, mask.block_k), *words)
    lq = rows[-1]
    return (out[:, :lq].reshape(*rows, v.shape[-1]),
            lse[:, :lq].reshape(rows))


def sparse_flash_attention(q, k, v, bits, bits_t, sm_scale=None):
    """Causal attention of each query over the keys a selection chose for it
    (module docstring): q (B, H, L, D), k and v (B, Hkv, L, D) grouped-query
    heads, ``bits`` / ``bits_t`` the packed selection of every batch row
    (``check_selection``; ``ops/dsa_ops.py`` ``pack_selection`` makes
    them).  Returns (out (B, H, L, Dv), lse (B, H, L) f32); differentiable in
    q, k and v, the row logsumexp is not."""
    check_selection(q.shape, k.shape, bits, bits_t)
    lq = q.shape[2]
    blk = select_block(lq)
    mask = Selection(blk, blk, lq, lq, q.shape[1])
    out = _attend(mask, q, k, v, sm_scale, bits, bits_t)
    telemetry.record_flash_tiles("dsa_tiles", {"length": lq, "block": blk},
                                 mask.tiles())
    return out


def flash_attention(q, k, v, causal: bool = False,
                    sm_scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    block_diffusion: int = 0):
    """Fused attention: softmax(q @ k^T * sm_scale [+ causal mask]) @ v.

    q: (N, Lq, D) or (B, H, Lq, D); k, v likewise with Lk (v of any width
    Dv: the output is Dv wide; `sm_scale` defaults to D^-0.5), or with fewer
    heads, (B, Hkv, Lk, D), H a multiple of Hkv (grouped-query attention: the
    module docstring).  Differentiable in q/k/v (FA2 backward).  Blocks of 512 x 512
    (shorter lengths take one block): at 8,192 positions and 128-wide heads
    on a v5e chip the forward takes 10.2 ms where 128 x 128 blocks took 50.5
    (PERF.md, PR 27: the softmax's elementwise work per tile, not the MXU,
    sets the pace).
    """
    lq, lk = q.shape[-2], k.shape[-2]
    bq, bk = _pick_block(lq, block_q), _pick_block(lk, block_k)
    if block_diffusion:
        check_block_diffusion(lq, lk, block_diffusion, causal)
        mask = BlockDiffusion(bq, bk, lq, lk, int(block_diffusion))
        telemetry.record_flash_tiles("bd_tiles", {
            "length": lq, "block": block_diffusion, "block_q": bq,
            "block_k": bk}, mask.tiles())
    else:
        mask = Causal(bq, bk, lq, lk, bool(causal))
    return _attend(mask, q, k, v, sm_scale)[0]
