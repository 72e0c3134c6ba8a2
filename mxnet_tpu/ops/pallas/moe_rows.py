"""The experts' combine as one Mosaic call: rows that were worked through
sorted by expert go back to their tokens, each times its weight, summed in
f32 into what the tokens' rows already hold.

No reference counterpart.  ``ops/moe_ops.py`` works a chunk of (token,
choice) pairs through the held experts sorted by expert, and the stable sort
leaves every expert's rows in the order of their tokens.  So the rows that
belong to a tile of tokens are one run of consecutive rows per expert: the
kernel walks the tokens tile by tile, fetches each expert's run by DMA in
slabs of whole row tiles (a one-row slice of a tiled array is not a DMA
Mosaic issues), and adds a row to its token's row of the tile in VMEM.  Every
row of the result is written once, a token that two experts hold is summed in
f32 in the order of the experts, and nothing is scattered: XLA's scatter-add
of these rows took 2.1 to 2.4 ms for 16,384 rows of 2,688 on a v5e chip where
its gather of the same rows took 0.14 (PERF.md section 5, PR 30).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: tokens of a tile at most, rows of a slab (whole row tiles of bf16), and
#: the most experts a call unrolls over; VMEM: about 14 MB at 2,688 columns
TOKENS, SLAB, MAX_GROUPS = 256, 32, 16
#: what a call's blocks and scratch may take of the 16 MB it gets unasked
VMEM_BUDGET = 15 * 2 ** 20


def _vmem(tile, width, groups, itemsize):
    """Bytes a call holds: the tokens' tile in and out (f32, two buffers
    each), two sets of every expert's slab, one slab widened."""
    return (4 * tile * 4 + 2 * groups * SLAB * itemsize + SLAB * 4) * width


def _token_tile(tokens, width, groups, itemsize=2):
    """The most tokens a tile holds: a whole number of tiles, inside the
    budget (256 up to 2,688 columns of 8 experts' bf16 rows, 128 at 3,584)."""
    return next((t for t in (TOKENS, 128, 64, 32, 16, 8) if tokens % t == 0
                 and _vmem(t, width, groups, itemsize) <= VMEM_BUDGET), None)


def fits(tokens, rows, width, groups, itemsize=2):
    """True for shapes the kernel takes: whole lane tiles across, whole
    sublane tiles of tokens, whole slabs of rows, few enough experts."""
    return (width % LANES == 0 and rows % SLAB == 0 and groups <= MAX_GROUPS
            and _token_tile(tokens, width, groups, itemsize) is not None)


def _runs(token, group_sizes, n_live, tile, n_tiles):
    """First and one-past-last row of every (tile of tokens, group): the
    landed rows ``< n_live`` of a group are in the order of their tokens, so
    those of a tile are the group's rows with fewer tokens below the tile's
    first, up to those below the next tile's first."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    row = jnp.arange(token.shape[0])[:, None]
    mine = ((row >= starts[None]) & (row < ends[None])
            & (row < n_live)).astype(jnp.float32)               # (rows, groups)
    edge = jnp.arange(n_tiles + 1) * tile
    below = (token[:, None] < edge[None]).astype(jnp.float32)   # (rows, tiles + 1)
    # counts stay exact in f32: there are fewer than 2**24 rows
    before = jnp.einsum("rt,rg->tg", below, mine,
                        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    first = starts[None] + before
    return first[:-1].reshape(-1), first[1:].reshape(-1)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine(acc, rows, token, scale, group_sizes, n_live, fresh=False,
            interpret=False):
    """``acc`` (tokens, d) f32 plus ``rows[i] * scale[i]`` at ``token[i]``
    for the rows ``i < n_live``, the multiplication and the sum in f32.

    rows (m, d) are sorted by group (``group_sizes`` (groups,) sum to m) and
    the rows ``< n_live`` of a group are in the order of their tokens, a
    token at most once a group.  ``acc`` is written in place; where ``fresh``
    (a traced flag) it is taken as all zero and not read."""
    tokens, d = acc.shape
    m, groups = rows.shape[0], group_sizes.shape[0]
    tile = _token_tile(tokens, d, groups, rows.dtype.itemsize)
    n_tiles = tokens // tile
    first, last = _runs(token, group_sizes.astype(jnp.int32),
                        jnp.asarray(n_live, jnp.int32), tile, n_tiles)

    def kernel(token_ref, scale_ref, first_ref, last_ref, fresh_ref, acc_ref,
               rows_ref, out_ref, slabs, wide, sems):
        i = pl.program_id(0)
        buf = i % 2

        def slab_start(row):
            return pl.multiple_of(
                jnp.minimum(row // SLAB * SLAB, m - SLAB), SLAB)

        def fetch(start, buf, g):
            return pltpu.make_async_copy(
                rows_ref.at[pl.ds(start, SLAB)], slabs.at[buf, g],
                sems.at[buf, g])

        def fetch_tile(t, buf):
            for g in range(groups):
                fetch(slab_start(first_ref[t * groups + g]), buf, g).start()

        @pl.when(i == 0)
        def _():
            fetch_tile(0, 0)

        @pl.when(i + 1 < pl.num_programs(0))
        def _():
            fetch_tile(i + 1, 1 - buf)

        out_ref[...] = jnp.where(fresh_ref[0] != 0, 0.0, acc_ref[...])

        for g in range(groups):
            lo, hi = first_ref[i * groups + g], last_ref[i * groups + g]
            start = slab_start(lo)

            # the run's slabs: the first is on its way, and holds the whole
            # run unless the expert draws many times its even share; any
            # further one is fetched and waited for
            def one_slab(s, carry):
                at = slab_start(start + s * SLAB)
                copy = fetch(at, buf, g)

                @pl.when(s > 0)
                def _():
                    copy.start()

                copy.wait()
                wide[...] = slabs[buf, g].astype(jnp.float32)

                def one_row(r, carry):
                    to = token_ref[r] - i * tile
                    out_ref[pl.ds(to, 1), :] += (
                        scale_ref[r] * wide[pl.ds(r - at, 1), :])
                    return carry

                return jax.lax.fori_loop(
                    jnp.maximum(lo, start + s * SLAB),
                    jnp.minimum(hi, start + (s + 1) * SLAB), one_row, carry)

            jax.lax.fori_loop(
                0, 1 + jnp.maximum(hi - start - 1, 0) // SLAB, one_slab, 0)

    block = pl.BlockSpec((tile, d), lambda i, *_: (i, 0))
    # a fresh acc stays at its first block: the pipeline fetches a block only
    # when its index changes, so nothing of it is read after that
    held = pl.BlockSpec((tile, d), lambda i, *s: (jnp.where(s[4][0], 0, i), 0))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(acc.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(n_tiles,),
            in_specs=[held, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[pltpu.VMEM((2, groups, SLAB, d), rows.dtype),
                            pltpu.VMEM((SLAB, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, groups))]),
        input_output_aliases={5: 0},
        # no more VMEM than a call gets unasked: what a call may take, XLA
        # cannot keep there across it, and it keeps the tokens' rows there
        # for the chunk's gather (0.14 ms from VMEM, 0.57 from HBM; PR 30)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=16 * 2 ** 20),
        name="mx_moe_combine", interpret=interpret,
    )(token.astype(jnp.int32), scale.astype(jnp.float32), first, last,
      jnp.asarray(fresh, jnp.int32).reshape(1), acc, rows)
