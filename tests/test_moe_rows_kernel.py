"""The experts' chunk loop on its two back ends, at small sizes on the CPU:
the Mosaic form (``ops/pallas/moe_rows.py``'s combine and megablox's grouped
products, interpreted) against the XLA form (a scatter-add and
``jax.lax.ragged_dot``) of the same chunks.  ``ops/moe_ops.py`` has one
forward and one written-out backward; ``_kernels`` chooses the back end."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import moe_ops, pallas
from mxnet_tpu.ops.pallas import moe_rows

F32, BF16 = jnp.float32, jnp.bfloat16


def _rand(seed, shape, dtype=F32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape, F32)
            * scale).astype(dtype)


def _sorted_rows(tokens, groups, k, rows, seed, every=None):
    """(token of each sorted row, group sizes, landed rows): each token
    draws ``k`` of ``2 * groups`` experts (``every``: these, for every
    token), the first ``groups`` are held; pairs sorted as moe_experts sorts
    them, cut or padded to ``rows`` rows."""
    rng = np.random.default_rng(seed)
    if every is None:
        picks = np.stack([rng.permutation(2 * groups)[:k]
                          for _ in range(tokens)])
    else:
        picks = np.tile(np.asarray(every), (tokens, 1))
    key = np.where(picks < groups, picks, groups).reshape(-1)
    order = np.argsort(key, kind="stable")
    landed = min(int((key < groups).sum()), rows)
    order = np.pad(order, (0, max(0, rows - order.size)))[:rows]
    sizes = np.bincount(key[order[:landed]], minlength=groups + 1)[:groups]
    sizes[-1] += rows - sizes.sum()          # the dead rows: the last group's
    return (jnp.asarray(order // picks.shape[1], jnp.int32),
            jnp.asarray(sizes, jnp.int32), landed)


def _scatter_form(acc, rows, token, scale, n_live):
    live = jnp.arange(rows.shape[0]) < n_live
    return acc.at[token].add(
        rows.astype(F32) * jnp.where(live, scale, 0)[:, None])


# ---------------------------------------------------------------------------
# the combine alone
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tokens,d,groups,k,rows,landed_share,dtype", [
    (64, 256, 4, 3, 64, 1.0, F32),       # one slab holds every run
    (64, 256, 4, 3, 64, 0.5, BF16),      # dead rows past the landed pairs
    (64, 128, 2, 2, 96, 1.0, F32),       # 96 rows: three slabs
    (512, 128, 4, 4, 1024, 1.0, BF16),   # two tiles of 256 tokens
    (24, 128, 4, 3, 32, 1.0, F32),       # tokens in tiles of 8
    (64, 128, 4, 3, 64, 0.0, F32),       # nothing landed
])
def test_combine_adds_every_landed_row_to_its_token(tokens, d, groups, k,
                                                    rows, landed_share,
                                                    dtype):
    token, sizes, landed = _sorted_rows(tokens, groups, k, rows, seed=rows)
    n_live = int(landed * landed_share)
    y, scale = _rand(1, (rows, d), dtype), _rand(2, (rows,))
    acc = _rand(3, (tokens, d))
    want = _scatter_form(acc, y, token, scale, n_live)
    got = moe_rows.combine(acc, y, token, scale, sizes, n_live,
                           interpret=True)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
    # a fresh accumulator is not read: what was in it does not come through
    fresh = jax.jit(lambda f: moe_rows.combine(
        acc, y, token, scale, sizes, n_live, f, interpret=True))(
            jnp.asarray(True))
    np.testing.assert_allclose(fresh, want - acc, rtol=1e-6, atol=4e-6)


@pytest.mark.parametrize("rows,first_group", [(32, 5), (64, 40)])
def test_a_token_two_experts_hold_is_summed_from_one_slab_and_from_two(
        rows, first_group):
    """Token 3 sits in both groups: with 32 rows both of its rows come with
    one slab, with 64 its second row lies in the second slab."""
    tokens, d = 64, 128
    second = rows - first_group
    token = jnp.asarray(list(range(first_group)) + [3] + list(
        range(4, 3 + second)), jnp.int32)
    sizes = jnp.asarray([first_group, second], jnp.int32)
    y, scale = _rand(4, (rows, d)), _rand(5, (rows,))
    acc = jnp.zeros((tokens, d), F32)
    got = moe_rows.combine(acc, y, token, scale, sizes, rows, interpret=True)
    np.testing.assert_allclose(
        got[3], y[3] * scale[3] + y[first_group] * scale[first_group],
        rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got, _scatter_form(acc, y, token, scale, rows),
                               rtol=1e-6, atol=2e-6)


def test_a_run_longer_than_a_slab_is_fetched_slab_by_slab():
    """Every token chooses both held experts: the one tile of 64 tokens has
    a run of 64 rows an expert, two slabs of 32."""
    tokens, d, rows = 64, 128, 128
    token, sizes, landed = _sorted_rows(tokens, 2, 2, rows, 0, every=(0, 1))
    assert landed == rows and sizes.tolist() == [64, 64]
    y, scale = _rand(6, (rows, d), BF16), _rand(7, (rows,))
    acc = _rand(8, (tokens, d))
    got = moe_rows.combine(acc, y, token, scale, sizes, rows, interpret=True)
    np.testing.assert_allclose(got, _scatter_form(acc, y, token, scale, rows),
                               rtol=1e-6, atol=4e-6)


def test_shapes_the_combine_does_not_take():
    assert moe_rows.fits(16384, 16384, 2688, 8)
    assert not moe_rows.fits(16384, 16384, 1856, 8)      # 14.5 lane tiles
    assert not moe_rows.fits(16384, 16400, 2688, 8)      # no whole slabs
    assert not moe_rows.fits(16387, 16384, 2688, 8)      # no tile of tokens
    assert not moe_rows.fits(16384, 16384, 2688, 128)    # too many to unroll


# ---------------------------------------------------------------------------
# megablox's weight gradient into what is there
# ---------------------------------------------------------------------------
def test_tgmm_with_existing_out_adds_to_what_is_there():
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    sizes = jnp.asarray([40, 0, 130, 86], jnp.int32)
    lhs, d_out = _rand(0, (256, 128), BF16), _rand(1, (256, 256), BF16)
    there = _rand(2, (4, 128, 256))
    got = tgmm(lhs.swapaxes(0, 1), d_out, sizes, F32, (128, 128, 128),
               existing_out=there, interpret=True)
    want = there + jax.lax.ragged_dot_general(
        lhs, d_out, sizes, moe_ops._ROWS_CONTRACTED,
        preferred_element_type=F32)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(got[1] - there[1]).max()) == 0   # an empty group


# ---------------------------------------------------------------------------
# the whole loop, forward and backward, on both back ends
# ---------------------------------------------------------------------------
def _layer(dtype, tokens=32, d=128, f=128, wide=16, count=4, seed=0):
    return {"u": _rand(seed, (tokens, d), dtype),
            "router": _rand(seed + 1, (wide, d), dtype, 0.5),
            "up": _rand(seed + 2, (count, d, f), dtype, 0.2),
            "down": _rand(seed + 3, (count, f, d), dtype, 0.2),
            "cot": _rand(seed + 4, (tokens, d))}


def _value_and_grads(p, chosen, kernels, monkeypatch):
    """The held experts' sum and its four gradients with every token's
    choices pinned to ``chosen``; ``kernels`` picks the back end."""
    tiling = (8, 128, 256)
    monkeypatch.setattr(moe_ops, "grouped_tiling", lambda *a, **k: tiling)
    monkeypatch.setattr(moe_ops, "_kernels",
                        lambda rows, tm: kernels and rows % tm == 0)
    combines, combine = [], moe_rows.combine
    monkeypatch.setattr(moe_rows, "combine",
                        lambda *a, **k: combines.append(1) or combine(*a, **k))
    bias = jnp.full((p["router"].shape[0],), -5.0).at[
        jnp.asarray(chosen)].set(5.0)
    experts, weights = moe_ops.moe_route(p["u"], p["router"], bias,
                                         top_k=len(chosen), scaling=2.5)

    def total(u, w, up, down):
        out, pairs = moe_ops.moe_experts(u, experts, w, up, down, first=0)
        return jnp.sum(out.astype(F32) * p["cot"]), (out, pairs)

    (_v, (out, pairs)), grads = jax.jit(jax.value_and_grad(
        total, argnums=(0, 1, 2, 3), has_aux=True))(
            p["u"], weights, p["up"], p["down"])
    assert len(combines) == (2 if kernels else 0)   # forward and backward
    return (out,) + grads, int(pairs.sum())


def _gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# chunks the loop runs: none lands; one of three choices is held; all three
@pytest.mark.parametrize("chosen,chunks", [((9, 10, 11), 0), ((0, 9, 10), 1),
                                           ((0, 1, 2), 3)])
def test_the_kernel_form_matches_the_xla_form_in_f32(monkeypatch, chosen,
                                                     chunks):
    p = _layer(F32)
    want, landed = _value_and_grads(p, chosen, False, monkeypatch)
    got, landed_k = _value_and_grads(p, chosen, True, monkeypatch)
    tokens = p["u"].shape[0]
    assert landed == landed_k == tokens * chunks
    for name, g, w in zip(("out", "data", "weights", "up", "down"), got,
                          want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4, err_msg=name)
    if not chunks:
        assert all(float(jnp.abs(g).max()) == 0 for g in got)


@pytest.mark.parametrize("chosen", [(0, 9, 10), (0, 1, 2)])
def test_the_kernel_form_in_bf16_is_as_near_f32_as_the_xla_form(monkeypatch,
                                                               chosen):
    p32 = _layer(F32)
    p16 = {k: v if k == "cot" else v.astype(BF16) for k, v in p32.items()}
    exact, _ = _value_and_grads(
        {k: v.astype(F32) for k, v in p16.items()}, chosen, False,
        monkeypatch)
    xla, _ = _value_and_grads(p16, chosen, False, monkeypatch)
    kern, _ = _value_and_grads(p16, chosen, True, monkeypatch)
    for name, e, x, g in zip(("out", "data", "weights", "up", "down"), exact,
                             xla, kern):
        assert g.dtype == x.dtype
        assert _gap(g, e) <= 1.25 * _gap(x, e) + 1e-6, name


def test_rows_that_are_no_multiple_of_the_tile_take_the_xla_form(monkeypatch):
    with pallas.compute_on("tpu"):
        assert moe_ops._kernels(16384, 256)
        assert not moe_ops._kernels(16400, 256)
    with pallas.compute_on("tpu", partitioned=True):
        assert not moe_ops._kernels(16384, 256)
    assert not moe_ops._kernels(16384, 256)               # the CPU
    # where the products' tile is 512 rows, 24 tokens make a chunk of 512
    # sorted rows for the grouped products, and their combine falls to the
    # scatter where the width is no whole lane tile
    monkeypatch.setattr(moe_ops, "_kernels", lambda rows, tm: True)
    called = []
    monkeypatch.setattr(moe_rows, "combine",
                        lambda *a, **k: called.append(a) or a[0])
    acc, y = jnp.zeros((24, 96)), jnp.ones((512, 96))
    token = jnp.arange(512, dtype=jnp.int32) % 24
    out = moe_ops._add_rows(acc, y, token, jnp.ones((512,)),
                            jnp.asarray([512], jnp.int32), 48, False)
    assert not called and float(out.sum()) == 48 * 96
