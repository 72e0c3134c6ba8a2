"""The tiles of the held experts' grouped products (``ops/moe_ops.py``
``grouped_tiling``): megablox at the tiles the function picks against
``ragged_dot`` (interpret mode, small shapes), the picks for the benchmark's
three expert shapes (arithmetic alone), and what telemetry is told."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.ops import moe_ops

F32, BF16 = jnp.float32, jnp.bfloat16
M, K, N = 256, 256, 384

# where the boundaries between four groups of 256 sorted rows fall
SIZES = {"inside_a_tile": [40, 100, 30, 86], "on_a_tiles_edge": [128, 0, 64, 64],
         "an_empty_group": [50, 0, 130, 76], "all_in_one_group": [0, 0, 256, 0]}


def _rand(seed, shape, dtype=F32, scale=1.0):
    return (jax.random.normal(jax.random.PRNGKey(seed), shape, F32)
            * scale).astype(dtype)


@pytest.fixture
def kernels(monkeypatch):
    """The Mosaic form wherever the rows are whole tiles (interpreted here)."""
    monkeypatch.setattr(moe_ops, "_kernels",
                        lambda rows, tm: rows % tm == 0)
    seen = []
    monkeypatch.setattr(
        telemetry, "record_grouped_tiles",
        lambda kind, m, k, n, groups, carry, tiling: seen.append(
            (kind, tiling)))
    return seen


def _pin(monkeypatch, tiling):
    if tiling:
        monkeypatch.setattr(moe_ops, "grouped_tiling", lambda *a, **k: tiling)


# the function's own pick (the whole contraction in one tile), and two k
# tiles with a row tile that boundaries cut more often
TILINGS = [None, (64, 128, 128)]


@pytest.mark.parametrize("tiling", TILINGS, ids=["picked", "two_k_tiles"])
@pytest.mark.parametrize("transpose_rhs", [False, True])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_grouped_dot_equals_ragged_dot(monkeypatch, kernels, sizes,
                                       transpose_rhs, tiling):
    _pin(monkeypatch, tiling)
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs = _rand(0, (M, K), BF16)
    rhs = _rand(1, (4, N, K) if transpose_rhs else (4, K, N), BF16, 0.1)
    got = jax.jit(lambda *a: moe_ops._grouped_dot(
        *a, transpose_rhs=transpose_rhs))(lhs, rhs, sizes)
    want = jax.lax.ragged_dot(
        lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs, sizes,
        preferred_element_type=F32)
    assert kernels == [("gmm", tiling or moe_ops.grouped_tiling(
        "gmm", M, K, N, 4, 2))]
    assert got.dtype == BF16 and got.shape == (M, N)
    np.testing.assert_allclose(got.astype(F32), want, rtol=1e-2, atol=2e-2)


@pytest.mark.parametrize("tiling", TILINGS, ids=["picked", "two_k_tiles"])
@pytest.mark.parametrize("carry", [False, True])
@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
def test_grouped_weights_grad_equals_ragged_dot(monkeypatch, kernels, sizes,
                                                carry, tiling):
    _pin(monkeypatch, tiling)
    sizes = jnp.asarray(sizes, jnp.int32)
    lhs, d_out = _rand(2, (M, K), BF16), _rand(3, (M, N), BF16)
    acc = _rand(4, (4, K, N)) if carry else None
    got = jax.jit(lambda *a: moe_ops._grouped_dot_weights_grad(*a))(
        acc, lhs, d_out, sizes)
    want = jax.lax.ragged_dot_general(
        lhs, d_out, sizes, moe_ops._ROWS_CONTRACTED,
        preferred_element_type=F32)
    assert kernels == [("tgmm", tiling or moe_ops.grouped_tiling(
        "tgmm", M, K, N, 4, 2, carry))]
    if carry:       # summed in f32 into what is there
        assert got.dtype == F32
        np.testing.assert_allclose(got, acc + want, rtol=1e-5, atol=1e-3)
    else:           # rounded once
        assert got.dtype == BF16
        np.testing.assert_allclose(got.astype(F32), want, rtol=1e-2,
                                   atol=0.1)


# ---------------------------------------------------------------------------
# the picks for the three cells' shapes: arithmetic, no kernel runs
# ---------------------------------------------------------------------------
# chunk rows, (d, f), held experts, whether a loop's f32 carries are added to
CELLS = {"zaya": (8192, (2048, 2048), 16, False),
         "xing": (4096, (3584, 1024), 8, True),
         "nemotron": (16384, (2688, 1856), 8, True)}
PRODUCTS = [(cell, kind, flip) for cell in CELLS for kind in ("gmm", "tgmm")
            for flip in (False, True) if not (cell == "zaya" and flip)]


@pytest.mark.parametrize("cell,kind,flip", PRODUCTS)
def test_a_cells_pick_divides_the_chunk_and_fits(cell, kind, flip):
    m, (k, n), groups, loop = CELLS[cell]
    if flip:
        k, n = n, k
    carry = loop and kind == "tgmm"
    tiling = tm, tk, tn = moe_ops.grouped_tiling(kind, m, k, n, groups, 2,
                                                 carry)
    assert m % tm == 0 and tm in moe_ops.ROW_TILES
    assert tk == k or tk % 128 == 0
    assert tn == n or tn % 128 == 0
    # counted by hand: two buffers a block, the accumulator, the carry's
    # f32 block in and out
    if kind == "gmm":
        vmem = 2 * 2 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
    else:
        vmem = (2 * 2 * tm * (tk + tn) + 4 * tk * tn
                + 2 * tk * tn * (8 if carry else 2))
    assert vmem == moe_ops.grouped_vmem(kind, tiling, 2, carry)
    assert vmem <= moe_ops.VMEM_BYTES < 16 << 20
    if kind == "gmm":   # one k tile wherever a block of the whole k fits
        fits = moe_ops.grouped_vmem("gmm", (128, k, 256), 2) \
            <= moe_ops.VMEM_BYTES
        assert (tk == k) == fits and fits
    # no dearer than the one tiling every shape had, by the function's own
    # cost, and fewer rows run for the boundaries
    before = (512, 1024, 512 if carry else 1024)
    cost = lambda t: moe_ops.grouped_cost(kind, t, m, k, n, groups, 2, carry)
    assert cost(tiling) <= cost(before)


@pytest.mark.parametrize("cell", CELLS)
def test_the_chunk_keeps_its_rows(cell):
    m, (d, f), groups, loop = CELLS[cell]
    tm = moe_ops._row_tile(m, d, f, groups, 2, loop)
    assert tm in moe_ops.ROW_TILES and m % tm == 0


def test_the_choice_reads_shapes_alone():
    """Small row tiles where boundaries are many, large ones where the rows
    are many and the groups few; a stated cost, so a table of names has
    nowhere to live."""
    many = moe_ops.grouped_tiling("gmm", 8192, 2048, 2048, 16, 2)
    few = moe_ops.grouped_tiling("gmm", 65536, 2048, 2048, 2, 2)
    assert many[0] < few[0] and many[1] == few[1] == 2048
    cost = moe_ops.grouped_cost("gmm", (512, 1024, 1024), 8192, 2048, 2048,
                                16, 2)
    visits, steps = 16 + 15, (16 + 15) * 2 * 2
    mxu = 2 * 512 * 1024 * 1024 / moe_ops.MXU_FLOPS
    assert cost == pytest.approx(steps * (mxu + moe_ops.STEP_S), rel=1e-6)
    assert visits * 512 / 8192 == pytest.approx(1.94, abs=0.01)


@pytest.mark.parametrize("kind,sizes,tm,want", [
    ("gmm", [40, 100, 30, 86], 64, 1 + 3 + 1 + 2),
    ("gmm", [128, 0, 64, 64], 64, 2 + 0 + 1 + 1),
    ("tgmm", [128, 0, 64, 64], 64, 2 + 1 + 1 + 1),
    ("gmm", [0, 0, 256, 0], 128, 2),
    ("tgmm", [0, 0, 256, 0], 128, 2 + 3)])
def test_row_tiles_visited_counts_a_shared_tile_for_each_group(kind, sizes,
                                                               tm, want):
    assert moe_ops.row_tiles_visited(kind, sizes, tm) == want


# ---------------------------------------------------------------------------
# what telemetry is told: at trace time the tiles, at drain the rows run
# ---------------------------------------------------------------------------
def test_a_traced_step_reports_its_tiles_and_drain_the_rows_run(monkeypatch):
    from mxnet_tpu.models.zaya import zaya
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    tiling = (8, 128, 128)
    monkeypatch.setattr(moe_ops, "grouped_tiling", lambda *a, **k: tiling)
    monkeypatch.setattr(moe_ops, "_kernels", lambda rows, tm: rows % tm == 0)
    telemetry.reset()
    mx.random.seed(3)
    net = zaya(vocab_size=48, hidden_size=128, num_layers=1,
               num_attention_heads=4, num_key_value_heads=2, head_dim=8,
               num_experts=4, moe_intermediate_size=128,
               router_hidden_size=16)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(
        net, lambda lg, lb: ce(lg.reshape(-1, lg.shape[-1]), lb.reshape(-1)),
        mesh=local_mesh(devices=jax.devices()[:1]), optimizer="adam",
        optimizer_params={"learning_rate": 1e-2})
    tokens = np.random.default_rng(0).integers(0, 48, (2, 12), dtype=np.int32)
    step.step(nd.array(tokens, dtype="int32"),
              nd.array(tokens.astype(np.float32)))
    told = telemetry.summary()["grouped_tiles"]
    assert not told["rows_run_over_rows"]           # nothing before drain
    rows = {(t["kind"], t["k"], t["n"]): t for t in told["tilings"]}
    assert sorted(rows) == [("gmm", 128, 128), ("tgmm", 128, 128)]
    for t in rows.values():
        assert (t["m"], t["groups"], t["carry"]) == (24, 4, False)
        assert t["tiling"] == list(tiling)
    # a layer runs nine gmm and three tgmm; its forward is traced again
    assert rows["gmm", 128, 128]["sites"] >= 9
    assert rows["tgmm", 128, 128]["sites"] == 3
    step.drain()
    summary = telemetry.summary()
    (name, load), = [(n, v) for n, v in summary["moe_load"].items()
                     if n.endswith("_moe_load")]
    landed = np.round(np.asarray(load) * 24 / 4).astype(int)
    assert landed.sum() == 24
    ends = np.cumsum(landed)
    # by hand: a tile of 8 rows counts once for every group with rows in it
    shared = sum(1 for tile in range(3) for lo, hi in zip(ends - landed, ends)
                 if max(lo, 8 * tile) < min(hi, 8 * tile + 8))
    visits = {"gmm": shared, "tgmm": shared + int((landed == 0).sum())}
    sites = {k[0]: t["sites"] for k, t in rows.items()}
    want = (sum(sites[k] * visits[k] * 8 for k in sites)
            / (24 * sum(sites.values())))
    got = summary["grouped_tiles"]["rows_run_over_rows"]
    assert list(got) == [name]
    assert got[name] == pytest.approx(want) and 1 <= got[name] <= 2
    telemetry.reset()
    assert telemetry.summary()["grouped_tiles"] == {
        "tilings": [], "rows_run_over_rows": {}}


def test_rows_run_over_rows_follows_a_loops_chunks():
    """Three choices a token over 4 held experts of 16, 32 tokens: 70 landed
    pairs make three chunks of 32 rows; the last runs whole, its spare rows
    on the last held expert."""
    tilings = [{"kind": "gmm", "m": 32, "k": 128, "n": 128, "groups": 4,
                "carry": False, "tiling": [8, 128, 128], "sites": 2},
               {"kind": "tgmm", "m": 32, "k": 128, "n": 128, "groups": 4,
                "carry": True, "tiling": [16, 128, 128], "sites": 1}]
    landed = [20, 0, 27, 23]
    # chunk 0: [20, 0, 12, 0]; chunk 1: [0, 0, 15, 17]; chunk 2: [0, 0, 0, 32]
    gmm = (3 + 2) + (2 + 3) + 4
    tgmm = (2 + 1 + 1 + 1) + (1 + 1 + 1 + 2) + (1 + 1 + 1 + 2)
    want = (2 * gmm * 8 + tgmm * 16) / (3 * 3 * 32)
    assert moe_ops.rows_run_over_rows(landed, 32, 3, tilings) \
        == pytest.approx(want)
    # another layer's products (other groups, other rows) are not this one's
    assert moe_ops.rows_run_over_rows(landed, 32, 3, [
        dict(tilings[0], groups=8), dict(tilings[0], m=1024)]) is None
