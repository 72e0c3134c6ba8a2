"""The mechanisms ``models/sdar.py`` brought, at small sizes on the CPU: the
flash kernels under the block-diffusion mask (interpret mode) against a dense
masked softmax with all three gradients, the tiles every mask mode visits
and how each visited tile masks, every mode's kernels lowered as before,
softmax routing renormalised over the chosen
experts (and ZAYA's unrenormalised default), the shares of an expert layer,
the noising block, and the stack through ``DataParallelStep`` against the
plain reference (``benchmark/references/sdar_30b_a3b_chat.py``)."""
import base64
import hashlib
import importlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.ops import contrib_ops, dsa_ops, moe_ops
from mxnet_tpu.models import sdar
from mxnet_tpu.ops import pallas as pk

fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"vocab_size": 40, "hidden_size": 32, "layer_types": ["sparse"] * 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_experts": 3, "num_experts_published": 6, "experts_held_first": 2,
        "num_experts_per_tok": 2, "moe_intermediate_size": 16,
        "norm_topk_prob": True, "rope_theta": 1000000, "rms_norm_eps": 1e-6,
        "block_length": 4, "noise_floor": 1e-3, "mask_token_id": 39,
        "initializer_range": 0.1, "router_lr_mult": 0.0, "dtype": "float32",
        "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                      "beta2": 0.95, "epsilon": 1e-8}}
KWARGS = dict(vocab_size=40, hidden_size=32, num_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              num_experts=6, experts_held=(2, 3), num_experts_per_tok=2,
              moe_intermediate_size=16, router_lr_mult=0.0, block_length=4,
              mask_token_id=39)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "sdar_reference", os.path.join(_REPO, "benchmark", "references",
                                       "sdar_30b_a3b_chat.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rand(seed, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


# ---------------------------------------------------------------------------
# the kernels under the block-diffusion mask
# ---------------------------------------------------------------------------
def _dense(q, k, v, block):
    """softmax over the allowed keys, K and V repeated over their query
    heads: the dense composition the op takes off the chip."""
    b, h, n, d = q.shape
    g = h // k.shape[1]
    k, v = jnp.repeat(k, g, 1), jnp.repeat(v, g, 1)
    out = contrib_ops._dense_attention(
        q.reshape(b * h, n, d), k.reshape(b * h, n, d),
        v.reshape(b * h, n, -1), False, d ** -0.5, block)
    return out.reshape(b, h, n, -1)


@pytest.mark.parametrize("length, block, tile, heads, kv_heads", [
    (64, 1, 32, 2, 2),       # blocks of one position
    (64, 4, 32, 2, 1),
    (64, 16, 16, 2, 2),      # a block longer than a tile: whole noisy tiles
    (100, 4, 64, 2, 1),      # 2L = 200: not a multiple of the tile
    (48, 4, 32, 8, 1),       # grouped-query heads 8:1
    (256, 4, 32, 4, 1),      # the cell's 16 x 16 tiles, scaled down
    (96, 4, 64, 2, 1),       # L not a multiple of the tile: the general form
    (100, 4, 48, 2, 2),      # padded, and L not a multiple of the tile
])
def test_flash_under_the_block_diffusion_mask_forward_and_all_gradients(
        length, block, tile, heads, kv_heads):
    ks = jax.random.split(jax.random.PRNGKey(length + block), 4)
    q = jax.random.normal(ks[0], (1, heads, 2 * length, 16))
    k = jax.random.normal(ks[1], (1, kv_heads, 2 * length, 16))
    v = jax.random.normal(ks[2], (1, kv_heads, 2 * length, 16))
    do = jax.random.normal(ks[3], (1, heads, 2 * length, 16))

    def flash(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=block,
                                  block_q=tile, block_k=tile)

    np.testing.assert_allclose(flash(q, k, v), _dense(q, k, v, block),
                               atol=2e-6)
    got = jax.grad(lambda *a: (flash(*a) * do).sum(), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (_dense(*a, block) * do).sum(), (0, 1, 2))(
        q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg="d" + name)


def test_the_mask_against_its_definition(ref):
    """Noisy queries see their own noisy block and earlier clean blocks,
    clean queries the clean blocks up to their own; the reference's
    predicate is the kernels'."""
    length, block = 12, 4
    pos = np.arange(2 * length)
    q, k = pos[:, None], pos[None, :]
    got = np.asarray(fa.bd_allowed(length, block, jnp.asarray(q),
                                   jnp.asarray(k)))
    s_q, s_k = q >= length, k >= length
    j_q, j_k = (q % length) // block, (k % length) // block
    want = (s_q & s_k & (j_k <= j_q)) | (~s_q & ~s_k & (j_k == j_q)) \
        | (~s_q & s_k & (j_k < j_q))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(ref.allowed(length, block, q, k), want)
    # every query sees at least itself, and no clean query a noisy key
    assert got.diagonal().all() and not got[length:, :length].any()


def _mode(length, block, tile):
    """(mask, words, the predicate over the padded square) of a case:
    ``block`` is the block-diffusion block, or ``causal``, ``full`` or
    ``selection`` (a random one, packed as ``ops/dsa_ops.py`` packs it);
    rows past the stream count as clean queries of later blocks, as in the
    kernels."""
    n = -(-length // tile) * tile
    pos = jnp.arange(n)
    words = ()
    if block == "selection":
        sel = np.tril(np.random.default_rng(length).random((n, n)) < 0.3)
        sel[length:] = False
        sel[:, length:] = False
        words = (dsa_ops.pack(jnp.asarray(sel)[None], n),
                 dsa_ops.pack(jnp.asarray(sel.T)[None], n))
        mask = fa.Selection(tile, tile, length, length, 2)
    elif block in ("causal", "full"):
        mask = fa.Causal(tile, tile, length, length, block == "causal")
    else:
        mask = fa.BlockDiffusion(tile, tile, length, length, block)
    keep = np.asarray(mask.allowed(pos[:, None], pos[None, :],
                                   [w[0] for w in words]))
    if block == "selection":
        np.testing.assert_array_equal(keep, sel)
    return mask, words, keep & (np.arange(n)[None, :] < length)


def _brute_tiles(keep, tile):
    """(whole, masked) tiles of the square by the predicate itself."""
    whole = masked = 0
    for i in range(0, len(keep), tile):
        for j in range(0, len(keep), tile):
            t = keep[i:i + tile, j:j + tile]
            whole += bool(t.all())
            masked += bool(t.any() and not t.all())
    return whole, masked


@pytest.mark.parametrize("length, block, tile", [
    (8192, 4, 512), (8192, 16, 512), (1024, 1, 128), (200, 4, 64),
    (96, 16, 16), (1200, 8, 512), (1024, "causal", 128), (200, "full", 64),
    (300, "selection", 256)])
def test_the_tiles_visited_are_those_the_mask_reaches(length, block, tile):
    """The runs the kernels loop over visit every tile the mask reaches, run
    unmasked only tiles it leaves whole, and the dkv kernel's runs visit as
    many tiles as the forward's."""
    mask, _words, keep = _mode(length, block, tile)
    counts = mask.tiles()
    whole, masked = _brute_tiles(keep, tile)
    assert counts["visited"] == whole + masked
    assert counts.get("whole", 0) <= whole
    n = -(-length // tile)
    by_key = sum(max(int(e) - int(s), 0) for kj in range(n)
                 for s, e, _m in mask.q_runs(np.int64(kj), n, np))
    assert by_key == counts["visited"]
    if (length, block, tile) == (8192, 4, 512):
        assert counts == fa.bd_tiles(length, block, tile, tile) == {
            "visited": 80, "whole": 56, "masked": 24, "square": 256,
            "per_copy": 24, "general": 0}
        assert counts["whole"] == whole
    if block == "selection":
        assert counts == {"visited": 3, "square": 4}


class _Ref:
    """An array read as a kernel reads its ref (``pl.ds`` slices)."""

    def __init__(self, x):
        self.x = x

    def __getitem__(self, idx):
        return self.x[tuple(
            slice(int(i.start), int(i.start) + i.size)
            if isinstance(i, pl.Slice) else i for i in idx)]


@pytest.mark.parametrize("length, block, tile", [
    (8192, 4, 512),   # the cell: L a multiple of the tile
    (512, 4, 32),     # its 16 x 16 tiles scaled down
    (96, 16, 16),     # blocks of a tile: no tile is masked
    (192, 16, 48),    # blocks that straddle tiles of 48
    (192, 4, 64),     # L not a multiple of the tile: tiles hold both copies
    (200, 4, 48),     # and padding
    (1024, "causal", 128),
    (200, "full", 64),              # keys past the length are masked
    (300, "selection", 256)])
def test_each_visited_tile_masks_as_the_predicate(length, block, tile):
    """For every (query tile, key tile) pair the kernels visit, the mask the
    tile builds from its rows' and columns' indices (the run's own form and
    the general one) is the mode's predicate; the forward's and dq's tiles,
    and the dkv kernel's transposed ones; every tile they skip is empty
    under it.  Block-diffusion runs name a copy exactly where L is a
    multiple of the tiles."""
    n = -(-length // tile)
    mask, words, keep = _mode(length, block, tile)
    forms = set()

    def check(mask, want, copy):
        forms.add(copy)
        for form in {copy, True}:
            np.testing.assert_array_equal(mask(form), want, err_msg=str(
                (form, copy)))

    def rows(w, blk):   # the words of one row block, as a kernel sees them
        return [_Ref(x[:, blk * tile // 32:(blk + 1) * tile // 32])
                for x in w]

    seen = set()
    for i in range(n):
        for lo, hi, copy in mask.k_runs(np.int64(i), n, np):
            for j in range(int(lo), int(hi)):
                seen.add((i, j))
                want = keep[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
                if not copy:
                    assert want.all()
                    continue
                check(lambda f: mask.tile(f, jnp.int32(i), jnp.int32(j),
                                          rows(words, i)), want, copy)
    seen_t = set()
    for j in range(n):
        for lo, hi, copy in mask.q_runs(np.int64(j), n, np):
            for i in range(int(lo), int(hi)):
                seen_t.add((i, j))
                want = keep[i * tile:(i + 1) * tile, j * tile:(j + 1) * tile]
                if not copy:
                    assert want.all()
                    continue
                check(lambda f: mask.tile_t(f, jnp.int32(j), jnp.int32(i),
                                            rows(words[1:], j)), want.T,
                      copy)
    assert seen == seen_t
    for i in range(n):
        for j in range(n):
            if (i, j) not in seen:
                assert not keep[i * tile:(i + 1) * tile,
                                j * tile:(j + 1) * tile].any()
    if not isinstance(block, int):
        assert forms == {True}
    elif block < tile:
        aligned = (length // 2) % tile == 0
        assert forms == ({"noisy", "clean"} if aligned else {True})
    else:
        assert not forms


def test_the_cells_masked_tiles_all_take_the_per_copy_form():
    """At L = 4,096, blocks of 4 and 512 x 512 tiles a traced call reports
    its 24 masked tiles of 80 all of one key copy: one compare a score."""
    telemetry.reset()
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: fa.flash_attention(
        q, k, v, block_diffusion=4), q, kv, kv)
    (row,) = telemetry.summary()["bd_tiles"]
    assert (row["length"], row["block"], row["block_q"], row["block_k"]) == (
        8192, 4, 512, 512)
    assert (row["visited"], row["whole"], row["masked"], row["per_copy"],
            row["general"]) == (80, 56, 24, 24, 0)


def test_a_traced_call_tells_telemetry_its_tiles():
    telemetry.reset()
    q = jnp.zeros((1, 2, 64, 8))
    jax.jit(lambda q: fa.flash_attention(q, q, q, block_diffusion=4,
                                         block_q=16, block_k=16))(q)
    (row,) = telemetry.summary()["bd_tiles"]
    assert (row["length"], row["block"], row["block_q"]) == (64, 4, 16)
    assert row["visited"] == row["whole"] + row["masked"] < row["square"]


def test_block_diffusion_refuses_what_the_mask_does_not_describe():
    q = jnp.zeros((1, 1, 24, 8))
    for kw in ({"block_diffusion": 4, "causal": True},
               {"block_diffusion": 3}, {"block_diffusion": 8}):
        with pytest.raises(ValueError):
            fa.flash_attention(q, q, q, **kw)
    with pytest.raises(ValueError):
        fa.flash_attention(q, q[:, :, :16], q[:, :, :16], block_diffusion=4)


# the Mosaic bodies of the kernels in each mask mode as the commit before the
# modes became objects lowered them, printed without locations (sha256, first
# 20 hex digits; fwd, dq, dkv): (length, group, mode) -> bodies
PARENT_BODIES = {
    "causal": (1024, 2, {"causal": True}, (
        "97d104ff72cfd28513a9", "56add080d14af5360fcb",
        "8a2b953ff358c3c3175a")),
    "full, padded": (1000, 1, {}, (
        "3561fe088684616d4515", "1090a4a33d203656b31f",
        "f428e6a3363011bb077f")),
    "block diffusion, per copy": (1024, 2, {"block_diffusion": 4}, (
        "3ec4bb417377d317d1c3", "6ab8d559ff607d630f47",
        "80f75c434a23bda8bd53")),
    "block diffusion, general": (1200, 2, {"block_diffusion": 4}, (
        "f38f597cc59b56dd4f3b", "bdb370a30cfaca137e7e",
        "0056cfce9e96d9198f70")),
    "selection": (1024, 2, {"select": True}, (
        "51a41db13e36e780682d", "ce09087370ce3544f5cc",
        "9c81911b60de8523a5aa")),
}


def _bodies(length, group, mode):
    """The (kernel names, body hashes) of the forward and both gradients of
    one call in ``mode``: ``flash_attention``'s keywords, or ``{"select":
    True}`` for ``sparse_flash_attention`` over int32 words."""
    from jax._src.lib.mlir import ir

    q = jax.ShapeDtypeStruct((1, 4 * group, length, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 4, length, 128), jnp.bfloat16)
    args = (q, kv, kv)
    if mode.get("select"):
        lp = -(-length // fa.select_block(length)) * fa.select_block(length)
        words = jax.ShapeDtypeStruct((1, lp // 32, lp), jnp.int32)
        args += (words, words)

        def attend(q, k, v, bits, bits_t):
            return fa.sparse_flash_attention(q, k, v, bits, bits_t)[0]
    else:
        def attend(q, k, v):
            return fa.flash_attention(q, k, v, **mode)

    def loss(*a):
        return attend(*a).astype(jnp.float32).sum()

    with pk.compute_on("tpu"):
        exp = jax.export.export(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                                platforms=["tpu"])(*args)
    text = exp.mlir_module()
    names = re.findall(r'kernel_name = "(\w+)"', text)
    out = []
    for body in re.findall(r'\\22body\\22: \\22([^\\]*)\\22', text):
        ctx = ir.Context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(body))
            asm = module.operation.get_asm(enable_debug_info=False)
        out.append(hashlib.sha256(asm.encode()).hexdigest()[:20])
    return names, tuple(out)


@pytest.mark.parametrize("mode", sorted(PARENT_BODIES))
def test_each_mask_mode_lowers_as_it_did(mode):
    length, group, kwargs, want = PARENT_BODIES[mode]
    names, bodies = _bodies(length, group, kwargs)
    assert names == ["mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv"]
    assert bodies == want


# ---------------------------------------------------------------------------
# routing and the experts
# ---------------------------------------------------------------------------
def test_softmax_routing_renormalised_over_the_chosen_by_hand():
    logits = jnp.log(jnp.asarray([[0.1, 0.4, 0.2, 0.3],
                                  [0.25, 0.25, 0.05, 0.45]]))
    experts, w = moe_ops.moe_route_softmax(logits, top_k=2,
                                           norm_topk_prob=True)
    np.testing.assert_array_equal(experts, [[1, 3], [3, 0]])
    np.testing.assert_allclose(w, [[0.4 / 0.7, 0.3 / 0.7],
                                   [0.45 / 0.7, 0.25 / 0.7]], rtol=1e-6)
    # ZAYA's call (a balancing bias, no renormalisation) is unchanged: the
    # chosen probabilities themselves
    bias = jnp.asarray([0.0, 0.0, 0.5, 0.0])
    experts, w = moe_ops.moe_route_softmax(logits, bias, top_k=2)
    np.testing.assert_array_equal(experts, [[2, 1], [2, 3]])
    np.testing.assert_allclose(w, [[0.2, 0.4], [0.05, 0.45]], rtol=1e-6)
    _e, w2 = moe_ops.moe_route_softmax(logits, bias, top_k=2,
                                       norm_topk_prob=False)
    np.testing.assert_array_equal(w, w2)


def _moe_params(d, f, wide):
    return {"router_weight": _rand(30, (wide, d), 0.5),
            "experts_gate_weight": _rand(32, (wide, d, f), 0.2),
            "experts_up_weight": _rand(33, (wide, d, f), 0.2),
            "experts_down_weight": _rand(34, (wide, f, d), 0.2)}


def _moe(P, d, f, wide, first, count):
    from mxnet_tpu.models.nemotron_h import MoELayer

    moe = MoELayer(d, wide, (first, count), top_k=2, expert_width=f,
                   shared_width=0, routed_scaling_factor=1.0,
                   scoring="softmax", activation="swiglu", prefix="moe_")
    moe.initialize(mx.init.Zero(), ctx=mx.cpu())
    assert {n[len("moe_"):] for n in moe.collect_params()} == set(P) | {
        "load", "load_max"}
    for name, p in moe.collect_params().items():
        key = name[len("moe_"):]
        if key in P:
            value = P[key]
            if key.startswith("experts_"):
                value = value[first:first + count]
            p.set_data(nd.array(value))
    return moe


@pytest.mark.parametrize("chips", [2, 4])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref, chips):
    """Eight experts over ``chips`` chips, two choices a token renormalised:
    the shares' routed parts add up to the reference's whole layer, and each
    share is the reference's own share."""
    d, f, wide = 32, 16, 8
    cfg = dict(TINY, num_experts=wide, num_experts_published=wide,
               experts_held_first=0)
    P = _moe_params(d, f, wide)
    u = _rand(38, (2, 10, d))
    whole = ref.experts_layer(P, "", u, cfg)
    total = np.zeros(u.shape, np.float32)
    count = wide // chips
    for i in range(chips):
        out, load = _moe(P, d, f, wide, i * count, count)(nd.array(u))
        mine = {k: v[i * count:(i + 1) * count] if k.startswith("experts_")
                else v for k, v in P.items()}
        part = ref.experts_layer(mine, "", u, cfg, first=i * count,
                                 count=count)
        np.testing.assert_allclose(out.asnumpy(), part, rtol=2e-5, atol=2e-5)
        total += out.asnumpy()
        assert load.shape == (count,)
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------
def _weights(ref, seed=3):
    sys.path.insert(0, _REPO)
    from benchmark import weights

    return weights, weights.make_weights(ref.param_spec(TINY), seed,
                                         "float32")


def _net(ref, seed=3):
    from mxnet_tpu.models.sdar import sdar

    weights, w = _weights(ref, seed)
    net = sdar(**KWARGS)
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    weights.install(net, w, mx.cpu())
    return net, w


def _strip(net, tree):
    n = len(net.prefix)
    return {k[n:]: v for k, v in tree.items()}


def test_the_noise_follows_the_key_and_the_schedule(ref):
    from mxnet_tpu.models.sdar import block_noise

    tokens = jnp.arange(4 * 4096, dtype=jnp.int32).reshape(4, 4096) % 30
    key = jax.random.PRNGKey(11)
    x_t, w, share = block_noise(tokens, jax.random.split(key)[1], 4, 39,
                                1e-3)
    want_x, want_w = ref.noise(TINY, tokens, key)
    np.testing.assert_array_equal(x_t, want_x)
    np.testing.assert_allclose(w, want_w)
    m = np.asarray(x_t == 39)
    # a block's positions share one level: the weights of its masked ones
    # are equal, and about half the positions are masked (E t = 0.5)
    wb = np.asarray(w).reshape(4, -1, 4)
    top, low = wb.max(-1), np.where(wb > 0, wb, np.inf).min(-1)
    assert np.all((top == 0) | (top == low)) and top.max() <= 1e3
    assert abs(float(share) - 0.5) < 0.02 and abs(m.mean() - share) < 1e-6
    assert np.all((np.asarray(w) > 0) == m)


def test_the_logits_and_weights_match_the_reference(ref):
    net, w = _net(ref)
    tokens = np.random.default_rng(0).integers(0, 39, (2, 12), dtype=np.int32)
    key = jax.random.PRNGKey(5)
    mx.random.seed(5)
    # the block draws the step's first key: split(PRNGKey(5))[1] eagerly
    logits, weight = net(nd.array(tokens, dtype="int32"))
    want, want_w = jax.jit(lambda w, t, k: ref.forward(TINY, w, t, k))(
        w, jnp.asarray(tokens), key)
    assert logits.shape == (2, 12, 40) and weight.shape == (2, 12)
    np.testing.assert_allclose(weight.asnumpy(), want_w)
    np.testing.assert_allclose(logits.asnumpy(), want, atol=2e-5)
    # the leaked mask moves the logits: the comparison can see it
    leaked, _ = ref.forward(TINY, w, jnp.asarray(tokens), key, leak=True)
    assert float(np.abs(leaked - want).max()) > 1e-3


def test_the_stack_matches_the_reference_loss_every_leafs_gradient_three_steps(
        ref):
    from benchmark.programs.block_diffusion import block_diffusion
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    telemetry.reset()
    mx.random.seed(7)
    net, w = _net(ref)
    tokens = np.random.default_rng(1).integers(0, 39, (2, 12), dtype=np.int32)
    step = DataParallelStep(
        net, block_diffusion(), mesh=local_mesh(devices=jax.devices()[:1]),
        optimizer="adam", optimizer_params={
            k: v for k, v in TINY["optimizer"].items() if k != "name"})
    (x,), y = step.stage(nd.array(tokens, dtype="int32"),
                         nd.array(tokens.astype(np.float32)))
    losses = [float(step.step(x, y))]
    got = {k: np.asarray(v) / (1 - 0.9)           # Adam's first moment
           for k, v in _strip(net, step.opt_state[0]).items()}
    step.drain()
    (name, (share,)), = telemetry.aux_readings("bd_masked").items()
    assert name.endswith("noise_masked") and 0 < share < 1
    losses += [float(step.step(x, y)) for _ in range(2)]
    want = {}
    _key, step_key = jax.random.split(jax.random.PRNGKey(7))
    x_t, wts = ref.noise(TINY, jnp.asarray(tokens), step_key)
    assert abs(float(np.mean(np.asarray(x_t) == 39)) - share) < 1e-6
    total = ref._gradient(
        TINY, ref._fns(ref._freeze(TINY), None, None, False), dict(w),
        jnp.asarray(tokens).reshape(1, 2, 12), x_t.reshape(1, 2, 12),
        wts.reshape(1, 2, 12),
        lambda p, g: want.update({p + k: v for k, v in g.items()}),
        jnp.float32(1 / 24))
    assert set(got) == set(want)
    for name in sorted(want):
        scale = float(np.abs(want[name]).max()) + 1e-8
        np.testing.assert_allclose(got[name] / scale, want[name] / scale,
                                   atol=2e-4, err_msg=name)
    still = sorted(k for k, v in want.items() if np.abs(v).max() == 0)
    assert [k.rsplit("_", 1)[-1] for k in still] == [
        "load", "max", "load", "max", "masked"]
    out = ref.train(TINY, w, tokens, 7, 3, 2)
    np.testing.assert_allclose(losses[0], float(total), rtol=1e-5)
    np.testing.assert_allclose(losses, out["loss"], rtol=2e-5)
    now = _strip(net, step.params)
    for name, delta in out["delta_norm"].items():
        if name.endswith(("_load", "_load_max", "_masked")):   # aux
            continue
        mine = float(np.linalg.norm(np.asarray(now[name], np.float32)
                                    - np.asarray(w[name], np.float32)))
        np.testing.assert_allclose(mine, delta, rtol=2e-2, atol=1e-7,
                                   err_msg=name)
    # every layer is recomputed under the one rule, the routers held
    assert telemetry.summary()["recompute_kept"]["layers"] == 2
    for name in want:
        if name.endswith("router_weight"):
            np.testing.assert_array_equal(now[name], w[name])
    step.drain()
    assert len([k for k in telemetry.moe_load() if k.endswith("_load")]) == 2


# ---------------------------------------------------------------------------
# the kernels at the cell's widths, compiled for a described v5e chip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no libtpu to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_block_diffusion_kernels_compile_at_the_cells_widths(one_chip):
    """8,192 positions, 32 query heads over 4 of 128, bf16: what interpret
    mode cannot show (Mosaic refused a select between boolean vectors)."""
    q = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4, 8192, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=4).astype(
            jnp.float32).sum()

    with pk.compute_on("tpu"):
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile()
    text = compiled.as_text()
    assert sorted(set(re.findall(r"mx_flash_(fwd|dq|dkv)", text))) == [
        "dkv", "dq", "fwd"]
    assert not re.search(r"\[\d*,?8192,8192\]", text)


def test_the_general_form_compiles_for_the_chip(one_chip):
    """2L = 1,200 over 512 x 512 tiles: tiles that hold both copies and
    padding take the general form, which Mosaic must take too."""
    q = jax.ShapeDtypeStruct((1, 4, 1200, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2, 1200, 128), jnp.bfloat16,
                              sharding=one_chip)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=4).astype(
            jnp.float32).sum()

    assert fa.bd_tiles(1200, 4)["general"] > 0
    with pk.compute_on("tpu"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, kv, kv).compile().as_text()
    assert sorted(set(re.findall(r"mx_flash_(fwd|dq|dkv)", text))) == [
        "dkv", "dq", "fwd"]


@pytest.mark.parametrize("shape, rows", [
    ((16384, 6, 8, 128), 16384),     # the Nemotron cell: as before
    ((4096, 4, 8, 64), 4096),        # the Xing cell: as before
    ((8192, 1, 16, 16), 8192),       # the ZAYA cell: one chunk, no loop
    ((8192, 8, 16, 128), 16384),     # twice the even pairs
    ((8192, 8, 16, 128, sdar.CHUNK_COVER), 32768),   # the SDAR cell
    ((8192, 8, 16, 128, 8), 65536),  # all that can land: no loop
    ((8192, 8, 16, None), 8192)])    # no width given: as many as tokens
def test_a_chunk_holds_its_cover_of_the_pairs_an_even_router_lands(shape,
                                                                   rows):
    """A chunk of the experts' loop runs whole; sized to the tokens alone,
    the SDAR cell's 16 of 128 experts at 8 choices land one chunk's worth on
    average, so the step's work followed the routing (PERF.md section 6)."""
    got, worst = moe_ops.chunk_rows(*shape)
    assert got == rows and worst == shape[0] * min(shape[1], shape[2])


def test_the_sdar_layers_pass_their_cover_to_the_loop():
    """The model's expert layers size their chunks by ``CHUNK_COVER``; the
    other families keep twice the even pairs."""
    from mxnet_tpu.models.nemotron_h import MoELayer

    net = sdar.sdar(vocab_size=64, hidden_size=32, num_layers=1,
                    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                    num_experts=8, experts_held=(0, 4), num_experts_per_tok=2,
                    moe_intermediate_size=16)
    assert net.layers[0].moe._cover == sdar.CHUNK_COVER
    assert MoELayer(32, 8, (0, 4), 2, 16)._cover == 2


@pytest.mark.parametrize("cover", [1, 2, 4])
def test_the_gather_sum_gives_what_the_walk_over_landed_rows_gives(cover):
    """``gather_sum`` (a gather of every token's choices, fixed in size)
    against the walk over the landed rows: the output and every gradient,
    with a loop of three chunks (cover 1: 128 rows a chunk of the 288 pairs
    that can land), of two, and with one."""
    tokens, d, f, n, held, k = 96, 16, 8, 16, 6, 3
    keys = jax.random.split(jax.random.PRNGKey(7), 6)
    data = jax.random.normal(keys[0], (tokens, d))
    experts = jax.vmap(lambda kk: jax.random.choice(
        kk, n, (k,), replace=False))(jax.random.split(keys[1], tokens))
    # one expert drawn by nearly every token: a chunk past twice its share
    experts = experts.at[:80, 0].set(2)
    experts = jnp.where((experts == 2) & (jnp.arange(k) > 0), 5, experts)
    weights = jax.random.uniform(keys[2], (tokens, k))
    mats = [jax.random.normal(kk, s) * 0.3 for kk, s in zip(
        keys[3:], [(held, d, f), (held, d, f), (held, f, d)])]

    def run(gather_sum, data, weights, *mats):
        out, _ = moe_ops.moe_experts(
            data, experts, weights, mats[1], mats[2], gate_weight=mats[0],
            first=1, activation="swiglu", n_experts=n, cover=cover,
            gather_sum=gather_sum)
        return out

    def loss(gather_sum, *args):
        return jnp.sum(run(gather_sum, *args) * jnp.cos(jnp.arange(d)))

    args = (data, weights, *mats)
    np.testing.assert_allclose(run(True, *args), run(False, *args),
                               rtol=1e-5, atol=1e-6)
    got = jax.grad(loss, argnums=tuple(range(1, 6)))(True, *args)
    want = jax.grad(loss, argnums=tuple(range(1, 6)))(False, *args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)
