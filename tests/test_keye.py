"""Keye-VL-2.0's language model (``models/keye.py``) and its learned sparse
attention (``ops/dsa_ops.py``, ``ops/pallas/dsa.py``, the flash kernels'
selection mode) against the plain reference
(``benchmark/references/keye_vl_2_0_30b_a3b.py``) on seeded weights, on the
CPU: the indexer's scores, the thresholds and the packed selection (rows
before ``topk``, a planted tie), the kernels in interpret mode against the
dense compositions (forward and gradients), the KL's target, which weights
each loss reaches, the experts' shares against the uncut layer, and a
recomputed layer that keeps its selection's words and the KL's target and
makes each once."""
import importlib
import importlib.util
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, nd, telemetry
from mxnet_tpu.ops import dsa_ops, recompute
from mxnet_tpu.ops.pallas import dsa

fa = importlib.import_module("mxnet_tpu.ops.pallas.flash_attention")

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SA = {"indexer_head_dim": 8, "indexer_num_heads": 2, "indexer_num_kv_heads": 1,
      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 8}
TINY = {"vocab_size": 40, "hidden_size": 32, "layer_types": ["sparse"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "num_experts": 3, "num_experts_published": 6, "experts_held_first": 2,
        "num_experts_per_tok": 2, "moe_intermediate_size": 16,
        "norm_topk_prob": True, "rope_theta": 10000000, "rms_norm_eps": 1e-6,
        "sa_config": SA, "indexer_rope_fraction": 0.5,
        "initializer_range": 0.1, "router_lr_mult": 0.0, "dtype": "float32",
        "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                      "beta2": 0.95, "epsilon": 1e-8}}
KWARGS = dict(vocab_size=40, hidden_size=32, num_layers=1,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              num_experts=6, experts_held=(2, 3), num_experts_per_tok=2,
              moe_intermediate_size=16, router_lr_mult=0.0,
              rope_theta=10000000.0, indexer_num_heads=2, indexer_head_dim=8,
              topk=8)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "keye_reference", os.path.join(_REPO, "benchmark", "references",
                                       "keye_vl_2_0_30b_a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rand(seed, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale


def _indexer_inputs(length, heads=2, dim=8, seed=0):
    qi = _rand(seed, (1, length, heads, dim))
    ki = _rand(seed + 1, (1, length, dim))
    w = _rand(seed + 2, (1, length, heads), 0.3)
    return qi, ki, w


# ---------------------------------------------------------------------------
# the indexer and its selection
# ---------------------------------------------------------------------------
def test_the_indexers_scores_agree_with_the_reference(ref):
    """The program's indexer block over a layer's weights, then its scores,
    against the reference's projections and scores."""
    from mxnet_tpu.models.keye import DSAIndexer

    d, length = 32, 20
    P = {"wq_weight": _rand(10, (16, d), 0.2), "wk_weight": _rand(11, (8, d),
                                                                   0.2),
         "k_norm_gamma": 1.0 + _rand(12, (8,), 0.1),
         "k_norm_beta": _rand(13, (8,), 0.1),
         "weights_proj_weight": _rand(14, (2, d), 0.2)}
    u = _rand(15, (1, length, d))
    block = DSAIndexer(d, heads=2, head_dim=8, rope_theta=1e7,
                       prefix="indexer_")
    block.initialize(mx.init.Zero(), ctx=mx.cpu())
    for name, p in block.collect_params().items():
        key = name[len("indexer_"):]
        if key in P:
            p.set_data(nd.array(P[key]))
    qi, ki, w = (x._data for x in block(nd.array(u)))
    rq, rk, rw = ref.indexer({"indexer_" + k: v for k, v in P.items()},
                             "indexer_", u, TINY)
    for a, b in ((qi, rq), (ki, rk), (w, rw)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    score = dsa_ops.index_scores(qi, ki, w)
    want = ref.index_scores(rq, rk, rw)
    causal = np.tril(np.ones((length, length), bool))
    np.testing.assert_allclose(np.where(causal, score[0], 0.0),
                               np.where(causal, want[0], 0.0),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(score[0])[~causal] == fa._NEG)


@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_thresholds_and_words_agree_with_the_references_top_k(ref, path):
    """Both paths' selection against the reference's own ``lax.top_k`` rule:
    rows before ``topk`` keep every earlier key, later rows ``topk`` of them
    or more where scores tie at the threshold (two heads: a quarter of the
    scores are exactly 0); the words unpack to the same pairs both ways
    round."""
    length, topk = 300, 40
    qi, ki, w = _indexer_inputs(length)
    if path == "dense":
        bits, bits_t = dsa_ops.select(qi, ki, w, topk)
    else:
        bits, bits_t = dsa_ops._selection_blocks(qi, ki, w, topk, length)
    pos = jnp.arange(length)
    want = ref.selection(ref.index_scores(qi, ki, w), pos, pos, topk)
    sel = dsa_ops.unpack(bits)[:, :length, :length]
    np.testing.assert_array_equal(sel, want)
    np.testing.assert_array_equal(
        dsa_ops.unpack(bits_t)[:, :length, :length], jnp.swapaxes(want, 1, 2))
    counts = np.asarray(sel.sum(-1))[0]
    assert list(counts[:topk]) == list(range(1, topk + 1))
    assert (counts[topk:] >= topk).all() and (counts[topk:] == topk).any()
    # padding rows and columns select nothing
    assert not dsa_ops.unpack(bits)[:, length:].any()
    assert not dsa_ops.unpack(bits)[:, :, length:].any()


def test_a_tie_at_the_threshold_is_in_on_both_paths():
    """Row 12's scores with the 4th and 5th largest equal: both are in (five
    keys), on the dense path and through the bisection kernel."""
    length, topk = 16, 4
    score = jnp.full((1, length, length), -1.0)
    score = score.at[0, 12, :13].set(jnp.asarray(
        [0.1, 0.9, 0.5, 0.5, 0.2, 0.7, 0.3, 0.05, 0.0, 0.6, 0.25, 0.15, 0.01]))
    theta = dsa_ops.thresholds(jnp.where(jnp.tril(jnp.ones((length, length),
                                                           bool)), score,
                                         fa._NEG), topk)
    assert float(theta[0, 12]) == 0.5
    padded = jnp.full((1, 256, 256), fa._NEG).at[:, :length, :length].set(
        jnp.where(jnp.tril(jnp.ones((length, length), bool)), score, fa._NEG))
    got = dsa.topk(jnp.int32(0), jnp.swapaxes(padded, 1, 2), topk,
                   interpret=True)
    assert float(got[0, 0, 12]) == 0.5
    assert int(jnp.sum(score[0, 12] >= got[0, 0, 12])) == 5


def test_a_selection_of_the_wrong_shape_or_mode_fails_when_traced():
    q = jnp.zeros((1, 4, 24, 8))
    k = jnp.zeros((1, 2, 24, 8))
    good = jnp.zeros((1, 8, 256), jnp.int32)
    fa.check_selection(q.shape, k.shape, good, good)
    for bad in (jnp.zeros((1, 8, 255), jnp.int32),
                jnp.zeros((2, 8, 256), jnp.int32),
                jnp.zeros((1, 8, 256), jnp.uint32)):
        with pytest.raises(ValueError):
            fa.sparse_flash_attention(q, k, k, bad, good)
        with pytest.raises(ValueError):
            fa.sparse_flash_attention(q, k, k, good, bad)
    with pytest.raises(ValueError):        # keys of another length
        fa.sparse_flash_attention(q, k[:, :, :16], k[:, :, :16], good, good)


# ---------------------------------------------------------------------------
# the kernels in interpret mode against the dense compositions
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def case():
    length, topk = 300, 40
    qi, ki, w = _indexer_inputs(length, seed=20)
    bits, bits_t = dsa_ops.select(qi, ki, w, topk)
    q = _rand(23, (1, 4, length, 32))
    k = _rand(24, (1, 2, length, 32))
    v = _rand(25, (1, 2, length, 32))
    return {"qi": qi, "ki": ki, "w": w, "bits": bits, "bits_t": bits_t,
            "q": q, "k": k, "v": v, "length": length}


def test_the_flash_kernels_under_a_selection_match_the_dense_composition(case):
    """``mx_flash_fwd``, ``mx_flash_dq`` and ``mx_flash_dkv`` in the new mode
    against the dense masked softmax: the output, the rows' logsumexp, and
    the gradients in q, k and v."""
    q, k, v, bits, bits_t = (case[n] for n in ("q", "k", "v", "bits",
                                               "bits_t"))
    weight = _rand(26, (1, 4, case["length"], 32))
    telemetry.reset()
    out, lse = fa.sparse_flash_attention(q, k, v, bits, bits_t)
    want, want_lse = dsa_ops.sparse_attention(q, k, v, bits, bits_t)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)
    (row,) = telemetry.summary()["dsa_tiles"]
    assert (row["visited"], row["square"], row["block"]) == (3, 4, 256)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, bits, bits_t)[0] * weight)

    got = jax.grad(loss(fa.sparse_flash_attention), (0, 1, 2))(q, k, v)
    exp = jax.grad(loss(dsa_ops.sparse_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_the_kl_kernels_match_the_dense_composition(case):
    """``mx_dsa_kl_fwd`` against the dense KL a query, and ``mx_dsa_kl_bwd``
    against autodiff of it in the indexer's queries, key and weights."""
    qi, ki, w = case["qi"], case["ki"], case["w"]
    q, k, bits, bits_t = case["q"], case["k"], case["bits"], case["bits_t"]
    _out, lse = dsa_ops.sparse_attention(q, k, case["v"], bits, bits_t)
    want = dsa_ops._kl_dense(qi, ki, w, q, k, lse, bits_t)
    got = dsa_ops._kl_kernels(qi, ki, w, q, k, lse, bits_t)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert float(jnp.min(want)) >= -1e-6 and float(jnp.max(want)) > 0.01
    weight = jnp.linspace(0.5, 1.5, case["length"])

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c, q, k, lse, bits_t)
                                       * weight)

    got = jax.grad(loss(dsa_ops._kl_kernels), (0, 1, 2))(qi, ki, w)
    exp = jax.grad(loss(dsa_ops._kl_dense), (0, 1, 2))(qi, ki, w)
    for a, b in zip(got, exp):
        np.testing.assert_allclose(a, b, rtol=1e-4,
                                   atol=1e-5 * float(jnp.abs(b).max()))


@pytest.mark.parametrize("length", [300, 1100])
def test_the_kl_forward_returns_the_heads_mean_probability(length):
    """``mx_dsa_kl_fwd``'s second result, the target its backward reads, is
    ``mean_probs`` on every causal tile (transposed, keys as rows; packed by
    query block), 0 off the selection and past the length."""
    qi, ki, w = _indexer_inputs(length, seed=30)
    bits, bits_t = dsa_ops.select(qi, ki, w, 40)
    q, k, v = (_rand(33 + i, (1, h, length, 32)) for i, h in enumerate(
        (4, 2, 2)))
    _out, lse = dsa_ops.sparse_attention(q, k, v, bits, bits_t)
    _blk, lp = dsa_ops.padded(length)
    _st, p = dsa.kl_fwd(*(dsa_ops._pad_to(x, 2, lp) for x in (q, k, lse)),
                        *dsa_ops._index_operands(qi, ki, w, lp), bits_t,
                        interpret=True)
    sel = jnp.swapaxes(dsa_ops.unpack(bits_t)[:, :length, :length], 1, 2)
    want = jnp.pad(dsa_ops.mean_probs(q, k, lse, sel),
                   ((0, 0), (0, lp - length), (0, lp - length)))
    blk = min(512, lp)
    n = lp // blk
    tiles = [want[:, a * blk:(a + 1) * blk, c * blk:(c + 1) * blk].mT
             for a in range(n) for c in range(a + 1)]
    assert p.shape == (1, n * (n + 1) // 2, blk, blk)
    np.testing.assert_allclose(p, jnp.stack(tiles, 1), rtol=0, atol=1e-6)


def test_the_tile_counter_reads_the_words(case):
    assert float(dsa_ops.tile_hit(case["bits"], case["length"])) == 1.0
    bits = case["bits"].at[:, 8:16, :256].set(0)     # tile (1, 0) emptied
    assert float(dsa_ops.tile_hit(bits, case["length"])) == pytest.approx(
        2 / 3)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def _net(ref, seed=3, layers=1):
    sys.path.insert(0, _REPO)
    from benchmark import weights

    from mxnet_tpu.models.keye import keye

    cfg = dict(TINY, layer_types=["sparse"] * layers)
    w = weights.make_weights(ref.param_spec(cfg), seed, "float32")
    net = keye(**dict(KWARGS, num_layers=layers))
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    weights.install(net, w, mx.cpu())
    return net, w


def test_the_model_agrees_with_the_reference(ref):
    net, w = _net(ref)
    tokens = np.random.default_rng(0).integers(0, 40, (2, 16)).astype(
        np.int32)
    logits, kl = net(nd.array(tokens, dtype="int32"))
    want, want_kl = jax.jit(lambda P, t: ref.forward(TINY, P, t))(
        w, jnp.asarray(tokens))
    np.testing.assert_allclose(logits.asnumpy(), want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(kl.asnumpy(), want_kl, rtol=1e-4, atol=1e-6)
    assert float(jnp.min(want_kl)) > 0


@pytest.mark.parametrize("part", ["kl", "cross_entropy"])
def test_each_loss_reaches_its_own_weights_only(ref, part):
    """The KL's gradient reaches the indexers' weights and nothing else; the
    cross-entropy's reaches everything else and no indexer weight."""
    net, _w = _net(ref)
    tokens = nd.array(np.random.default_rng(1).integers(0, 40, (1, 16)),
                      dtype="int32")
    params = net.collect_params()
    with autograd.record():
        logits, kl = net(tokens)
        if part == "kl":
            loss = kl.sum()
        else:
            lg = logits[:, :-1]
            loss = -(nd.log_softmax(lg, axis=-1) * nd.one_hot(
                tokens[:, 1:], 40)).sum()
    loss.backward()
    for name, p in params.items():
        if p.grad_req == "null" or name.endswith("router_weight"):
            continue
        norm = float(jnp.linalg.norm(p.grad()._data))
        indexer = "_indexer_" in name
        if (part == "kl") == indexer:
            assert norm > 0, name
        else:
            assert norm == 0, name


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref):
    """Sixteen of 128 experts is one share of eight: at eight experts over
    four chips, two choices a token renormalised, the shares' routed parts
    add up to the reference's whole layer."""
    from mxnet_tpu.models.nemotron_h import MoELayer

    d, f, wide, chips = 32, 16, 8, 4
    cfg = dict(TINY, num_experts=wide, num_experts_published=wide,
               experts_held_first=0)
    P = {"router_weight": _rand(30, (wide, d), 0.5),
         "experts_gate_weight": _rand(32, (wide, d, f), 0.2),
         "experts_up_weight": _rand(33, (wide, d, f), 0.2),
         "experts_down_weight": _rand(34, (wide, f, d), 0.2)}
    u = _rand(38, (2, 10, d))
    whole = ref.experts_layer(P, "", u, cfg)
    total = np.zeros(u.shape, np.float32)
    count = wide // chips
    for i in range(chips):
        moe = MoELayer(d, wide, (i * count, count), top_k=2, expert_width=f,
                       shared_width=0, routed_scaling_factor=1.0,
                       scoring="softmax", activation="swiglu",
                       gather_sum=True, prefix="moe_")
        moe.initialize(mx.init.Zero(), ctx=mx.cpu())
        for name, p in moe.collect_params().items():
            key = name[len("moe_"):]
            if key in P:
                value = P[key]
                if key.startswith("experts_"):
                    value = value[i * count:(i + 1) * count]
                p.set_data(nd.array(value))
        out, _load = moe(nd.array(u))
        total += out.asnumpy()
    np.testing.assert_allclose(total, whole, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the recomputed layer keeps its selection
# ---------------------------------------------------------------------------
LAYERS = 2
WORDS = 1 * (256 // 32) * 256 * 4     # int32 (B, Lp / 32, Lp) at Lp = 256


@pytest.fixture(scope="module")
def stack(ref):
    """A two-layer model's losses as a function of all its weights, as the
    training step takes their gradient (each layer under ``checkpointed``).
    Each check takes ``jax.grad`` anew: a traced function is cached, and
    the checks trace again after patching what the trace reads."""
    from mxnet_tpu.parallel.data_parallel import _block_apply_fn

    net, _w = _net(ref, seed=5, layers=LAYERS)
    fn, items = _block_apply_fn(net, mx.cpu(), True)
    params = {name: p.data()._data for name, p in items}
    tokens = jnp.asarray(np.random.default_rng(2).integers(0, 40, (1, 16)),
                         jnp.int32)

    def loss(params):
        (logits, kl), _aux = fn(params, jax.random.PRNGKey(0), tokens)
        return jnp.mean(jnp.square(logits)) + jnp.sum(kl)

    return loss, params


def _on(path, monkeypatch):
    if path == "kernels":
        monkeypatch.setattr(dsa_ops, "_compiled", lambda: True)


def _float_results(*xs):
    """``recompute.kernel_out`` that names a kernel's float results only:
    the selection's int32 words then fall to the rule's default."""
    return tuple(x if x.dtype == jnp.int32 else
                 jax.ad_checkpoint.checkpoint_name(x, recompute.KERNEL_OUT)
                 for x in xs)


def _selections(jaxpr):
    """name -> how often the jaxpr makes a selection, at any depth:
    ``top_k`` under ``mx_dsa_select`` (the dense path; the routers' own
    ``top_k`` is not counted), the ``mx_dsa_scores`` and ``mx_dsa_topk``
    calls (the kernels)."""
    counts = {}
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "pallas_call":
            name = eqn.params["name"]
        elif "mx_dsa_select" not in str(eqn.source_info.name_stack):
            name = None
        if name in ("top_k", "mx_dsa_scores", "mx_dsa_topk"):
            counts[name] = counts.get(name, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for key, n in _selections(sub).items():
                counts[key] = counts.get(key, 0) + n
    return counts


@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_a_recomputed_layer_makes_its_selection_once(stack, monkeypatch,
                                                     path):
    """The forward pass makes each layer's selection and its backward reads
    the kept words: one ``top_k`` (or one ``mx_dsa_scores`` and one
    ``mx_dsa_topk``) a layer.  Words left unnamed are made twice."""
    loss, params = stack
    _on(path, monkeypatch)
    once = ({"top_k": LAYERS} if path == "dense" else
            {"mx_dsa_scores": LAYERS, "mx_dsa_topk": LAYERS})
    assert _selections(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == once
    monkeypatch.setattr(recompute, "kernel_out", _float_results)
    assert _selections(jax.make_jaxpr(jax.grad(loss))(params).jaxpr) == {
        name: 2 * LAYERS for name in once}


@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_a_recomputed_layer_keeps_both_words(stack, monkeypatch, path):
    """``tally`` counts the two words of every layer, with their bytes, on
    top of what the rule keeps of the layer's other kernels and products."""
    loss, params = stack
    _on(path, monkeypatch)
    with recompute.tally() as kept:
        jax.make_jaxpr(jax.grad(loss))(params)
    monkeypatch.setattr(recompute, "kernel_out", _float_results)
    with recompute.tally() as unnamed:
        jax.make_jaxpr(jax.grad(loss))(params)
    assert kept.layers == unnamed.layers == LAYERS
    assert kept.tensors - unnamed.tensors == 2 * LAYERS
    assert kept.bytes - unnamed.bytes == 2 * LAYERS * WORDS


def _kernel_calls(jaxpr):
    """(name, operand shapes) of every Pallas call of the jaxpr, at any
    depth."""
    calls = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            calls.append((eqn.params["name"],
                          [v.aval.shape for v in eqn.invars]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            calls += _kernel_calls(sub)
    return calls


@pytest.mark.parametrize("named", [True, False])
def test_a_recomputed_layer_makes_the_kl_target_once(stack, monkeypatch,
                                                     named):
    """The KL's backward reads the target its forward made: one
    ``mx_dsa_kl_fwd`` a layer, and no ``mx_dsa_kl_bwd`` operand of q's
    shape.  With ``kernel_out`` naming nothing the recomputed layer runs
    ``mx_dsa_kl_fwd`` a second time."""
    loss, params = stack
    _on("kernels", monkeypatch)
    if not named:
        monkeypatch.setattr(recompute, "kernel_out", lambda *xs: xs)
    calls = _kernel_calls(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    fwd = [shapes for name, shapes in calls if name == "mx_dsa_kl_fwd"]
    bwd = [shapes for name, shapes in calls if name == "mx_dsa_kl_bwd"]
    assert len(fwd) == (1 if named else 2) * LAYERS
    assert len(bwd) == LAYERS
    q_shape = fwd[0][0]
    assert q_shape[1] == TINY["num_attention_heads"]
    assert not any(q_shape in shapes for shapes in bwd)


def test_a_recomputed_layer_keeps_the_kl_target(stack, monkeypatch):
    """``tally`` counts the KL's target, one f32 (B, T, blk, blk) tensor a
    layer, on top of what the rule keeps with the target left unnamed."""
    loss, params = stack
    _on("kernels", monkeypatch)
    _blk, lp = dsa_ops.padded(16)
    blk = min(512, lp)
    n = lp // blk
    target = (1, n * (n + 1) // 2, blk, blk)

    def all_but_target(*xs):
        return tuple(x if x.shape == target else
                     jax.ad_checkpoint.checkpoint_name(x, recompute.KERNEL_OUT)
                     for x in xs)

    with recompute.tally() as kept:
        jax.make_jaxpr(jax.grad(loss))(params)
    monkeypatch.setattr(recompute, "kernel_out", all_but_target)
    with recompute.tally() as unnamed:
        jax.make_jaxpr(jax.grad(loss))(params)
    assert kept.layers == unnamed.layers == LAYERS
    assert kept.tensors - unnamed.tensors == LAYERS
    assert kept.bytes - unnamed.bytes == LAYERS * math.prod(target) * 4


@pytest.mark.parametrize("path", ["dense", "kernels"])
def test_the_kept_selection_gives_the_plain_calls_gradients(stack,
                                                            monkeypatch, path):
    """Every leaf's gradient with the layers recomputed equals that of the
    layers called plainly."""
    from mxnet_tpu.models import keye as keye_mod

    loss, params = stack
    _on(path, monkeypatch)
    got = jax.jit(jax.grad(loss))(params)
    monkeypatch.setattr(keye_mod, "checkpointed",
                        lambda block, *xs: block(*xs))
    want = jax.jit(jax.grad(loss))(params)
    (indexer,) = [n for n in want if n.endswith("layer1_indexer_wq_weight")]
    assert float(jnp.linalg.norm(want[indexer])) > 0     # the KL's gradient
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=2e-5,
                                   atol=2e-5, err_msg=name)
