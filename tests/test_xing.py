"""The mechanisms ``models/xing.py`` brought, at small sizes on the CPU: flash
attention with a value width of its own (and its equal-width form
unchanged), YaRN's rotary frequencies, the hyper-connection ops and their
Sinkhorn iterations, the gated expert at four choices a token through the
chunk loop, the shares of an expert layer, and the stack with its prediction
module through ``DataParallelStep`` against the plain reference
(``benchmark/references/xing4_0_29b_a4b.py``)."""
import hashlib
import importlib.util
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.ops import contrib_ops, hc_ops, moe_ops
from mxnet_tpu.ops import pallas as pk
from mxnet_tpu.ops.pallas.flash_attention import flash_attention

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"vocab_size": 48, "hidden_size": 32,
        "layer_types": ["dense", "sparse"], "num_attention_heads": 4,
        "q_lora_rank": 16, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 4, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "intermediate_size": 40, "moe_intermediate_size": 24,
        "n_routed_experts": 2, "n_routed_experts_published": 4,
        "experts_held_first": 1, "num_experts_per_tok": 2,
        "n_shared_experts": 1, "routed_scaling_factor": 2,
        "norm_topk_prob": True, "hc_mult": 4, "hc_sinkhorn_iters": 3,
        "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "num_nextn_predict_layers": 1,
        "mtp_loss_weight": 0.3, "rms_norm_eps": 1e-6,
        "initializer_range": 0.1, "router_lr_mult": 0.0, "dtype": "float32",
        "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                      "beta2": 0.95, "epsilon": 1e-8}}
KWARGS = dict(vocab_size=48, hidden_size=32, num_layers=2, first_k_dense=1,
              num_attention_heads=4, q_lora_rank=16, kv_lora_rank=16,
              qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=4,
              intermediate_size=40, moe_intermediate_size=24,
              n_routed_experts=4, experts_held=(1, 2), num_experts_per_tok=2,
              hc_sinkhorn_iters=3, router_lr_mult=0.0)
ITERS_20 = dict(TINY, hc_sinkhorn_iters=20)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "xing_reference", os.path.join(_REPO, "benchmark", "references",
                                       "xing4_0_29b_a4b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


# ---------------------------------------------------------------------------
# flash attention with a value width of its own
# ---------------------------------------------------------------------------
def _dense_attention(q, k, v, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    lq = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((lq, lq), bool)), s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("d, dv, length", [(24, 16, 40), (16, 24, 33)])
def test_flash_attention_at_unequal_widths_forward_and_all_gradients(
        d, dv, length):
    """Scores over ``d`` channels, values ``dv`` wide (latent attention is
    192 and 128), two blocks and a padded tail, interpreted on the CPU."""
    q, k = _rand(0, (2, 3, length, d)), _rand(1, (2, 3, length, d))
    v, ct = _rand(2, (2, 3, length, dv)), _rand(3, (2, 3, length, dv))
    scale = 0.37

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=True, sm_scale=scale,
                               block_q=16, block_k=16)

    out, pull = jax.vjp(kernel, q, k, v)
    want, pull0 = jax.vjp(lambda *a: _dense_attention(*a, scale), q, k, v)
    assert out.shape == (2, 3, length, dv)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for got, exp in zip(pull(ct), pull0(ct)):
        assert got.shape == exp.shape
        np.testing.assert_allclose(got, exp, rtol=1e-4, atol=1e-4)
    # the op passes the scale and the widths through on both of its paths
    op = nd._contrib_flash_attention(nd.array(q), nd.array(k), nd.array(v),
                                     causal=True, sm_scale=scale)
    np.testing.assert_allclose(op.asnumpy(), want, rtol=2e-5, atol=2e-5)


#: sha256 of the equal-width calls' jaxpr (forward, dq and dkv kernels with
#: their block specs) as the parent commit of PR 33 traced them for a TPU
PARENT_JAXPR = {
    (32, 2): "de29763163d84eacded15451c1a37778ea13a04540b4b9270be1babd1d5d771a",
    (8, 2): "0b78277e008a25b2d66c02e903fabdb5b5240a7af2c3d4159c57f57d453dca91"}


@pytest.mark.parametrize("heads", sorted(PARENT_JAXPR))
def test_equal_width_flash_calls_trace_as_they_did_before(heads):
    """The Nemotron cell's call (32 query heads over 2) and the ZAYA cell's
    (8 over 2), 128 wide: the value width the kernels now take changes
    nothing where it equals the scores' width."""
    h, hkv = heads
    q = jax.ShapeDtypeStruct((1, h, 1024, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, hkv, 1024, 128), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    with pk.compute_on("tpu"):
        text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))
    text = re.sub(r" at \S+:\d+", "", text)
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_JAXPR[heads]


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------
def test_yarn_frequencies_against_the_formula_at_factor_64(ref):
    dim, theta, factor, orig = 64, 10000.0, 64, 4096
    plain = theta ** (-np.arange(32) / 32)
    lo = math.floor(dim * math.log(orig / (32 * 2 * math.pi))
                    / (2 * math.log(theta)))
    hi = math.ceil(dim * math.log(orig / (1 * 2 * math.pi))
                   / (2 * math.log(theta)))
    assert (lo, hi) == (10, 23)
    ramp = np.clip((np.arange(32) - lo) / (hi - lo), 0, 1)
    want = plain / factor * ramp + plain * (1 - ramp)
    got = contrib_ops._yarn_blend(jnp.asarray(plain, jnp.float32), dim, theta,
                                  factor, 32, 1, orig)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], plain[23:] / 64, rtol=1e-6)
    cfg = dict(TINY, qk_rope_head_dim=64)
    np.testing.assert_allclose(ref.yarn_frequencies(cfg), want, rtol=1e-6)
    np.testing.assert_allclose(
        ref.softmax_scale(TINY), 16 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)


def test_rotary_with_yarn_turns_by_the_blended_frequencies_and_without_is_as_before():
    x = _rand(4, (2, 9, 3, 64))
    yarn = dict(yarn_factor=64, yarn_beta_fast=32, yarn_beta_slow=1,
                yarn_original_max=4096)
    freq = np.asarray(contrib_ops._yarn_blend(
        jnp.asarray(10000.0 ** (-np.arange(32) / 32), jnp.float32), 64,
        10000.0, 64, 32, 1, 4096))
    z = (np.asarray(x[..., :32]) + 1j * np.asarray(x[..., 32:])) * np.exp(
        1j * np.arange(9)[:, None] * freq)[None, :, None, :]
    got = nd._contrib_rotary(nd.array(x), theta=10000.0, **yarn).asnumpy()
    np.testing.assert_allclose(got, np.concatenate([z.real, z.imag], -1),
                               rtol=1e-5, atol=1e-5)

    def parent(data, theta=10000.0, fraction=1.0, axis=1):
        # the op as it was before it took YaRN's arguments
        d = data.shape[-1]
        rot = int(round(d * float(fraction)))
        half = rot // 2
        freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
        pos = jnp.arange(data.shape[axis], dtype=jnp.float32)
        shape = [1] * data.ndim
        shape[axis], shape[-1] = data.shape[axis], half
        angle = (pos[:, None] * freq[None, :]).reshape(shape)
        cos, sin = jnp.cos(angle), jnp.sin(angle)
        x = data.astype(jnp.float32)
        x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
            -1).astype(data.dtype)

    for kw in ({"theta": 5e6, "fraction": 0.5}, {}):
        np.testing.assert_array_equal(contrib_ops.rotary(x, **kw),
                                      parent(x, **kw))


# ---------------------------------------------------------------------------
# hyper-connections
# ---------------------------------------------------------------------------
def test_sinkhorn_gives_rows_and_columns_that_sum_to_one():
    # entries as the configuration starts them: N(0, 1) before exp
    m = hc_ops.sinkhorn(_rand(5, (4, 4, 50)), 20, 1e-6)
    np.testing.assert_allclose(m.sum(1), 1.0, atol=1e-3)
    np.testing.assert_allclose(m.sum(0), 1.0, atol=1e-3)
    assert float(m.min()) >= 0
    # one pass is not there yet: the iterations do the work
    once = hc_ops.sinkhorn(_rand(5, (4, 4, 50)), 1, 1e-6)
    assert float(jnp.abs(once.sum(1) - 1).max()) > 1e-2


def _mixing_leaves(n, d, key=6, b_scale=1.0):
    k = 2 * n + n * n
    return {"norm_gamma": 1 + _rand(key, (n * d,), 0.1),
            "phi_weight": _rand(key + 1, (k, n * d), 0.1),
            "a": jnp.array([0.5, 0.7, 0.9]),
            "b": _rand(key + 2, (k,), b_scale)}


def test_mixing_ops_match_the_reference_and_obey_the_clamp(ref):
    n, d = 4, 8
    P = _mixing_leaves(n, d)
    X = _rand(9, (2, 5, n, d))
    streams = X.reshape(2, 5, n * d)
    attrs = dict(n=n, iters=20, eps=1e-6, clamp_min=-30.0, clamp_max=30.0,
                 rms_eps=1e-6)
    c = hc_ops.mhc_coefficients(streams, P["norm_gamma"], P["phi_weight"],
                                P["a"], P["b"], **attrs)
    h_pre, h_post, h_res = ref.mixing(P, "", X, ITERS_20)
    want = jnp.concatenate([h_pre, h_post, h_res.reshape(2, 5, n * n)], -1)
    assert c.shape == (2, 5, 24) and c.dtype == jnp.float32
    np.testing.assert_allclose(c, want, rtol=1e-5, atol=1e-6)
    y = _rand(10, (2, 5, d))
    np.testing.assert_allclose(
        hc_ops.mhc_pre(streams, c, n=n),
        jnp.einsum("rli,rlid->rld", h_pre, X), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        hc_ops.mhc_post(streams, y, c, n=n).reshape(X.shape),
        jnp.einsum("rlij,rljd->rlid", h_res, X)
        + h_post[..., None] * y[:, :, None, :], rtol=1e-5, atol=1e-6)
    # past the clamp an entry counts as the clamp: +-100 reads as +-30, and a
    # narrower clamp changes the matrix
    far = dict(P, b=P["b"].at[8:].set(jnp.where(P["b"][8:] > 0, 100., -100.)))
    at = dict(P, b=P["b"].at[8:].set(jnp.where(P["b"][8:] > 0, 30., -30.)))
    zero_phi = jnp.zeros_like(P["phi_weight"])

    def res(leaves, **kw):
        return hc_ops.mhc_coefficients(
            streams, leaves["norm_gamma"], zero_phi, leaves["a"],
            leaves["b"], **dict(attrs, **kw))[..., 8:]

    assert bool(jnp.isfinite(res(far)).all())
    np.testing.assert_array_equal(res(far), res(at))
    assert float(jnp.abs(res(far) - res(far, clamp_min=-1.0,
                                        clamp_max=1.0)).max()) > 1e-3


def test_the_mix_is_differentiable_through_the_iterations(ref):
    n, d = 4, 8
    P = _mixing_leaves(n, d, key=11)
    X, y, ct = (_rand(14, (1, 6, n, d)), _rand(15, (1, 6, d)),
                _rand(16, (1, 6, n, d)))

    def program(X, P):
        s = X.reshape(1, 6, n * d)
        c = hc_ops.mhc_coefficients(s, P["norm_gamma"], P["phi_weight"],
                                    P["a"], P["b"])
        u = hc_ops.mhc_pre(s, c)
        return (hc_ops.mhc_post(s, y * u, c).reshape(X.shape) * ct).sum()

    def plain(X, P):
        h_pre, h_post, h_res = ref.mixing(P, "", X, ITERS_20)
        u = jnp.einsum("rli,rlid->rld", h_pre, X)
        out = jnp.einsum("rlij,rljd->rlid", h_res, X) \
            + h_post[..., None] * (y * u)[:, :, None, :]
        return (out * ct).sum()

    g1, g0 = jax.grad(program, (0, 1))(X, P), jax.grad(plain, (0, 1))(X, P)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the gated expert at four choices a token, through the chunk loop
# ---------------------------------------------------------------------------
def _gated_dense(u, experts, weights, mats, first):
    gate, up, down = mats
    out = jnp.zeros_like(u)
    for e in range(gate.shape[0]):
        w = jnp.where(experts == first + e, weights, 0).sum(-1)
        hid = jax.nn.silu(u @ gate[e]) * (u @ up[e])
        out = out + w[:, None] * (hid @ down[e])
    return out


def test_gated_experts_at_four_choices_run_more_than_one_chunk(monkeypatch):
    """Four choices a token over 6 experts, 3 of them held: the held ones
    draw 43 pairs of 24 tokens' 96, two chunks of 24 rows; forward and every
    gradient against a loop over the experts."""
    monkeypatch.setattr(moe_ops, "grouped_tiling",
                        lambda *a, **k: (8, 128, 128))
    tokens, d, f, count, first, k = 24, 16, 12, 3, 2, 4
    u = _rand(20, (tokens, d))
    mats = (_rand(21, (count, d, f), 0.3), _rand(22, (count, d, f), 0.3),
            _rand(23, (count, f, d), 0.3))
    experts, weights = moe_ops.moe_route(
        u, _rand(24, (6, d), 0.5), _rand(25, (6,), 0.05), top_k=k,
        scaling=2.0)
    ct = _rand(26, (tokens, d))

    def op(u, weights, mats):
        gate, up, down = mats
        return moe_ops.moe_experts(u, experts, weights, up, down, gate,
                                   first=first, activation="swiglu")

    out, pairs = jax.jit(op)(u, weights, mats)
    landed = int(((experts >= first) & (experts < first + count)).sum())
    assert int(pairs.sum()) == landed and landed > tokens
    want = _gated_dense(u, experts, weights, mats, first)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda *a: (op(*a)[0] * ct).sum(), (0, 1, 2))(
        u, weights, mats)
    g0 = jax.grad(lambda u, w, m: (_gated_dense(u, experts, w, m, first)
                                   * ct).sum(), (0, 1, 2))(u, weights, mats)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_the_combines_token_tile_follows_the_width_and_the_other_cells_keep_theirs():
    """At 3,584 columns a tile of 256 tokens would take 18 MB of the 16 a
    Mosaic call gets (the step compiled for the chip said so): 128 there;
    the Nemotron and ZAYA cells' shapes keep 256."""
    from mxnet_tpu.ops.pallas import moe_rows

    assert moe_rows._token_tile(4096, 3584, 8) == 128
    assert moe_rows._token_tile(16384, 2688, 8) == 256
    assert moe_rows._token_tile(8192, 2048, 16) == 256
    assert moe_rows._vmem(256, 3584, 8, 2) > 16 * 2 ** 20
    assert moe_rows.fits(4096, 4096, 3584, 8)


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref):
    """Eight chips with one expert each of 8, two choices a token: the routed
    parts add up and the shared expert counts once."""
    from mxnet_tpu.models.xing import XingMoE

    cfg = dict(TINY, n_routed_experts=8, n_routed_experts_published=8,
               experts_held_first=0)
    d, f = 32, 24
    P = {"router_weight": _rand(30, (8, d), 0.5),
         "e_score_correction_bias": _rand(31, (8,), 0.01),
         "experts_gate_weight": _rand(32, (8, d, f), 0.2),
         "experts_up_weight": _rand(33, (8, d, f), 0.2),
         "experts_down_weight": _rand(34, (8, f, d), 0.2),
         "shared_gate_proj_weight": _rand(35, (f, d), 0.2),
         "shared_up_proj_weight": _rand(36, (f, d), 0.2),
         "shared_down_proj_weight": _rand(37, (d, f), 0.2)}
    u = _rand(38, (2, 10, d))
    whole = ref.experts_layer(P, "", u, cfg)
    shared = ref.gated(P, "shared_", u)
    total = jnp.zeros_like(u)
    for i in range(8):
        moe = XingMoE(d, 8, (i, 1), top_k=2, expert_width=f, shared_width=f,
                      prefix="moe_")
        moe.initialize(mx.init.Zero(), ctx=mx.cpu())
        for name, p in moe.collect_params().items():
            key = name[len("moe_"):]
            if key in P:
                value = P[key]
                if key.startswith("experts_"):
                    value = value[i:i + 1]
                p.set_data(nd.array(value))
        out, load = moe(nd.array(u))
        total = total + out.asnumpy() - np.asarray(shared)
        assert load.shape == (1,)
    np.testing.assert_allclose(total + shared, whole, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# the stack
# ---------------------------------------------------------------------------
def _weights(ref, seed=3):
    sys.path.insert(0, _REPO)
    from benchmark import weights

    return weights, weights.make_weights(ref.param_spec(TINY), seed,
                                         "float32")


def _net(ref, seed=3):
    from mxnet_tpu.models.xing import xing

    weights, w = _weights(ref, seed)
    net = xing(**KWARGS)
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    weights.install(net, w, mx.cpu())
    return net, w


def _strip(net, tree):
    n = len(net.prefix)
    return {k[n:]: v for k, v in tree.items()}


def test_both_logits_match_the_reference(ref):
    net, w = _net(ref)
    tokens = np.random.default_rng(0).integers(0, 48, (2, 12), dtype=np.int32)
    logits, logits_mtp = net(nd.array(tokens, dtype="int32"))
    want = jax.jit(lambda w, t: ref.forward(TINY, w, t))(
        w, jnp.asarray(tokens))
    assert logits.shape == logits_mtp.shape == (2, 12, 48)
    np.testing.assert_allclose(logits.asnumpy(), want[0], atol=2e-5)
    np.testing.assert_allclose(logits_mtp.asnumpy(), want[1], atol=2e-5)
    assert float(np.abs(want[0] - want[1]).max()) > 1e-3
    # without the module the trunk alone, and no leaf of the module
    from mxnet_tpu.models.xing import xing

    trunk = xing(mtp=False, **KWARGS)
    assert not [k for k in trunk.collect_params() if "mtp" in k]


def test_the_stack_matches_the_reference_loss_every_leafs_gradient_three_steps(
        ref):
    from benchmark.programs.next_token_mtp import next_token_mtp
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    net, w = _net(ref)
    tokens = np.random.default_rng(1).integers(0, 48, (2, 12), dtype=np.int32)
    step = DataParallelStep(
        net, next_token_mtp(), mesh=local_mesh(devices=jax.devices()[:1]),
        optimizer="adam", optimizer_params={
            k: v for k, v in TINY["optimizer"].items() if k != "name"})
    (x,), y = step.stage(nd.array(tokens, dtype="int32"),
                         nd.array(tokens.astype(np.float32)))
    losses = [float(step.step(x, y))]
    got = {k: np.asarray(v) / (1 - 0.9)           # Adam's first moment
           for k, v in _strip(net, step.opt_state[0]).items()}
    # the module's cross-entropy reaches telemetry at drain: aux state, never
    # a sync inside a step
    step.drain()
    (name, (nll,)), = telemetry.aux_readings("mtp_loss").items()
    assert name.endswith("mtp_loss")
    losses += [float(step.step(x, y)) for _ in range(2)]
    want = {}
    scales = (jnp.float32(1 / 22), jnp.float32(0.3 / 20))
    total = ref._gradient(
        TINY, ref._fns(ref._freeze(TINY), None, None), dict(w),
        jnp.asarray(tokens).reshape(1, 2, 12),
        lambda p, g: want.update({p + k: v for k, v in g.items()}), scales)
    assert set(got) == set(want)
    for name in sorted(want):
        scale = float(np.abs(want[name]).max()) + 1e-8
        np.testing.assert_allclose(got[name] / scale, want[name] / scale,
                                   atol=2e-4, err_msg=name)
    still = sorted(k for k, v in want.items() if np.abs(v).max() == 0)
    # the aux leaves and the routers' correction: nothing else stands still
    assert [k.rsplit("_", 1)[-1] for k in still] == [
        "bias", "load", "max", "bias", "load", "max", "loss"]
    out = ref.train(TINY, w, tokens, 0, 3, 2)
    np.testing.assert_allclose(losses[0], float(total), rtol=1e-5)
    np.testing.assert_allclose(losses, out["loss"], rtol=2e-5)
    now = _strip(net, step.params)
    for name, delta in out["delta_norm"].items():
        if name.endswith(("_load", "_load_max", "mtp_loss")):   # aux
            continue
        mine = float(np.linalg.norm(np.asarray(now[name], np.float32)
                                    - np.asarray(w[name], np.float32)))
        np.testing.assert_allclose(mine, delta, rtol=2e-2, atol=1e-7,
                                   err_msg=name)
    # the loss without the module's term (a planted fault of the cell)
    bare = float(ref._gradient(
        TINY, ref._fns(ref._freeze(TINY), None, None), dict(w),
        jnp.asarray(tokens).reshape(1, 2, 12), lambda p, g: None,
        (scales[0], jnp.float32(0))))
    assert losses[0] - bare > 0.5
    # every layer and the module are recomputed under the one rule
    assert telemetry.summary()["recompute_kept"]["layers"] == 3
    np.testing.assert_allclose(nll, (losses[0] - bare) / 0.3, rtol=1e-4)
    step.drain()
    loads = [k for k in telemetry.moe_load() if k.endswith("_ffn_load")]
    assert len(loads) == 2          # the expert layer's and the module's
