"""The mechanisms ``models/nemotron_h.py`` brought, at small sizes on the
CPU: the chunked state-space scan against the recurrence itself, the expert
layer's shares against the uncut layer, routing that drops no pair at any
skew, grouped-query flash attention (interpret mode) against dense attention
with K and V repeated, and the stack through ``DataParallelStep``.  The
oracles are the plain reference's own functions
(``benchmark/references/nemotron_twotower_30b_a3b.py``)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import moe_ops, pallas, ssm_ops

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def ref():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "nemotron_reference", os.path.join(
            _REPO, "benchmark", "references",
            "nemotron_twotower_30b_a3b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rand(key, shape, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


# ---------------------------------------------------------------------------
# the chunked scan against the recurrence
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("length", [1, 127, 128, 300])
def test_chunked_scan_matches_the_recurrence_values_and_gradients(ref, length):
    rows, heads, p, groups, n = 2, 4, 8, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(length), 8)
    x = _rand(ks[0], (rows, length, heads, p))
    dt = _rand(ks[1], (rows, length, heads)) - 3.0
    b = _rand(ks[2], (rows, length, groups, n))
    c = _rand(ks[3], (rows, length, groups, n))
    a_log, d, dt_bias = (_rand(ks[4], (heads,), 0.5),
                         _rand(ks[5], (heads,)), _rand(ks[6], (heads,), 0.3))
    ct = _rand(ks[7], (rows, length, heads, p))

    def chunked(x, dt, a_log, b, c, d, dt_bias):
        return ssm_ops.ssd_scan(x, dt, a_log, b, c, d, dt_bias, chunk=128)

    def plain(x, dt, a_log, b, c, d, dt_bias):
        return ref.recurrence(x, jax.nn.softplus(dt + dt_bias),
                              -jnp.exp(a_log), b, c, d)

    args = (x, dt, a_log, b, c, d, dt_bias)
    y0, vjp0 = jax.vjp(plain, *args)
    y1, vjp1 = jax.vjp(chunked, *args)
    np.testing.assert_allclose(y1, y0, rtol=2e-4, atol=2e-4)
    for g1, g0 in zip(vjp1(ct), vjp0(ct)):
        scale = float(jnp.abs(g0).max()) + 1e-6
        np.testing.assert_allclose(g1 / scale, g0 / scale, atol=3e-4)


def test_chunked_scan_keeps_a_state_per_chunk_not_per_position():
    """The backward's residuals hold no (positions x heads x P x N) array."""
    rows, length, heads, p, groups, n = 1, 256, 2, 8, 1, 16
    z = jnp.zeros
    jaxpr = jax.make_jaxpr(jax.grad(lambda x: ssm_ops.ssd_scan(
        x, z((rows, length, heads)), z((heads,)),
        z((rows, length, groups, n)), z((rows, length, groups, n)),
        z((heads,)), z((heads,)), chunk=64).sum()))(
            z((rows, length, heads, p)))
    per_position = rows * length * heads * p * n
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
             for v in eqn.outvars if hasattr(v.aval, "shape")]
    assert max(sizes) < per_position


def test_causal_conv1d_and_gated_norm_against_numpy():
    x = np.random.default_rng(0).normal(size=(2, 9, 6)).astype(np.float32)
    w = np.random.default_rng(1).normal(size=(6, 4)).astype(np.float32)
    bias = np.random.default_rng(2).normal(size=(6,)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(9):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += x[:, t - 3 + k] * w[:, k]
    want += bias
    got = nd._contrib_causal_conv1d(nd.array(x), nd.array(w), nd.array(bias))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
    z = np.random.default_rng(3).normal(size=(2, 9, 6)).astype(np.float32)
    gain = np.linspace(0.5, 1.5, 6).astype(np.float32)
    v = x * (z / (1 + np.exp(-z)))
    vg = v.reshape(2, 9, 2, 3)
    vg = vg / np.sqrt((vg ** 2).mean(-1, keepdims=True) + 1e-5)
    got = nd._contrib_gated_rms_norm(nd.array(x), nd.array(z), nd.array(gain),
                                     group_size=3)
    np.testing.assert_allclose(got.asnumpy(), vg.reshape(2, 9, 6) * gain,
                               rtol=1e-5, atol=1e-5)
    r = nd._contrib_rms_norm(nd.array(x), nd.array(gain)).asnumpy()
    np.testing.assert_allclose(
        r, x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) * gain,
        rtol=1e-5, atol=1e-5)
    assert nd._contrib_relu2(nd.array([-2.0, 3.0])).asnumpy().tolist() == \
        [0.0, 9.0]


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------
def _expert_cfg(wide, held, first, k=3, d=16, f=12, fs=20):
    return {"n_routed_experts": held, "n_routed_experts_published": wide,
            "experts_held_first": first, "num_experts_per_tok": k,
            "norm_topk_prob": True, "routed_scaling_factor": 2.5,
            "hidden_size": d, "moe_intermediate_size": f,
            "moe_shared_expert_intermediate_size": fs}


def _expert_weights(wide, d=16, f=12, fs=20, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {"router_weight": _rand(ks[0], (wide, d), 0.5),
            "e_score_correction_bias": _rand(ks[1], (wide,), 0.05),
            "experts_up_weight": _rand(ks[2], (wide, d, f), 0.3),
            "experts_down_weight": _rand(ks[3], (wide, f, d), 0.3),
            "shared_up_weight": _rand(ks[4], (fs, d), 0.3),
            "shared_down_weight": _rand(ks[5], (d, fs), 0.3)}


def _routed_share(u, w, first, count, k=3):
    experts, weights = moe_ops.moe_route(
        u, w["router_weight"], w["e_score_correction_bias"], top_k=k,
        scaling=2.5, norm_topk_prob=True)
    return moe_ops.moe_experts(
        u, experts, weights, w["experts_up_weight"][first:first + count],
        w["experts_down_weight"][first:first + count], first=first)


def test_the_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer(ref):
    wide, tokens = 32, 50
    w = _expert_weights(wide)
    u = _rand(jax.random.PRNGKey(7), (tokens, 16))
    uncut = ref._experts(w, "", u, _expert_cfg(wide, wide, 0), None)
    shared = ref._relu2(u @ w["shared_up_weight"].T) \
        @ w["shared_down_weight"].T
    total, landed = shared, 0
    for share in range(16):
        out, pairs = _routed_share(u, w, 2 * share, 2)
        total, landed = total + out, landed + int(pairs.sum())
    assert landed == tokens * 3            # every pair lands on one share
    np.testing.assert_allclose(total, uncut, rtol=2e-5, atol=2e-5)


def test_a_share_matches_the_reference_given_the_same_share_with_gradients(ref):
    wide, first, count = 16, 4, 4
    w = _expert_weights(wide, seed=3)
    u = _rand(jax.random.PRNGKey(1), (40, 16))
    cfg = _expert_cfg(wide, count, first)

    def plain(u, w):
        held = dict(w, experts_up_weight=w["experts_up_weight"][
            first:first + count], experts_down_weight=w[
                "experts_down_weight"][first:first + count])
        return ref._experts(held, "", u, cfg, None).sum()

    def program(u, w):
        shared = ref._relu2(u @ w["shared_up_weight"].T) \
            @ w["shared_down_weight"].T
        return (_routed_share(u, w, first, count)[0] + shared).sum()

    g0, g1 = jax.grad(plain, (0, 1))(u, w), jax.grad(program, (0, 1))(u, w)
    np.testing.assert_allclose(g1[0], g0[0], rtol=1e-4, atol=1e-4)
    for name in w:
        np.testing.assert_allclose(g1[1][name], g0[1][name], rtol=1e-4,
                                   atol=1e-4, err_msg=name)


@pytest.mark.parametrize("chosen,count", [((0, 9, 10), 4), ((0, 1, 2), 4)])
def test_every_token_sent_to_held_experts_loses_no_pair(monkeypatch, chosen,
                                                        count):
    """Every token's choices are ``chosen``: with one of them held one chunk
    (as many rows as tokens) carries a pair a token and two are skipped; with
    all three held the load is three times the tokens, the worst case, and
    all three chunks run."""
    monkeypatch.setattr(moe_ops, "grouped_tiling",
                        lambda *a, **k: (8, 128, 128))
    wide, tokens, d, f = 16, 24, 16, 12
    w = _expert_weights(wide, seed=5)
    bias = jnp.full((wide,), -5.0).at[jnp.array(chosen)].set(5.0)
    u = _rand(jax.random.PRNGKey(2), (tokens, d))
    experts, weights = moe_ops.moe_route(u, w["router_weight"], bias, top_k=3,
                                         scaling=2.5)
    assert sorted(set(np.asarray(experts).ravel().tolist())) == list(chosen)
    out, pairs = jax.jit(lambda *a: moe_ops.moe_experts(*a, first=0))(
        u, experts, weights, w["experts_up_weight"][:count],
        w["experts_down_weight"][:count])
    held = [e for e in chosen if e < count]
    assert int(pairs.sum()) == tokens * len(held)
    want = jnp.zeros_like(u)
    for e in held:
        gate = jnp.where(experts == e, weights, 0.0).sum(-1)
        hid = jnp.square(jnp.maximum(u @ w["experts_up_weight"][e], 0.0))
        want = want + gate[:, None] * (hid @ w["experts_down_weight"][e])
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_grouped_product_kernel_agrees_with_ragged_dot_in_interpret_mode():
    from jax.experimental.pallas.ops.tpu.megablox import ops as mb

    sizes = jnp.array([5, 0, 130, 57], jnp.int32)
    lhs = _rand(jax.random.PRNGKey(0), (256, 128))
    rhs = _rand(jax.random.PRNGKey(1), (4, 128, 128), 0.1)
    got = mb.gmm(lhs, rhs, sizes, jnp.float32, (128, 128, 128),
                 interpret=True)
    want = jax.lax.ragged_dot(lhs, rhs, sizes)
    np.testing.assert_allclose(got[:192], want[:192], rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# grouped-query flash attention
# ---------------------------------------------------------------------------
def _dense_gqa(q, k, v, causal):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[2], k.shape[2]), bool))
        s = jnp.where(mask, s, -1e30)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("length,causal", [(40, True), (256, True),
                                           (136, False)])
def test_grouped_query_flash_attention_matches_dense_with_repeated_kv(
        length, causal):
    ks = jax.random.split(jax.random.PRNGKey(length), 4)
    q = _rand(ks[0], (2, 8, length, 32))
    k = _rand(ks[1], (2, 2, length, 32))
    v = _rand(ks[2], (2, 2, length, 32))
    ct = _rand(ks[3], (2, 8, length, 32))
    y0, vjp0 = jax.vjp(lambda *a: _dense_gqa(*a, causal), q, k, v)
    y1, vjp1 = jax.vjp(lambda *a: pallas.flash_attention(
        *a, causal=causal, block_q=128, block_k=64), q, k, v)
    np.testing.assert_allclose(y1, y0, rtol=2e-4, atol=2e-4)
    for g1, g0 in zip(vjp1(ct), vjp0(ct)):
        assert g1.shape == g0.shape
        np.testing.assert_allclose(g1, g0, rtol=5e-4, atol=5e-4)


def test_flash_attention_op_takes_fewer_key_value_heads_on_the_dense_path():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (_rand(ks[0], (1, 4, 12, 8)), _rand(ks[1], (1, 2, 12, 8)),
               _rand(ks[2], (1, 2, 12, 8)))
    got = nd._contrib_flash_attention(nd.array(q), nd.array(k), nd.array(v),
                                      causal=True)
    np.testing.assert_allclose(got.asnumpy(), _dense_gqa(q, k, v, True),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        pallas.flash_attention(q, k[:, :1].repeat(3, 1), v, causal=True)


# ---------------------------------------------------------------------------
# the stack through the compiled step
# ---------------------------------------------------------------------------
TINY = dict(vocab_size=48, hidden_size=16, hybrid_override_pattern="ME*",
            mamba_num_heads=2, mamba_head_dim=8, ssm_state_size=8,
            n_groups=1, chunk_size=8, num_attention_heads=4,
            num_key_value_heads=2, head_dim=4, n_routed_experts=8,
            experts_held=(2, 4), num_experts_per_tok=2,
            moe_intermediate_size=8, moe_shared_expert_intermediate_size=12)


def _train(steps=2):
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.models.nemotron_h import nemotron_h
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    mx.random.seed(11)
    net = nemotron_h(**TINY)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    ce = gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(
        net, lambda lg, lb: ce(lg.reshape(-1, lg.shape[-1]), lb.reshape(-1)),
        mesh=local_mesh(devices=jax.devices()[:1]), optimizer="adam",
        optimizer_params={"learning_rate": 1e-2})
    tokens = np.random.default_rng(0).integers(0, 48, (2, 12), dtype=np.int32)
    (x,), y = step.stage(nd.array(tokens, dtype="int32"),
                         nd.array(tokens.astype(np.float32)))
    losses = [float(step.step(x, y)) for _ in range(steps)]
    telemetry.reset()
    step.drain()
    return losses, step, telemetry.moe_load()


@pytest.mark.parametrize("other", ["nothing recomputed",
                                   "everything recomputed"])
def test_the_stack_trains_and_recomputation_changes_no_number(monkeypatch,
                                                              other):
    """The rule of ``ops/recompute.py`` against a plain call, and to the
    bit against a bare ``jax.checkpoint`` (what it kept was made once)."""
    from mxnet_tpu.models import nemotron_h as model
    from mxnet_tpu.ops import recompute

    with_remat, step, _ = _train()
    if other == "nothing recomputed":
        monkeypatch.setattr(model, "checkpointed",
                            lambda block, *xs: block(*xs))
    else:
        monkeypatch.setattr(recompute, "policy", None)
    without, _, _ = _train()
    assert with_remat[1] < with_remat[0]
    if other == "nothing recomputed":
        np.testing.assert_allclose(with_remat, without, rtol=1e-6)
    else:
        assert with_remat == without
    frozen = [n for n in step.params if n.endswith("e_score_correction_bias")]
    assert frozen and all(n in step.opt_state[0] for n in frozen)


def test_router_load_is_aux_state_recorded_at_drain_only():
    _losses, step, loads = _train()
    names = sorted(loads)
    assert [n.rsplit("_mixer_", 1)[1] for n in names] == ["load", "load_max"]
    load, load_max = (np.asarray(loads[n]) for n in names)
    assert load.shape == (4,) and (load_max >= load - 1e-6).all()
    # relative to an even spread over all 8 experts: 24 tokens x 2 / 8
    pairs = load * 24 * 2 / 8
    np.testing.assert_allclose(pairs, np.round(pairs), atol=1e-4)
    assert 0 < pairs.sum() <= 24 * 2
    # the counters live in the step's state like BatchNorm's statistics
    assert all(n in step.params for n in names)


def test_expert_layer_is_told_its_share():
    from mxnet_tpu.models.nemotron_h import MoELayer

    with pytest.raises(ValueError):
        MoELayer(16, n_routed_experts=8, experts_held=(6, 4))
    layer = MoELayer(16, n_routed_experts=8, experts_held=(4, 4),
                     expert_width=8, shared_width=12, top_k=2)
    shapes = {k[len(layer.prefix):]: p.shape
              for k, p in layer.collect_params().items()}
    assert shapes["router_weight"] == (8, 16)             # routes over all 8
    assert shapes["experts_up_weight"] == (4, 16, 8)      # holds 4


def test_a_released_gradient_buffer_comes_back_on_demand():
    """``DataParallelStep`` frees the block's gradient buffers where it
    donates (a model's worth of device memory); ``grad()`` and
    ``sync_to_block`` restore them."""
    from mxnet_tpu import autograd, gluon

    net = gluon.nn.Dense(3, in_units=2)
    net.initialize(ctx=mx.cpu())
    w = net.weight
    assert w.data()._grad is not None
    w.release_grad()
    assert w._grad is None and w.data()._grad is None
    assert w.grad().shape == (3, 2)                  # back on demand
    with autograd.record():
        y = net(nd.ones((1, 2))).sum()
    y.backward()
    assert float(np.abs(w.grad().asnumpy()).sum()) > 0
    net.bias.grad_req = "null"
    net.bias.release_grad()
    with pytest.raises(mx.base.MXNetError):
        net.bias.grad()
