"""The mechanisms ``models/zaya.py`` brought, at small sizes on the CPU:
rotary position, the head-mixing convolution, the gated expert form of
``_contrib_moe_experts`` (and the ``relu2`` form unchanged), routing from
logits, the compressed convolutional attention sublayer, recomputation over
the pair ``(x, r)``, the tied head, and the stack through
``DataParallelStep`` against the plain reference
(``benchmark/references/zaya1_8b.py``)."""
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import contrib_ops, moe_ops, ssm_ops

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {"vocab_size": 48, "hidden_size": 32, "layer_types": ["hybrid"] * 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rope_parameters": {"hybrid": {"rope_theta": 5000000}},
        "num_experts": 4, "num_experts_per_tok": 1,
        "moe_intermediate_size": 24, "router_hidden_size": 16,
        "rms_norm_eps": 1e-5, "initializer_range": 0.1, "dtype": "float32",
        "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                      "beta2": 0.95, "epsilon": 1e-8}}
KWARGS = dict(vocab_size=48, hidden_size=32, num_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8,
              num_experts=4, moe_intermediate_size=24, router_hidden_size=16)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "zaya_reference", os.path.join(_REPO, "benchmark", "references",
                                       "zaya1_8b.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rand(key, shape, scale=1.0):
    return jax.random.normal(jax.random.PRNGKey(key), shape,
                             jnp.float32) * scale


# ---------------------------------------------------------------------------
# rotary position and the head-mixing convolution
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fraction", [0.5, 1.0])
def test_rotary_against_complex_rotation(fraction, ref):
    x = _rand(0, (2, 9, 3, 16))
    theta, rot = 5e6, int(16 * fraction)
    half = rot // 2
    z = np.asarray(x[..., :half]) + 1j * np.asarray(x[..., half:rot])
    angle = np.arange(9)[:, None] * theta ** (-np.arange(half) / half)
    z = z * np.exp(1j * angle)[None, :, None, :]
    want = np.concatenate([z.real, z.imag, np.asarray(x[..., rot:])], -1)
    got = nd._contrib_rotary(nd.array(x), theta=theta, fraction=fraction)
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.rotary(x, theta, fraction), want,
                               rtol=1e-5, atol=1e-5)
    # positions along another axis: heads before length
    moved = contrib_ops.rotary(x.transpose(0, 2, 1, 3), theta=theta,
                               fraction=fraction, axis=2)
    np.testing.assert_allclose(moved.transpose(0, 2, 1, 3), want, rtol=1e-5,
                               atol=1e-5)
    with pytest.raises(ValueError):
        contrib_ops.rotary(x, fraction=0.3)


def test_head_mixing_convolution_against_loops(ref):
    heads, k, d, length = 3, 2, 4, 7
    x = np.asarray(_rand(1, (2, length, heads * d)))
    w = np.asarray(_rand(2, (heads, k, d, d)))
    bias = np.asarray(_rand(3, (heads * d,)))
    want = np.zeros_like(x)
    for t in range(length):
        for i in range(k):
            if t - k + 1 + i >= 0:
                for h in range(heads):
                    want[:, t, h * d:(h + 1) * d] += \
                        x[:, t - k + 1 + i, h * d:(h + 1) * d] @ w[h, i]
    want += bias
    got = nd._contrib_causal_conv1d_heads(nd.array(x), nd.array(w),
                                          nd.array(bias))
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.conv_heads(x, w, bias), want, rtol=1e-5,
                               atol=1e-5)
    # the depthwise one at two taps, its second caller's width
    wa, ba = np.asarray(_rand(4, (heads * d, 2))), bias
    want = x * wa[:, 1] + np.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1] \
        * wa[:, 0] + ba
    np.testing.assert_allclose(ssm_ops.causal_conv1d(x, wa, ba), want,
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(ref.conv_depthwise(x, wa, ba), want,
                               rtol=1e-5, atol=1e-5)


def test_gelu_tanh_and_routing_from_logits():
    x = _rand(5, (6, 5))
    got = nd.LeakyReLU(nd.array(x), act_type="gelu_tanh").asnumpy()
    np.testing.assert_allclose(got, jax.nn.gelu(x, approximate=True),
                               rtol=1e-6, atol=1e-6)
    bias = jnp.array([0.0, 0.0, 0.0, 0.0, 5.0])
    experts, weights = moe_ops.moe_route_softmax(x, bias, top_k=1)
    assert np.asarray(experts).ravel().tolist() == [4] * 6   # the bias chose
    p = jax.nn.softmax(x, -1)
    np.testing.assert_allclose(weights[:, 0], p[:, 4], rtol=1e-6)  # not p + b
    g = jax.grad(lambda b: moe_ops.moe_route_softmax(x, b)[1].sum())(bias)
    assert not np.asarray(g).any()           # the bias takes no gradient
    experts, weights = moe_ops.moe_route_softmax(x, jnp.zeros(5), top_k=2)
    np.testing.assert_array_equal(experts, jax.lax.top_k(p, 2)[1])


# ---------------------------------------------------------------------------
# the gated expert form of the experts' op
# ---------------------------------------------------------------------------
def _gated_weights(count, d=16, f=12, seed=0):
    return {"gate": _rand(seed, (count, d, f), 0.3),
            "up": _rand(seed + 1, (count, d, f), 0.3),
            "down": _rand(seed + 2, (count, f, d), 0.3)}


def _gated_dense(u, experts, weights, w, first=0):
    out = jnp.zeros_like(u)
    for e in range(w["up"].shape[0]):
        gate = jnp.where(experts == first + e, weights, 0.0).sum(-1)
        hid = jax.nn.silu(u @ w["gate"][e]) * (u @ w["up"][e])
        out = out + gate[:, None] * (hid @ w["down"][e])
    return out


def _gated_op(u, experts, weights, w, first=0):
    return moe_ops.moe_experts(u, experts, weights, w["up"], w["down"],
                               w["gate"], first=first, activation="swiglu")


@pytest.mark.parametrize("load", ["even", "one_expert"])
def test_gated_experts_match_a_dense_loop_with_all_gradients(monkeypatch,
                                                             load):
    """Top-1 over 4 held experts: an even spread, and every token on one
    expert (as many rows as tokens on one group: no pair dropped)."""
    monkeypatch.setattr(moe_ops, "grouped_tiling",
                        lambda *a, **k: (8, 128, 128))
    tokens, count = 24, 4
    u, w = _rand(7, (tokens, 16)), _gated_weights(count)
    experts = (jnp.arange(tokens) % count if load == "even"
               else jnp.full((tokens,), 2)).astype(jnp.int32)[:, None]
    weights = jax.nn.sigmoid(_rand(8, (tokens, 1)))
    out, pairs = jax.jit(_gated_op)(u, experts, weights, w)
    assert int(pairs.sum()) == tokens
    assert pairs.tolist() == ([6] * 4 if load == "even" else [0, 0, 24, 0])
    np.testing.assert_allclose(out, _gated_dense(u, experts, weights, w),
                               rtol=2e-5, atol=2e-5)
    ct = _rand(9, (tokens, 16))
    g0 = jax.grad(lambda u, wt, w: (_gated_dense(u, experts, wt, w)
                                    * ct).sum(), (0, 1, 2))(u, weights, w)
    g1 = jax.grad(lambda u, wt, w: (_gated_op(u, experts, wt, w)[0]
                                    * ct).sum(), (0, 1, 2))(u, weights, w)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_the_two_shares_of_a_gated_layer_add_up_to_the_uncut_layer(ref):
    """Experts 0 to 7 and 8 to 15 of 16, each told its share, against the
    reference's uncut expert sublayer."""
    cfg = dict(TINY, num_experts=16, hidden_size=16, moe_intermediate_size=12)
    spec = [(n[len("layer0_moe_"):], s, i) for n, s, i in ref.param_spec(
        dict(cfg, layer_types=["hybrid"])) if n.startswith("layer0_moe_")]
    P = {n: _rand(i, s, 0.5) for i, (n, s, _init) in enumerate(spec)}
    u, r = _rand(50, (2, 20, 16)), _rand(51, (2, 20, 16), 0.3)
    uncut, r_out = ref._experts(P, "", u, r, cfg, None)
    _r, experts, weights = ref.route(P, "", u, r, cfg)
    total, landed = 0.0, 0
    for first in (0, 8):
        w = {k: P[f"experts_{k}_weight"][first:first + 8]
             for k in ("gate", "up", "down")}
        out, pairs = _gated_op(u.reshape(-1, 16), experts, weights, w, first)
        total, landed = total + out, landed + int(pairs.sum())
        share = ref._experts(
            dict(P, **{f"experts_{k}_weight": w[k] for k in w}), "", u, r,
            dict(cfg, num_experts=8, num_experts_published=16,
                 experts_held_first=first), None)[0]
        np.testing.assert_allclose(out, share.reshape(-1, 16), rtol=2e-5,
                                   atol=2e-5)
    assert landed == 40                     # every pair lands on one share
    np.testing.assert_allclose(total, uncut.reshape(-1, 16), rtol=2e-5,
                               atol=2e-5)


# the experts' op of the commit before the gated form came (PR 30), its XLA
# path, kept here as the oracle of "relu2 unchanged to the bit"
@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _parent_all_chunks(data, flat_w, up, down, order, starts, ends, k, chunk):
    def body(i, out):
        _pairs, token, n_live, w, here = moe_ops._chunk_index(
            i, order, flat_w, starts, ends, k, chunk)
        y = _parent_products(data, up, down, token, here)[-1]
        return moe_ops._add_rows(out, y, token, w, here, n_live,
                                 fresh=i == 0)

    out = jax.lax.fori_loop(0, (ends[-1] + chunk - 1) // chunk, body,
                            jnp.zeros(data.shape, jnp.float32))
    return out.astype(data.dtype)


def _parent_products(data, up, down, token, here):
    rows = data[token]
    h = moe_ops._grouped_dot(rows, up, here)
    r = jnp.maximum(h, 0)
    a = r * r
    return rows, h, r, a, moe_ops._grouped_dot(a, down, here)


def _parent_fwd(data, flat_w, up, down, order, starts, ends, k, chunk):
    out = _parent_all_chunks(data, flat_w, up, down, order, starts, ends, k,
                             chunk)
    return out, (data, flat_w, up, down, order, starts, ends)


def _parent_bwd(k, chunk, res, d_out):
    data, flat_w, up, down, order, starts, ends = res
    f32 = jnp.float32

    def body(i, acc):
        d_data, d_flat_w, d_up, d_down = acc
        pairs, token, n_live, w, here = moe_ops._chunk_index(
            i, order, flat_w, starts, ends, k, chunk)
        live = jnp.arange(chunk) < n_live
        rows, h, r, a, y = _parent_products(data, up, down, token, here)
        taken = d_out[token].astype(f32)
        d_y = (taken * w[:, None]).astype(y.dtype)
        d_w = jnp.sum(taken * y.astype(f32), -1)
        d_flat_w = d_flat_w.at[pairs].add(jnp.where(live, d_w, 0))
        d_a = moe_ops._grouped_dot(d_y, down, here, transpose_rhs=True)
        d_down = moe_ops._grouped_dot_weights_grad(d_down, a, d_y, here)
        d_r = d_a * r
        d_h = jnp.where(h > 0, d_r + d_r, 0)
        d_rows = moe_ops._grouped_dot(d_h, up, here, transpose_rhs=True)
        d_up = moe_ops._grouped_dot_weights_grad(d_up, rows, d_h, here)
        d_data = moe_ops._add_rows(d_data, d_rows, token, live.astype(f32),
                                   here, n_live, fresh=i == 0)
        return d_data, d_flat_w, d_up, d_down

    acc = jax.lax.fori_loop(
        0, (ends[-1] + chunk - 1) // chunk, body,
        tuple(jnp.zeros(a.shape, f32) for a in (data, flat_w, up, down)))
    return tuple(g.astype(a.dtype) for g, a in zip(
        acc, (data, flat_w, up, down))) + (None, None, None)


_parent_all_chunks.defvjp(_parent_fwd, _parent_bwd)


def test_relu2_experts_are_unchanged_to_the_bit(monkeypatch):
    """One seeded case, three choices a token over 4 of 16 experts: the op
    with its ``activation`` argument against the op as it was, forward and
    every gradient, bit for bit."""
    monkeypatch.setattr(moe_ops, "grouped_tiling",
                        lambda *a, **k: (8, 128, 128))
    tokens, d, f, count, k = 24, 16, 12, 4, 3
    u, up, down = (_rand(20, (tokens, d)), _rand(21, (count, d, f), 0.3),
                   _rand(22, (count, f, d), 0.3))
    experts, weights = moe_ops.moe_route(
        u, _rand(23, (16, d), 0.5), _rand(24, (16,), 0.05), top_k=k,
        scaling=2.5)
    ct = _rand(25, (tokens, d))

    def parent(u, weights, up, down):
        chunk = 24
        key = jnp.where(experts < count, experts, count).reshape(-1)
        order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32),
                        (0, 3 * chunk - tokens * k))
        sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), 0)
        ends = jnp.cumsum(sizes)
        return _parent_all_chunks(u, weights.reshape(-1), up, down, order,
                                  ends - sizes, ends, k, chunk)

    def now(u, weights, up, down):
        return moe_ops.moe_experts(u, experts, weights, up, down)[0]

    args = (u, weights, up, down)
    y0, vjp0 = jax.vjp(parent, *args)
    y1, vjp1 = jax.vjp(now, *args)
    assert float(jnp.abs(y0).sum()) > 0
    np.testing.assert_array_equal(y1, y0)
    for g1, g0 in zip(vjp1(ct), vjp0(ct)):
        np.testing.assert_array_equal(g1, g0)
    with pytest.raises(ValueError):        # a form names its matrices
        moe_ops.moe_experts(u, experts, weights, up, down,
                            activation="swiglu")
    with pytest.raises(ValueError):
        moe_ops.moe_experts(u, experts, weights, up, down, up)


# ---------------------------------------------------------------------------
# the attention sublayer
# ---------------------------------------------------------------------------
def _attention(seed=0):
    from mxnet_tpu.models.zaya import CCAttention

    mx.random.seed(seed)
    att = CCAttention(32, num_heads=4, num_kv_heads=2, head_dim=8)
    att.initialize(mx.init.Normal(0.3), ctx=mx.cpu())
    return att


def test_attention_sublayer_is_causal_and_the_shifted_head_reads_the_token_before(
        ref):
    att = _attention()
    u = np.asarray(_rand(30, (1, 12, 32)))
    base = att(nd.array(u)).asnumpy()
    moved = u.copy()
    moved[0, 7] += 1.0
    out = att(nd.array(moved)).asnumpy()
    np.testing.assert_array_equal(out[0, :7], base[0, :7])   # nothing before
    assert np.abs(out[0, 7:] - base[0, 7:]).max() > 1e-4
    # against the reference's sublayer on the block's own weights
    P = {k[len(att.prefix):]: jnp.asarray(p.data().asnumpy())
         for k, p in att.collect_params().items()}
    want = ref._attention(P, "", jnp.asarray(u), TINY, None)
    np.testing.assert_allclose(base, want, rtol=2e-4, atol=2e-5)
    # v: the first key-value head reads token t, the second token t-1
    _q, _k, v = ref.qkv(P, "", jnp.asarray(u), TINY)
    v1 = u @ np.asarray(P["v1_proj_weight"]).T
    v2 = u @ np.asarray(P["v2_proj_weight"]).T
    np.testing.assert_allclose(v[0, :, 0], v1[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(v[0, 1:, 1], v2[0, :-1], rtol=1e-5, atol=1e-5)
    assert not np.asarray(v[0, 0, 1]).any()              # nought before 0
    # and the program's own v moves with token t-1 only at head 2
    grad = jax.grad(lambda uu: ref.qkv(P, "", uu, TINY)[2][0, 5, 1].sum())(
        jnp.asarray(u))
    assert np.abs(grad[0, 4]).max() > 0 and not np.asarray(grad[0, 5]).any()


# ---------------------------------------------------------------------------
# the stack through the compiled step
# ---------------------------------------------------------------------------
def _install(net, ref, seed):
    import sys

    sys.path.insert(0, _REPO)
    try:
        from benchmark import weights
    finally:
        sys.path.remove(_REPO)
    w = weights.make_weights(ref.param_spec(TINY), seed, "float32")
    weights.install(net, w, mx.cpu())
    return w


def _step(ref, seed=11, **opt):
    from mxnet_tpu.models.zaya import zaya
    from mxnet_tpu.parallel import DataParallelStep, local_mesh
    import sys

    sys.path.insert(0, _REPO)
    try:
        from benchmark.programs.next_token import next_token
    finally:
        sys.path.remove(_REPO)
    mx.random.seed(seed)
    net = zaya(**KWARGS)
    net.initialize(mx.init.Zero(), ctx=mx.cpu())
    w = _install(net, ref, seed)
    params = dict(TINY["optimizer"], **opt)
    step = DataParallelStep(
        net, next_token(), mesh=local_mesh(devices=jax.devices()[:1]),
        optimizer=params.pop("name"), optimizer_params=params)
    tokens = np.random.default_rng(seed).integers(0, 48, (2, 12),
                                                  dtype=np.int32)
    (x,), y = step.stage(nd.array(tokens, dtype="int32"),
                         nd.array(tokens.astype(np.float32)))
    return net, step, w, tokens, x, y


def _strip(net, tree):
    return {k[len(net.prefix):]: v for k, v in tree.items()}


def test_the_stack_matches_the_reference_loss_every_leafs_gradient_three_steps(
        ref):
    net, step, w, tokens, x, y = _step(ref)
    losses = [float(step.step(x, y))]
    got = {k: np.asarray(v) / (1 - 0.9)           # Adam's first moment
           for k, v in _strip(net, step.opt_state[0]).items()}
    losses += [float(step.step(x, y)) for _ in range(2)]
    want = {}
    stored = dict(w)
    blocks = jnp.asarray(tokens).reshape(1, 2, 12)
    total = ref._gradient(TINY, ref._fns(ref._freeze(TINY), None, None),
                          stored, blocks,
                          lambda p, g: want.update(
                              {p + k: v / 22 for k, v in g.items()}))
    assert set(got) == set(want)
    for name in sorted(want):
        scale = float(np.abs(want[name]).max()) + 1e-8
        np.testing.assert_allclose(got[name] / scale, want[name] / scale,
                                   atol=2e-4, err_msg=name)
    moving = [k for k, v in want.items() if np.abs(v).max() > 0]
    # load, load_max and the balancing bias of both layers, and layer 0's
    # depth gain (it multiplies the nought before the first held layer)
    assert len(moving) == len(want) - 3 * 2 - 1
    out = ref.train(TINY, w, tokens, 0, 3, 2)
    np.testing.assert_allclose(losses[0], float(total) / 22, rtol=1e-5)
    np.testing.assert_allclose(losses, out["loss"], rtol=2e-5)
    now = _strip(net, step.params)
    for name, delta in out["delta_norm"].items():
        if name.endswith(("_load", "_load_max")):      # aux: the step's own
            continue
        mine = float(np.linalg.norm(np.asarray(now[name], np.float32)
                                    - np.asarray(w[name], np.float32)))
        np.testing.assert_allclose(mine, delta, rtol=2e-2, atol=1e-7,
                                   err_msg=name)


@pytest.mark.parametrize("other", ["nothing recomputed",
                                   "everything recomputed"])
def test_recomputation_over_the_pair_changes_no_gradient(ref, monkeypatch,
                                                         other):
    """The rule of ``ops/recompute.py`` against a plain call, and to the
    bit against a bare ``jax.checkpoint`` (what it kept was made once)."""
    from mxnet_tpu.models import zaya as model
    from mxnet_tpu.ops import recompute

    def first_moment():
        net, step, _w, _tokens, x, y = _step(ref)
        loss = float(step.step(x, y))
        return loss, _strip(net, step.opt_state[0])

    loss0, g0 = first_moment()
    if other == "nothing recomputed":
        monkeypatch.setattr(model, "checkpointed",
                            lambda block, *xs: block(*xs))
    else:
        monkeypatch.setattr(recompute, "policy", None)
    loss1, g1 = first_moment()
    assert loss0 == loss1
    for name in g0:
        if other == "nothing recomputed":
            np.testing.assert_allclose(g0[name], g1[name], rtol=1e-5,
                                       atol=1e-9, err_msg=name)
        else:
            np.testing.assert_array_equal(g0[name], g1[name], err_msg=name)
    # the router's representation reaches the next layer: layer 1's depth
    # gain has a gradient only through layer 0's r
    assert float(jnp.abs(g0["layer1_moe_router_depth_gain"]).max()) > 0
    assert not np.asarray(g0["layer0_moe_router_depth_gain"]).any()


def test_the_tied_matrix_gets_the_sum_of_its_two_gradients(ref):
    """One parameter read by the look-up and by the head: through
    ``DataParallelStep`` its gradient is the head's plus the look-up's."""
    net, step, w, tokens, x, y = _step(ref)
    assert sum(k.endswith("embed_weight") for k in step.params) == 1
    assert not any("head" in k for k in step.params)
    step.step(x, y)
    got = np.asarray(_strip(net, step.opt_state[0])["embed_weight"]) / 0.1
    stored = dict(w)

    def loss(table, head):
        fns = ref._fns(ref._freeze(TINY), None, None)
        xs = table[jnp.asarray(tokens)]
        r = jnp.zeros(xs.shape[:2] + (16,), jnp.float32)
        for i in range(2):
            xs, r = fns[0](xs, r, ref._sub(stored, f"layer{i}_"))
        top = {"norm_f_gamma": stored["norm_f_gamma"], "embed_weight": head}
        return ref.head_loss_sum(xs, top, jnp.asarray(tokens), TINY) / 22

    g_table, g_head = jax.grad(loss, (0, 1))(w["embed_weight"],
                                             w["embed_weight"])
    assert float(jnp.abs(g_table).max()) > 0 and \
        float(jnp.abs(g_head).max()) > 0
    scale = float(jnp.abs(g_table + g_head).max())
    np.testing.assert_allclose(got / scale, (g_table + g_head) / scale,
                               atol=2e-4)
    assert np.abs(got - np.asarray(g_head)).max() / scale > 1e-2


def test_router_load_reaches_telemetry_at_drain_and_the_layer_is_told_its_share():
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.zaya import ZayaExperts, zaya
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    mx.random.seed(3)
    net = zaya(**KWARGS)
    net.initialize(mx.init.Normal(0.1), ctx=mx.cpu())
    ce = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(
        net, lambda lg, lb: ce(lg.reshape(-1, lg.shape[-1]), lb.reshape(-1)),
        mesh=local_mesh(devices=jax.devices()[:1]), optimizer="adam",
        optimizer_params={"learning_rate": 1e-2})
    tokens = np.random.default_rng(0).integers(0, 48, (2, 12), dtype=np.int32)
    (x,), y = step.stage(nd.array(tokens, dtype="int32"),
                         nd.array(tokens.astype(np.float32)))
    losses = [float(step.step(x, y)) for _ in range(3)]
    assert losses[-1] < losses[0]
    telemetry.reset()
    step.drain()
    loads = telemetry.moe_load()
    names = sorted(n for n in loads if n.endswith("_moe_load"))
    assert len(names) == 2
    for n in names:     # relative to an even spread: 24 tokens x 1 / 4
        pairs = np.asarray(loads[n]) * 24 / 4
        np.testing.assert_allclose(pairs, np.round(pairs), atol=1e-4)
        assert round(pairs.sum()) == 24      # every expert held: all land
    with pytest.raises(ValueError):
        ZayaExperts(16, n_experts=8, experts_held=(6, 4))
    layer = ZayaExperts(16, n_experts=8, experts_held=(4, 4),
                        expert_width=8, router_hidden_size=4)
    shapes = {k[len(layer.prefix):]: p.shape
              for k, p in layer.collect_params().items()}
    assert shapes["router_fc3_weight"] == (8, 4)          # routes over all 8
    assert shapes["experts_gate_weight"] == (4, 16, 8)    # holds 4
