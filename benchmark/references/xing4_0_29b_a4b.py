"""Plain reference of next-token training of one chip's share of
Xing4.0-29B-A4B as ``configs/xing4_0_29b_a4b.json`` states it: jax.numpy,
float32, matmuls at ``highest``, no kernels.  Imports nothing of the program.

The model (``model_type`` ``xing4_0``; latent attention as DeepSeek-V2,
arXiv:2405.04434; sigmoid ``noaux_tc`` routing and multi-token prediction as
DeepSeek-V3, arXiv:2412.19437; hyper-connections, arXiv:2409.19606, with the
residual mix constrained to doubly stochastic matrices, arXiv:2512.24880).
The residual path is ``n = hc_mult`` streams, ``X`` (R, L, n, d); ``X_0 = [e,
.., e]`` with ``e`` the token's embedding; ``len(layer_types)`` layers, each
an attention sublayer and a feed-forward sublayer; ``h = sum_i X[i]``; a
final RMSNorm; the head.

A sublayer around ``F`` with its own parameters, ``x = vec(X)`` (n d wide):
``x' = RMSNorm(x)``; ``[P | Q | R] = x' phi`` (n, n, n x n); ``H_pre =
sigmoid(a_pre P + b_pre)``; ``H_post = 2 sigmoid(a_post Q + b_post)``;
``H_res = SK(clip(a_res R + b_res))`` with ``SK``: ``exp``, then
``hc_sinkhorn_iters`` times every row over (its sum + ``hc_eps``), every
column over (its sum + ``hc_eps``).  ``u = sum_i H_pre[i] X[i]``; ``y =
F(RMSNorm(u))``; ``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y``.

Attention (H heads; ``nope``, ``rope``, ``v`` channels a head): ``c_q =
RMSNorm(u W_qa)``, ``q = c_q W_qb`` (H x (nope | rope)); ``[c_kv | k_r] = u
W_kva``; ``[k_n | v] = RMSNorm(c_kv) W_kvb`` (H x (nope | v)); rotary on the
rope channels of every q head and on the one ``k_r`` all heads share
(rotate-half pairing; YaRN's frequencies: ``yarn_frequencies``; cos and sin
not rescaled); ``o = causal softmax(q k^T s) v`` with ``s = (nope +
rope)^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``, dense, in row
blocks; ``o W_o``.

Feed-forward: ``dense`` layers ``(silu(u W_gate) * (u W_up)) W_down``;
``sparse`` layers ``s = sigmoid(u W_r)``, the ``num_experts_per_tok`` largest
of ``s + b`` (``b`` takes no gradient), weights ``s_e / sum(chosen s) *
routed_scaling_factor``, ``y = sum_e w_e E_e(u) + S(u)`` with every expert
and the shared one of the gated form.  THIS CHIP'S SHARE: the router is
``n_routed_experts_published`` wide, the experts held are
``experts_held_first .. + n_routed_experts - 1``; what the absent experts
would add is left out and that partial result goes on.  ``load``,
``load_max`` and ``mtp_loss`` are the program's aux leaves (no gradient
reaches them).

Prediction module (``num_nextn_predict_layers`` 1): ``g_i = [RMSNorm(h_i) |
RMSNorm(e_(i+1))] W_eh`` (the last position takes its own embedding again:
no loss reads it); ``X = [g, .., g]``; one ``sparse`` layer; sum; its own
final RMSNorm; the SAME head: position ``i`` predicts token ``i + 2``.

Loss: mean cross-entropy of the trunk's logits at positions 0 .. L-2 against
the next token, plus ``mtp_loss_weight`` times the mean cross-entropy of the
module's logits at positions 0 .. L-3 against the token after, logits over
the held slice of the vocabulary.  Optimizer: Adam without weight decay on
parameters STORED in the configuration's type with no float32 master copy;
``router_lr_mult`` scales the routers' learning rate.  The backward pass is
written out group by group (head, module, layers from the last), each
sublayer recomputed inside its own gradient, so that no whole float32
gradient has to live beside the moments from step 2 on.

``quant="fp8"`` is the control of the comparison: both operands of every
matmul rounded to 8-bit floats (e4m3, per-tensor scale, straight-through
gradient).  ``positions`` (the loss over a row's first positions only) and
``mtp_weight`` (0: the module's loss left out) are planted faults.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128   # query rows per block of the dense attention
HEAD_BLOCK = 1024   # positions per block of the head's logits


def _layer_spec(cfg, prefix, kind):
    """Ordered (name, shape, init) of one layer's leaves."""
    d, n = cfg["hidden_size"], cfg["hc_mult"]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    w = ("normal", cfg["initializer_range"])
    one, zero = ("const", 1.0), ("const", 0.0)
    k = 2 * n + n * n

    def sublayer(sub):
        p = prefix + sub + "_hc_"
        return [(p + "norm_gamma", (n * d,), one),
                (p + "phi_weight", (k, n * d), w),
                (p + "a", (3,), ("const", 0.01)),
                (p + "b", (k,), ("normal", 1.0)),
                (prefix + sub + "_norm_gamma", (d,), one)]

    p = prefix + "attn_"
    spec = sublayer("attn") + [
        (p + "q_a_proj_weight", (qr, d), w),
        (p + "q_a_norm_gamma", (qr,), one),
        (p + "q_b_proj_weight", (h * (dn + dr), qr), w),
        (p + "kv_a_proj_weight", (kr + dr, d), w),
        (p + "kv_a_norm_gamma", (kr,), one),
        (p + "kv_b_proj_weight", (h * (dn + dv), kr), w),
        (p + "o_proj_weight", (d, h * dv), w)] + sublayer("ffn")
    p = prefix + "ffn_"
    if kind == "dense":
        f = cfg["intermediate_size"]
        return spec + [(p + "gate_proj_weight", (f, d), w),
                       (p + "up_proj_weight", (f, d), w),
                       (p + "down_proj_weight", (d, f), w)]
    held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    wide = cfg.get("n_routed_experts_published", held)
    fs = cfg["n_shared_experts"] * f
    return spec + [(p + "experts_gate_weight", (held, d, f), w),
                   (p + "experts_up_weight", (held, d, f), w),
                   (p + "experts_down_weight", (held, f, d), w),
                   (p + "load", (held,), zero),
                   (p + "load_max", (held,), zero),
                   (p + "router_weight", (wide, d), w),
                   (p + "e_score_correction_bias", (wide,), ("normal", 0.01)),
                   (p + "shared_gate_proj_weight", (fs, d), w),
                   (p + "shared_up_proj_weight", (fs, d), w),
                   (p + "shared_down_proj_weight", (d, fs), w)]


def param_spec(cfg):
    """Ordered (name, shape, init) of every leaf of the program's state.
    The mixing offsets ``b`` N(0, 1) so that the three maps differ by stream
    and Sinkhorn does not start at its fixed point; ``a`` 0.01; the router's
    correction small and not zero; the aux leaves 0."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg["initializer_range"])
    one = ("const", 1.0)
    spec = [("embed_weight", (v, d), w)]
    for i, kind in enumerate(cfg["layer_types"]):
        spec += _layer_spec(cfg, f"layer{i}_", kind)
    spec += [("norm_f_gamma", (d,), one), ("head_weight", (v, d), w)]
    if cfg["num_nextn_predict_layers"]:
        spec += [("mtp_hnorm_gamma", (d,), one),
                 ("mtp_enorm_gamma", (d,), one),
                 ("mtp_eh_proj_weight", (d, 2 * d), w)]
        spec += _layer_spec(cfg, "mtp_layer_", "sparse")
        spec += [("mtp_norm_f_gamma", (d,), one),
                 ("mtp_loss", (1,), ("const", 0.0))]
    return spec


def _q8(x):
    """Round to e4m3 (3 mantissa bits) under a per-tensor scale that puts the
    largest magnitude at 448; gradient straight through."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -9)))
    step = 2.0 ** (jnp.maximum(e, -6.0) - 3.0)
    q = jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) / s
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, quant):
    if quant == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True) + eps) \
        * gain


def yarn_frequencies(cfg):
    """The ``qk_rope_head_dim / 2`` rotary frequencies: ``theta^(-2 i /
    dim)``, divided by ``factor`` past the pair that turns ``beta_slow``
    times over the original context, kept below the pair that turns
    ``beta_fast`` times, a linear blend between."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    sc = cfg.get("rope_scaling")
    if not sc:
        return freq.astype(np.float32)
    orig = sc["original_max_position_embeddings"]

    def pair(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    lo = max(math.floor(pair(sc["beta_fast"])), 0)
    hi = min(math.ceil(pair(sc["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (freq / sc["factor"] * ramp + freq * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg):
    sc = cfg.get("rope_scaling")
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0 if sc \
        else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rotary(x, freq):
    """Rotary position on the whole last axis of x (R, L, H, D), positions
    along axis 1, rotate-half pairing, ``freq`` (D / 2,)."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def sinkhorn(r, iters, eps):
    """``r`` (..., n, n) -> exp(r) with rows then columns normalised,
    ``iters`` times."""
    m = jnp.exp(r)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def mixing(P, p, X, cfg, quant=None):
    """(H_pre (R, L, n), H_post (R, L, n), H_res (R, L, n, n)) of the streams
    X (R, L, n, d) under the sublayer's parameters ``P[p + ..]``."""
    rows, length, n, d = X.shape
    x = _rms(X.reshape(rows, length, n * d), P[p + "norm_gamma"],
             cfg["rms_norm_eps"])
    proj = _mm("rlc,kc->rlk", x, P[p + "phi_weight"], quant)
    a, b = P[p + "a"], P[p + "b"]
    h_pre = jax.nn.sigmoid(a[0] * proj[..., :n] + b[:n])
    h_post = 2 * jax.nn.sigmoid(a[1] * proj[..., n:2 * n] + b[n:2 * n])
    r = a[2] * proj[..., 2 * n:] + b[2 * n:]
    r = jnp.clip(r, cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    h_res = sinkhorn(r.reshape(rows, length, n, n), cfg["hc_sinkhorn_iters"],
                     cfg["hc_eps"])
    return h_pre, h_post, h_res


def attention(P, p, u, cfg, quant=None):
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    rows, length, _ = u.shape
    c_q = _rms(_mm("rlc,oc->rlo", u, P[p + "q_a_proj_weight"], quant),
               P[p + "q_a_norm_gamma"], eps)
    q = _mm("rlc,oc->rlo", c_q, P[p + "q_b_proj_weight"], quant).reshape(
        rows, length, h, dn + dr)
    latent = _mm("rlc,oc->rlo", u, P[p + "kv_a_proj_weight"], quant)
    c_kv = _rms(latent[..., :rank], P[p + "kv_a_norm_gamma"], eps)
    kv = _mm("rlc,oc->rlo", c_kv, P[p + "kv_b_proj_weight"], quant).reshape(
        rows, length, h, dn + dv)
    freq = jnp.asarray(yarn_frequencies(cfg))
    k_r = rotary(latent[..., rank:].reshape(rows, length, 1, dr), freq)
    q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:], freq)], -1)
    k = jnp.concatenate([kv[..., :dn],
                         jnp.broadcast_to(k_r, (rows, length, h, dr))], -1)
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, kv[..., dn:]))
    blk = min(QUERY_BLOCK, length)
    pad = (-length) % blk
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(rows, h, -1, blk, dn + dr), 2, 0)
    kpos, scale = jnp.arange(length), softmax_scale(cfg)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = _mm("rhqd,rhkd->rhqk", qi, k, quant) * scale
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
        return _mm("rhqk,rhkd->rhqd", jax.nn.softmax(s, axis=-1), v, quant)

    o = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    o = jnp.moveaxis(o, 0, 2).reshape(rows, h, -1, dv)[:, :, :length]
    o = o.transpose(0, 2, 1, 3).reshape(rows, length, h * dv)
    return _mm("rlc,oc->rlo", o, P[p + "o_proj_weight"], quant)


def gated(P, p, u, quant=None):
    hid = jax.nn.silu(_mm("...c,fc->...f", u, P[p + "gate_proj_weight"],
                          quant)) \
        * _mm("...c,fc->...f", u, P[p + "up_proj_weight"], quant)
    return _mm("...f,cf->...c", hid, P[p + "down_proj_weight"], quant)


def route(P, p, u, cfg, quant=None):
    """(experts (T, k) int32, weights (T, k)) of the flat tokens u (T, d)."""
    s = jax.nn.sigmoid(_mm("tc,ec->te", u, P[p + "router_weight"], quant))
    _top, experts = jax.lax.top_k(
        s + jax.lax.stop_gradient(P[p + "e_score_correction_bias"]),
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * cfg["routed_scaling_factor"]


def experts_layer(P, p, u, cfg, quant=None, first=None, count=None):
    """The routed part the experts ``first .. first + count - 1`` give (the
    configuration's share by default) plus the shared expert."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    experts, w = route(P, p, u, cfg, quant)
    first = cfg.get("experts_held_first", 0) if first is None else first
    count = cfg["n_routed_experts"] if count is None else count

    @jax.checkpoint
    def one(out, args):
        e, gate_w, up_w, down_w = args
        gate = jnp.where(experts == first + e, w, 0.0).sum(-1)
        hid = jax.nn.silu(_mm("tc,cf->tf", u, gate_w, quant)) \
            * _mm("tc,cf->tf", u, up_w, quant)
        return out + gate[:, None] * _mm("tf,fc->tc", hid, down_w, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(count), P[p + "experts_gate_weight"][:count],
        P[p + "experts_up_weight"][:count],
        P[p + "experts_down_weight"][:count]))
    return (out + gated(P, p + "shared_", u, quant)).reshape(shape)


def sublayer(X, P, sub, cfg, kind, quant=None):
    """One hyper-connected sublayer (``sub`` ``attn`` or ``ffn``) over the
    streams X (R, L, n, d)."""
    h_pre, h_post, h_res = mixing(P, sub + "_hc_", X, cfg, quant)
    u = jnp.einsum("rli,rlid->rld", h_pre, X, precision=HI)
    u = _rms(u, P[sub + "_norm_gamma"], cfg["rms_norm_eps"])
    if sub == "attn":
        y = attention(P, "attn_", u, cfg, quant)
    elif kind == "dense":
        y = gated(P, "ffn_", u, quant)
    else:
        y = experts_layer(P, "ffn_", u, cfg, quant)
    return jnp.einsum("rlij,rljd->rlid", h_res, X, precision=HI) \
        + h_post[..., None] * y[:, :, None, :]


def layer(X, P, cfg, kind, quant=None):
    """One layer ``X -> X``; P holds the layer's leaves by their names
    without the layer's prefix.  Each sublayer is recomputed inside its own
    gradient."""
    for sub in ("attn", "ffn"):
        X = jax.checkpoint(
            lambda X, P, sub=sub: sublayer(X, P, sub, cfg, kind, quant))(X, P)
    return X


def spread(e, n):
    return jnp.broadcast_to(e[:, :, None, :], e.shape[:2] + (n, e.shape[-1]))


def mtp_input(h, e, P, cfg, quant=None):
    """``g`` (R, L, d) of the trunk's summed streams h and the embeddings e
    (R, L, d): position i reads h_i and e_(i+1), the last its own e."""
    eps = cfg["rms_norm_eps"]
    e_next = jnp.concatenate([e[:, 1:], e[:, -1:]], 1)
    both = jnp.concatenate([_rms(h, P["mtp_hnorm_gamma"], eps),
                            _rms(e_next, P["mtp_enorm_gamma"], eps)], -1)
    return _mm("rlc,oc->rlo", both, P["mtp_eh_proj_weight"], quant)


def logits_of(h, gain, head, cfg, quant=None):
    return _mm("rlc,vc->rlv", _rms(h, gain, cfg["rms_norm_eps"]), head, quant)


def head_loss_sum(h, gain, head, tokens, ahead, cfg, quant=None,
                  positions=None):
    """Sum of the cross-entropies of position i's logits against token ``i +
    ahead`` over positions 0 .. L-1-ahead (the first ``positions`` of them
    when given), from h (R, L, d); a block of positions' logits at a time."""
    rows, length, _ = h.shape
    n = length - ahead if positions is None else positions
    blk = min(HEAD_BLOCK, n)
    pad = (-n) % blk
    xs = jnp.pad(h[:, :n], ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(tokens[:, ahead:n + ahead], ((0, 0), (0, pad)))
    live = jnp.pad(jnp.ones((rows, n)), ((0, 0), (0, pad)))

    @jax.checkpoint
    def block(args):
        x, lab, keep = args
        logp = jax.nn.log_softmax(logits_of(x, gain, head, cfg, quant), -1)
        return -(jnp.take_along_axis(logp, lab[..., None], -1)[..., 0]
                 * keep).sum()

    def split(t):
        return jnp.moveaxis(t.reshape((rows, -1, blk) + t.shape[2:]), 1, 0)

    return jax.lax.map(block, (split(xs), split(labels), split(live))).sum()


def forward(cfg, P, tokens, quant=None):
    """[logits, logits_mtp] (the first alone without the module) of tokens
    (R, L) under float32 leaves P: the whole model at once, for the tests."""
    n = cfg["hc_mult"]
    e = P["embed_weight"][tokens]
    X = spread(e, n)
    for i, kind in enumerate(cfg["layer_types"]):
        X = layer(X, _sub(P, f"layer{i}_"), cfg, kind, quant)
    h = X.sum(2)
    out = [logits_of(h, P["norm_f_gamma"], P["head_weight"], cfg, quant)]
    if cfg["num_nextn_predict_layers"]:
        X = spread(mtp_input(h, e, P, cfg, quant), n)
        X = layer(X, _sub(P, "mtp_layer_"), cfg, "sparse", quant)
        out.append(logits_of(X.sum(2), P["mtp_norm_f_gamma"],
                             P["head_weight"], cfg, quant))
    return out


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _sub(tree, prefix):
    return {k[len(prefix):]: a for k, a in tree.items()
            if k.startswith(prefix)}


MTP_FRONT = ("mtp_hnorm_gamma", "mtp_enorm_gamma", "mtp_eh_proj_weight")


# The stack is sequential, so the backward pass is written out group by
# group: a group's float32 gradient (and the float32 widening of its stored
# leaves, 0.51 GB a layer) lives only while that group is worked on, and
# from step 2 on its Adam update follows at once.  914 M leaves cost 3.7 GB
# in float32: a whole gradient beside the moments, the stored values and the
# caller's weights would not fit a 16 GB chip.
@functools.lru_cache(maxsize=32)
def _fns(cfg_key, quant, positions):
    cfg = json.loads(cfg_key)

    def fwd(kind):
        return jax.jit(lambda X, P: layer(X, _f32(P), cfg, kind, quant))

    def bwd(kind):
        def run(X, P, dX):
            _y, pull = jax.vjp(
                lambda X, P32: layer(X, P32, cfg, kind, quant), X, _f32(P))
            return pull(dX)
        return jax.jit(run)

    def head(ahead):
        def run(h, gain, weight, tokens, scale):
            return jax.value_and_grad(
                lambda h, g, w: scale * head_loss_sum(
                    h, g, w, tokens, ahead, cfg, quant, positions),
                argnums=(0, 1, 2))(h, gain.astype(jnp.float32),
                                   weight.astype(jnp.float32))
        return jax.jit(run)

    def front_bwd(h, e, P, dg):
        _g, pull = jax.vjp(
            lambda h, e, P32: mtp_input(h, e, P32, cfg, quant), h, e, _f32(P))
        return pull(dg)

    return {"fwd": {k: fwd(k) for k in ("dense", "sparse")},
            "bwd": {k: bwd(k) for k in ("dense", "sparse")},
            "head": {a: head(a) for a in (1, 2)},
            "front": jax.jit(lambda h, e, P: mtp_input(h, e, _f32(P), cfg,
                                                       quant)),
            "front_bwd": jax.jit(front_bwd),
            "embed_bwd": jax.jit(lambda tok, de, g: g.at[tok].add(de))}


def _freeze(cfg):
    """The configuration's sizes as a hashable key of the jit cache."""
    return json.dumps({k: v for k, v in cfg.items()
                       if k not in ("assumed", "rehearsal", "program", "flops",
                                    "deployment", "depth", "reduced_from",
                                    "optimizer")}, sort_keys=True)


def _park(x):
    """A layer's input between the forward and the backward pass: on the
    host where the device is an accelerator (six float32 stream tensors are
    1.4 GB at 4,096 tokens, beside 11 GB of weights and moments)."""
    return x if jax.devices()[0].platform == "cpu" else jax.device_get(x)


def _gradient(cfg, fns, stored, blocks, each, scales):
    """The loss and, handed to ``each(prefix, grads)`` group by group as soon
    as the backward pass has it, the float32 gradient over ``blocks`` (nb,
    R, L); ``scales`` are what a summed cross-entropy of the trunk's head
    and of the module's head count for in the loss.  The head's and the
    embedding's come last, once both uses of each have met."""
    n, kinds = cfg["hc_mult"], cfg["layer_types"]
    mtp = bool(cfg["num_nextn_predict_layers"])
    add = functools.partial(jax.tree_util.tree_map, jnp.add)
    embed = stored["embed_weight"].astype(jnp.float32)
    acts = []
    for tok in blocks:
        x, xs = spread(embed[tok], n), []
        for i, kind in enumerate(kinds):
            xs.append(_park(x))
            x = fns["fwd"][kind](x, _sub(stored, f"layer{i}_"))
        acts.append(xs + [x])
    total, g_head = 0.0, None
    g_embed = jnp.zeros_like(embed)
    dhs = []
    if mtp:
        front = {k: stored[k] for k in MTP_FRONT}
        P = _sub(stored, "mtp_layer_")
        g_norm = g_layer = g_front = None
        for tok, xs in zip(blocks, acts):
            h, e = xs[-1].sum(2), embed[tok]
            X = spread(fns["front"](h, e, front), n)
            loss, (dh2, gn, gw) = fns["head"][2](
                fns["fwd"]["sparse"](X, P).sum(2), stored["mtp_norm_f_gamma"],
                stored["head_weight"], tok, scales[1])
            total = total + loss
            dX, gl = fns["bwd"]["sparse"](X, P, spread(dh2, n))
            dh, de, gf = fns["front_bwd"](h, e, front, dX.sum(2))
            dhs.append(dh)
            g_embed = fns["embed_bwd"](tok, de, g_embed)
            g_head = gw if g_head is None else g_head + gw
            g_norm = gn if g_norm is None else g_norm + gn
            g_layer = gl if g_layer is None else add(g_layer, gl)
            g_front = gf if g_front is None else add(g_front, gf)
        each("", {"mtp_norm_f_gamma": g_norm, **g_front,
                  "mtp_loss": jnp.zeros((1,), jnp.float32)})   # an aux leaf
        each("mtp_layer_", g_layer)
        del g_layer, g_front
    g_norm, dxs = None, []
    for b, (tok, xs) in enumerate(zip(blocks, acts)):
        loss, (dh, gn, gw) = fns["head"][1](
            xs.pop().sum(2), stored["norm_f_gamma"], stored["head_weight"],
            tok, scales[0])
        total = total + loss
        g_head = gw if g_head is None else g_head + gw
        g_norm = gn if g_norm is None else g_norm + gn
        dxs.append(spread(dh + dhs[b] if mtp else dh, n))
    each("", {"norm_f_gamma": g_norm, "head_weight": g_head})
    del g_head
    for i in reversed(range(len(kinds))):
        P, g = _sub(stored, f"layer{i}_"), None
        for b, xs in enumerate(acts):
            dxs[b], gp = fns["bwd"][kinds[i]](jnp.asarray(xs.pop()), P,
                                              dxs[b])
            g = gp if g is None else add(g, gp)
        each(f"layer{i}_", g)
    for tok, dX in zip(blocks, dxs):
        g_embed = fns["embed_bwd"](tok, dX.sum(2), g_embed)
    each("", {"embed_weight": g_embed})
    return total


def train(cfg, weights, tokens, seed, steps, rows_per_block, quant=None,
          rows=None, probe=None, positions=None, mtp_weight=None):
    """``steps`` training steps on ``tokens`` (batch, seq; the label of a
    position is the next token, for the module the one after) from
    ``weights`` (stored type).  ``seed`` is unused: nothing here is random.
    ``rows`` restricts the batch, ``positions`` the loss to a row's first
    positions, ``mtp_weight`` replaces ``mtp_loss_weight`` (planted faults);
    ``rows_per_block`` is the most rows worked on at once.  Returns ``loss``
    per step, ``grad_norm`` per leaf at step 1, ``delta_norm`` per leaf
    after the last step and, where ``probe`` is given, ``grad_sketch``: what
    it returns for the first gradient."""
    del seed
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    batch, seq = tokens.shape
    use = np.arange(batch) if rows is None else np.asarray(rows)
    rows_per_block = min(rows_per_block, len(use))
    if len(use) % rows_per_block:
        raise ValueError("rows_per_block must divide the rows used")
    blocks = jnp.asarray(np.asarray(tokens)[use], jnp.int32).reshape(
        -1, rows_per_block, seq)
    lam = cfg["mtp_loss_weight"] if mtp_weight is None else mtp_weight

    def count(ahead):
        return len(use) * ((seq - ahead) if positions is None
                           else int(positions))

    scales = (jnp.float32(1.0 / count(1)), jnp.float32(lam / count(2)))
    fns = _fns(_freeze(cfg), quant, positions)
    donate = jax.devices()[0].platform != "cpu"

    def adam(p, g, m, v, t, mult):
        corr = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        new = p.astype(jnp.float32) \
            - lr * mult * corr * m / (jnp.sqrt(v) + eps)
        return new.astype(p.dtype), m, v

    adam = jax.jit(adam, donate_argnums=(0, 2, 3) if donate else ())
    router_mult = jnp.float32(cfg.get("router_lr_mult", 1.0))
    one = jnp.float32(1.0)
    # the update donates what it is given: a copy, the caller keeps its own
    stored = {k: jnp.copy(a) for k, a in weights.items()} if donate \
        else dict(weights)
    m, v, losses, first = {}, {}, [], {}

    def update(grads, t):
        for k, g in grads.items():
            if k not in m:
                m[k] = jnp.zeros(g.shape, jnp.float32)
                v[k] = jnp.zeros(g.shape, jnp.float32)
            stored[k], m[k], v[k] = adam(
                stored[k], g, m[k], v[k], jnp.float32(t),
                router_mult if k.endswith("router_weight") else one)

    for t in range(1, steps + 1):
        held = {}

        def each(prefix, grads, t=t):
            grads = {prefix + k: g for k, g in grads.items()}
            if t == 1:      # the whole first gradient is read before it goes
                held.update(grads)
            else:
                update(grads, t)

        losses.append(float(_gradient(cfg, fns, stored, blocks, each,
                                      scales)))
        if t == 1:
            first["grad_norm"] = {k: float(jnp.sqrt(jnp.sum(jnp.square(g))))
                                  for k, g in held.items()}
            first["grad_sketch"] = None if probe is None else \
                jax.device_get(probe(held))
            while held:
                k, g = held.popitem()
                update({k: g}, t)
    del m, v
    delta = {k: float(jnp.sqrt(jnp.sum(jnp.square(
        stored[k].astype(jnp.float32) - weights[k].astype(jnp.float32)))))
        for k in stored}
    return {"loss": losses, "grad_norm": first["grad_norm"],
            "delta_norm": delta, "grad_sketch": first["grad_sketch"]}
