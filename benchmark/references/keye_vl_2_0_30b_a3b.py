"""Plain reference of next-token training of one chip's share of
Keye-VL-2.0-30B-A3B's language model as ``configs/keye_vl_2_0_30b_a3b.json``
states it: jax.numpy, float32, matmuls at ``highest``, no kernels, its own
selection by ``lax.top_k``.  Imports nothing of the program.

The model (``model_type`` ``KeyeVL2``: a Qwen3-MoE decoder whose attention
keeps DeepSeek-V3.2's lightning-indexer selection, arXiv:2512.02556, at
``sa_config``'s sizes).  Tokens (R, L); a layer ``h -> h``, with ``u =
RMSNorm(h)`` (eps ``rms_norm_eps``, a gain a channel) and ``ub =
stop_gradient(u)``:

* indexer: ``qi_j = rot(ub W_qI[j])`` (J = ``indexer_num_heads`` heads of Dk
  = ``indexer_head_dim``), ``ki = rot(LayerNorm(ub W_kI))`` (one head, eps
  ``rms_norm_eps``, a gain and a bias), ``w = ub W_w / (sqrt(J) sqrt(Dk))``;
  ``rot`` rotate-half rotary over a head's first ``indexer_rope_fraction``
  of channels at ``rope_theta``, position t; ``I[t, s] = sum_j w[t, j]
  relu(qi_j[t] . ki[s])`` for ``s <= t``;
* selection: ``theta_t`` the ``topk``-th largest of ``I[t, s <= t]`` (by
  ``lax.top_k``; every ``s <= t`` while ``t < topk``), ``S_t = {s <= t :
  I[t, s] >= theta_t}`` (ties in);
* attention: ``q = RMSNorm_q(u W_q)``, ``k = RMSNorm_k(u W_k)`` a head of D
  (one gain of D each), ``v = u W_v``; rotate-half rotary over all D channels
  at ``rope_theta``; ``softmax(q k^T / sqrt(D))`` over ``S_t``, H / Hkv query
  heads a key-value head, dense, in query blocks; ``h += o W_o``;
* experts: SDAR's (``r = softmax(u W_r^T)`` over all
  ``num_experts_published`` in float32, top ``num_experts_per_tok``,
  renormalised; ``sum_e w_e W_down,e(silu(u W_gate,e) * u W_up,e)`` over
  the held experts ``experts_held_first`` .. + ``num_experts`` - 1: THIS
  CHIP'S SHARE, what the absent experts would add is left out);
* the indexer's loss ``(1/L) sum_t KL(p_t || softmax_{S_t} I_t)``, ``p_t =
  stop_gradient(mean_h P_{t,h})`` over ``S_t``.

Output: ``logits = RMSNorm_f(h) W_head^T`` over the held slice.  Loss a row:
the mean cross-entropy of position t against token t+1 (t = 0 .. L-2) plus
every layer's indexer loss; the mean over rows.  Optimizer: Adam without
weight decay on parameters STORED in the configuration's type with no float32
master copy; ``router_lr_mult`` scales the routers' learning rate.  The
backward pass is written out layer by layer, each sublayer recomputed inside
its own gradient.  ``load``, ``load_max``, ``kl`` and ``tile_hit`` are the
program's aux leaves (no gradient reaches them).

DEPARTURES, all stated in the configuration's ``assumed``: 1-D rotary for
M-RoPE over text positions; the indexer's rotary on its first half of
channels; no FP8 or Hadamard rotation in the indexer; the router held.

``quant="fp8"`` is the control of the comparison: both operands of every
matmul rounded to 8-bit floats (e4m3, per-tensor scale, straight-through
gradient).  ``fault="dense"`` (the selection ignored: every earlier key) and
``fault="recent"`` (the ``topk`` latest keys) are planted faults.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128   # query rows per block of the dense attention
HEAD_BLOCK = 1024   # positions per block of the head's logits


def _layer_spec(cfg, prefix):
    """Ordered (name, shape, init) of one layer's leaves."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    sa = cfg["sa_config"]
    jh, dk = sa["indexer_num_heads"], sa["indexer_head_dim"]
    wide = cfg.get("num_experts_published", held)
    w = ("normal", cfg["initializer_range"])
    one, zero = ("const", 1.0), ("const", 0.0)
    a, x, m = prefix + "attn_", prefix + "indexer_", prefix + "moe_"
    return [(prefix + "attn_norm_gamma", (d,), one),
            (a + "q_proj_weight", (h * hd, d), w),
            (a + "k_proj_weight", (hkv * hd, d), w),
            (a + "v_proj_weight", (hkv * hd, d), w),
            (a + "o_proj_weight", (d, h * hd), w),
            (a + "q_norm_gamma", (hd,), one),
            (a + "k_norm_gamma", (hd,), one),
            (x + "wq_weight", (jh * dk, d), w),
            (x + "wk_weight", (dk, d), w),
            (x + "k_norm_gamma", (dk,), one),
            (x + "k_norm_beta", (dk,), zero),
            (x + "weights_proj_weight", (jh, d), w),
            (x + "kl", (1,), zero),
            (x + "tile_hit", (1,), zero),
            (prefix + "moe_norm_gamma", (d,), one),
            (m + "router_weight", (wide, d), w),
            (m + "experts_gate_weight", (held, d, f), w),
            (m + "experts_up_weight", (held, d, f), w),
            (m + "experts_down_weight", (held, f, d), w),
            (m + "load", (held,), zero),
            (m + "load_max", (held,), zero)]


def param_spec(cfg):
    """Ordered (name, shape, init) of every leaf of the program's state: every
    matrix N(0, ``initializer_range``), the norms' gains 1 and biases 0, the
    aux leaves 0."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg["initializer_range"])
    spec = [("embed_weight", (v, d), w)]
    for i in range(len(cfg["layer_types"])):
        spec += _layer_spec(cfg, f"layer{i}_")
    return spec + [("norm_f_gamma", (d,), ("const", 1.0)),
                   ("head_weight", (v, d), w)]


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


def _layer_norm(x, gain, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gain + bias


def rotary(x, theta, rot):
    """Rotate-half rotary over the first ``rot`` channels of x (R, L, ...,
    D), position t along axis 1."""
    half = rot // 2
    n = x.shape[1]
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(n, dtype=jnp.float32)[:, None] * freq[None]
    shape = (1, n) + (1,) * (x.ndim - 3) + (half,)
    cos, sin = (f(angle).reshape(shape) for f in (jnp.cos, jnp.sin))
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def indexer(P, p, u, cfg, quant=None):
    """(qi (R, L, J, Dk), ki (R, L, Dk), w (R, L, J)) of the indexer over
    ``stop_gradient(u)``."""
    sa = cfg["sa_config"]
    jh, dk = sa["indexer_num_heads"], sa["indexer_head_dim"]
    rot = int(round(dk * cfg["indexer_rope_fraction"]))
    ub = jax.lax.stop_gradient(u)
    rows, n, _ = u.shape
    qi = rotary(_mm("rlc,oc->rlo", ub, P[p + "wq_weight"], quant).reshape(
        rows, n, jh, dk), cfg["rope_theta"], rot)
    ki = rotary(_layer_norm(_mm("rlc,oc->rlo", ub, P[p + "wk_weight"], quant),
                            P[p + "k_norm_gamma"], P[p + "k_norm_beta"],
                            cfg["rms_norm_eps"]), cfg["rope_theta"], rot)
    w = _mm("rlc,oc->rlo", ub, P[p + "weights_proj_weight"], quant) \
        / (jh ** 0.5 * dk ** 0.5)
    return qi, ki, w


def index_scores(qi, ki, w, quant=None):
    """I (R, T, L) of a block of queries against every key (unmasked)."""
    s = _mm("rtjd,rsd->rtjs", qi, ki, quant)
    return jnp.einsum("rtj,rtjs->rts", w, jnp.maximum(s, 0.0), precision=HI)


def selection(score, qpos, kpos, topk, fault=None):
    """S (R, T, L) bool of a block of queries' scores."""
    causal = kpos[None, :] <= qpos[:, None]
    if fault == "dense":
        return jnp.broadcast_to(causal, score.shape)
    if fault == "recent":
        near = kpos[None, :] > qpos[:, None] - topk
        return jnp.broadcast_to(causal & near, score.shape)
    masked = jnp.where(causal, jax.lax.stop_gradient(score), -jnp.inf)
    k = min(int(topk), score.shape[-1])
    theta = jax.lax.top_k(masked, k)[0][..., -1:]
    return causal & (masked >= theta)


def attention(P, p, u, cfg, quant=None, fault=None):
    """(attention's output (R, L, d), the indexer's loss (1/L) sum_t KL_t a
    row (R,)) over u (R, L, d)."""
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    rows, n, _ = u.shape
    topk = cfg["sa_config"]["topk"]

    def proj(name, heads):
        return _mm("rlc,oc->rlo", u, P[p + name], quant).reshape(
            rows, n, heads, d)

    q = rotary(_rms(proj("q_proj_weight", h), P[p + "q_norm_gamma"], eps),
               cfg["rope_theta"], d)
    k = rotary(_rms(proj("k_proj_weight", hkv), P[p + "k_norm_gamma"], eps),
               cfg["rope_theta"], d)
    v = proj("v_proj_weight", hkv)
    qi, ki, w = indexer(P, "indexer_", u, cfg, quant)
    k, v = (jnp.repeat(t, h // hkv, axis=2) for t in (k, v))
    q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
    blk = min(QUERY_BLOCK, n)
    pad = (-n) % blk
    nb = (n + pad) // blk

    def split(t, axis):
        t = jnp.pad(t, [(0, pad) if a == axis else (0, 0)
                        for a in range(t.ndim)])
        shape = t.shape[:axis] + (nb, blk) + t.shape[axis + 1:]
        return jnp.moveaxis(t.reshape(shape), axis, 0)

    kpos = jnp.arange(n)

    @jax.checkpoint
    def block(args):
        i, qb, qib, wb = args
        qpos = i * blk + jnp.arange(blk)
        score = index_scores(qib, ki, wb, quant)
        sel = selection(score, qpos, kpos, topk, fault)
        s = _mm("rhqd,rhkd->rhqk", qb, k, quant) * d ** -0.5
        s = jnp.where(sel[:, None], s, -1e30)
        prob = jax.nn.softmax(s, axis=-1)
        o = _mm("rhqk,rhkd->rhqd", prob, v, quant)
        target = jax.lax.stop_gradient(jnp.where(sel, prob.mean(1), 0.0))
        log_q = score - jax.nn.logsumexp(
            jnp.where(sel, score, -jnp.inf), axis=-1, keepdims=True)
        logp = jnp.log(jnp.where(target > 0, target, 1.0))
        kl = jnp.where(sel & (qpos < n)[None, :, None],
                       target * (logp - log_q), 0.0).sum((1, 2))
        return o, kl

    o, kl = jax.lax.map(block, (jnp.arange(nb), split(q, 2),
                                        split(qi, 1), split(w, 1)))
    o = jnp.moveaxis(o, 0, 2).reshape(rows, h, -1, d)[:, :, :n]
    o = o.transpose(0, 2, 1, 3).reshape(rows, n, h * d)
    return _mm("rlc,oc->rlo", o, P[p + "o_proj_weight"], quant), \
        kl.sum(0) / n


def route(P, p, u, cfg, quant=None):
    """(experts (T, k) int32, weights (T, k)) of the flat tokens u (T, d)."""
    r = jax.nn.softmax(_mm("tc,ec->te", u, P[p + "router_weight"], quant),
                       axis=-1)
    _top, experts = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(r, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w


def experts_layer(P, p, u, cfg, quant=None, first=None, count=None):
    """The routed part the experts ``first .. first + count - 1`` give (the
    configuration's share by default)."""
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    experts, w = route(P, p, u, cfg, quant)
    first = cfg.get("experts_held_first", 0) if first is None else first
    count = cfg["num_experts"] if count is None else count

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
    return out.reshape(shape)


def layer(h, P, cfg, quant=None, fault=None):
    """One layer ``h -> (h, the indexer's loss (R,))`` over (R, L, d); P
    holds the layer's leaves by their names without the layer's prefix.
    Each sublayer is recomputed inside its own gradient."""
    eps = cfg["rms_norm_eps"]

    def attn(h, P):
        y, kl = attention(P, "attn_", _rms(h, P["attn_norm_gamma"], eps),
                          cfg, quant, fault)
        return h + y, kl

    def moe(h, P):
        return h + experts_layer(P, "moe_", _rms(h, P["moe_norm_gamma"], eps),
                                 cfg, quant)

    h, kl = jax.checkpoint(attn)(h, P)
    return jax.checkpoint(moe)(h, P), kl


def logits_of(h, gain, head, cfg, quant=None):
    return _mm("rlc,vc->rlv", _rms(h, gain, cfg["rms_norm_eps"]), head, quant)


def forward(cfg, P, tokens, quant=None, fault=None):
    """(logits, the layers' indexer loss summed (R,)) of tokens (R, L) under
    float32 leaves P: the whole model at once, for the tests."""
    h = P["embed_weight"][tokens]
    total = 0.0
    for i in range(len(cfg["layer_types"])):
        h, kl = layer(h, _sub(P, f"layer{i}_"), cfg, quant, fault)
        total = total + kl
    return logits_of(h, P["norm_f_gamma"], P["head_weight"], cfg,
                     quant), total


def head_loss_sum(h, gain, head, tokens, cfg, quant=None):
    """Sum over rows and positions t = 0 .. L-2 of CE(logits_t, token t+1),
    from h (R, L, d); a block of positions' logits at a time."""
    rows, length, _ = h.shape
    n = length - 1
    blk = min(HEAD_BLOCK, n)
    pad = (-n) % blk
    xs = jnp.pad(h[:, :n], ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(tokens[:, 1:], ((0, 0), (0, pad)))
    live = jnp.pad(jnp.ones((rows, n), jnp.float32), ((0, 0), (0, pad)))

    @jax.checkpoint
    def block(args):
        x, lab, wt = args
        logp = jax.nn.log_softmax(logits_of(x, gain, head, cfg, quant), -1)
        return -(jnp.take_along_axis(logp, lab[..., None], -1)[..., 0]
                 * wt).sum()

    def split(t):
        return jnp.moveaxis(t.reshape((rows, -1, blk) + t.shape[2:]), 1, 0)

    return jax.lax.map(block, (split(xs), split(labels), split(live))).sum()


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


def _sub(tree, prefix):
    return {k[len(prefix):]: a for k, a in tree.items()
            if k.startswith(prefix)}


@functools.lru_cache(maxsize=32)
def _fns(cfg_key, quant, fault):
    cfg = json.loads(cfg_key)

    def bwd(h, P, dh, dkl):
        _y, pull = jax.vjp(lambda h, P32: layer(h, P32, cfg, quant, fault),
                           h, _f32(P))
        return pull((dh, dkl))

    def head(h, gain, weight_head, tokens, scale):
        return jax.value_and_grad(
            lambda h, g, w: scale * head_loss_sum(h, g, w, tokens, cfg,
                                                  quant),
            argnums=(0, 1, 2))(h, gain.astype(jnp.float32),
                               weight_head.astype(jnp.float32))

    return {"fwd": jax.jit(lambda h, P: layer(h, _f32(P), cfg, quant, fault)),
            "bwd": jax.jit(bwd), "head": jax.jit(head),
            "embed_bwd": jax.jit(lambda tok, de, g: g.at[tok].add(de))}


def _freeze(cfg):
    """The configuration's sizes as a hashable key of the jit cache."""
    return json.dumps({k: v for k, v in cfg.items()
                       if k not in ("assumed", "rehearsal", "program", "flops",
                                    "deployment", "depth", "reduced_from",
                                    "optimizer")}, sort_keys=True)


def _gradient(cfg, fns, stored, blocks, each, scale, kl_scale):
    """The loss and, handed to ``each(prefix, grads)`` group by group as soon
    as the backward pass has it, the float32 gradient over ``blocks`` (nb,
    R, L) of tokens; the head's and the embedding's come last, once every
    block has been through."""
    n = len(cfg["layer_types"])
    embed = stored["embed_weight"].astype(jnp.float32)
    acts, total = [], 0.0
    for tok in blocks:
        h, hs = embed[tok], []
        for i in range(n):
            hs.append(h)
            h, kl = fns["fwd"](h, _sub(stored, f"layer{i}_"))
            total = total + kl_scale * kl.sum()
        acts.append(hs + [h])
    g_head, g_norm, dhs = None, None, []
    for tok, hs in zip(blocks, acts):
        loss, (dh, gn, gw) = fns["head"](
            hs.pop(), stored["norm_f_gamma"], stored["head_weight"], tok,
            scale)
        total = total + loss
        g_head = gw if g_head is None else g_head + gw
        g_norm = gn if g_norm is None else g_norm + gn
        dhs.append(dh)
    each("", {"norm_f_gamma": g_norm, "head_weight": g_head})
    del g_head
    add = functools.partial(jax.tree_util.tree_map, jnp.add)
    for i in reversed(range(n)):
        P, g = _sub(stored, f"layer{i}_"), None
        for b, hs in enumerate(acts):
            dkl = jnp.full((blocks.shape[1],), kl_scale, jnp.float32)
            dhs[b], gp = fns["bwd"](hs.pop(), P, dhs[b], dkl)
            g = gp if g is None else add(g, gp)
        each(f"layer{i}_", g)
    g_embed = jnp.zeros_like(embed)
    for tok, dh in zip(blocks, dhs):
        g_embed = fns["embed_bwd"](tok, dh, g_embed)
    each("", {"embed_weight": g_embed})
    return total


def train(cfg, weights, tokens, seed, steps, rows_per_block, quant=None,
          rows=None, probe=None, fault=None):
    """``steps`` training steps on ``tokens`` (batch, seq) from ``weights``
    (stored type).  ``rows`` restricts the batch, ``fault`` plants a
    selection fault (``"dense"``, ``"recent"``); ``rows_per_block`` is the
    most rows worked on at once; ``seed`` is unused (no random stream).
    Returns ``loss`` per step, ``grad_norm`` per leaf at step 1,
    ``delta_norm`` per leaf after the last step and, where ``probe`` is
    given, ``grad_sketch``: what it returns for the first gradient."""
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
    scale = jnp.float32(1.0 / (len(use) * (seq - 1)))
    kl_scale = 1.0 / len(use)
    fns = _fns(_freeze(cfg), quant, fault)
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

        losses.append(float(_gradient(cfg, fns, stored, blocks, each, scale,
                                      kl_scale)))
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
