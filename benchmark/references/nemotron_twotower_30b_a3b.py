"""Plain reference of next-token training of ONE tower of
Nemotron-Labs-TwoTower-30B-A3B as ``configs/nemotron_twotower_30b_a3b.json``
states it: jax.numpy, float32, matmuls at ``highest``, no kernels.  Imports
nothing of the program.

The tower (what the published ``config.json`` defines, ``model_type``
``nemotron_h``): token embedding; one pre-norm residual layer per letter of
``hybrid_override_pattern``, ``x <- x + mixer(RMSNorm(x))``; a final RMSNorm;
an untied vocabulary head.  No bias except the convolution's, eps 1e-5, no
rotary position (the family's modelling code applies none).

``M`` (Mamba-2): ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv1d(xBC) + b)``
(depthwise, causal, width ``conv_kernel``); ``x`` (heads x head_dim), ``B``,
``C`` (``n_groups`` x ``ssm_state_size``, a group serves heads / groups
consecutive heads); ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``;
``h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``:
computed here as THE RECURRENCE ITSELF, a ``lax.scan`` over positions
(``jax.checkpoint`` over segments so its backward fits), never by chunks;
``y = RMSNorm(y * silu(z))`` over groups of inner / n_groups channels with a
per-channel gain; ``out = y W_out``.

``E`` (experts): ``s = sigmoid(u W_r)``; the choices are the top
``num_experts_per_tok`` of ``s + e_score_correction_bias``; a choice's
weight is its ``s`` over the choices' sum (+1e-20), times
``routed_scaling_factor``; ``out = sum_e w_e relu2(u W_up,e) W_down,e +
relu2(u W_up,s) W_down,s``.  THIS CHIP'S SHARE: the router is
``n_routed_experts_published`` wide and normalises over all its choices;
only experts ``experts_held_first .. + n_routed_experts - 1`` are here, as a
plain loop over them with a dense mask; what the absent experts would add
is left out and that partial result goes on.  ``load`` and ``load_max`` are
the program's aux leaves (no gradient reaches them).  ``router_lr_mult``
scales the router weights' learning rate (0 in the configuration: this chip
has only its share of the router's gradient, whose sum over the 16 chips is
the absent exchange's to make; the gradient and its moments are still
computed and compared).

``*`` (attention): ``num_attention_heads`` query heads over
``num_key_value_heads`` key-value heads (K and V repeated), causal
``softmax(q k^T / sqrt(head_dim)) v`` in row blocks, ``W_o``.

Loss: mean next-token cross-entropy over positions 0 .. L-2 (the label of
position t is token t+1), logits over the held slice of the vocabulary.

Optimizer: Adam without weight decay on parameters STORED in the
configuration's type with no float32 master copy: each update is computed
in float32 from the stored value and rounded back.  The backward pass is
written out layer by layer (the stack is sequential), so that no whole
float32 gradient has to live beside the moments from step 2 on (667 M leaves
cost 2.67 GB in float32 each).

``quant="fp8"`` is the control of the comparison: both operands of every
matmul (in the recurrence: ``x``, ``B`` and ``C``) rounded to 8-bit floats
(e4m3, per-tensor scale, straight-through gradient).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
SEGMENT = 64        # positions per checkpointed segment of the recurrence
QUERY_BLOCK = 128   # query rows per block of the dense attention


def _dims(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = h * p
    return h, p, g, n, inner, inner + 2 * g * n


def param_spec(cfg):
    """Ordered (name, shape, init) of every leaf of the program's state.
    Projections, embedding and head N(0, initializer_range); the convolution
    wide enough that x, B and C are of order one; gains 1; ``dt_bias`` and
    ``A_log`` such that dt falls in the published 0.001 to 0.1 and the
    decays spread over heads; the router's correction small and not zero;
    the load counters (aux) 0."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    w, one, zero = ("normal", std), ("const", 1.0), ("const", 0.0)
    h, _p, _g, _n, inner, conv_dim = _dims(cfg)
    held, wide = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    spec = [("embed_weight", (v, d), w)]
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        p = f"layer{i}_"
        spec.append((p + "norm_gamma", (d,), one))
        p += "mixer_"
        if letter == "M":
            spec += [(p + "in_proj_weight", (inner + conv_dim + h, d), w),
                     (p + "conv_weight", (conv_dim, cfg["conv_kernel"]),
                      ("normal", 0.5)),
                     (p + "conv_bias", (conv_dim,), ("normal", 0.1)),
                     (p + "A_log", (h,), ("normal", 1.0)),
                     (p + "D", (h,), one),
                     (p + "dt_bias", (h,), ("const", -4.6)),
                     (p + "norm_gamma", (inner,), one),
                     (p + "out_proj_weight", (d, inner), w)]
        elif letter == "E":
            spec += [(p + "router_weight", (wide, d), w),
                     (p + "e_score_correction_bias", (wide,),
                      ("normal", 0.01)),
                     (p + "experts_up_weight", (held, d, f), w),
                     (p + "experts_down_weight", (held, f, d), w),
                     (p + "shared_up_weight", (fs, d), w),
                     (p + "shared_down_weight", (d, fs), w),
                     (p + "load", (held,), zero),
                     (p + "load_max", (held,), zero)]
        elif letter == "*":
            spec += [(p + "q_proj_weight", (qd, d), w),
                     (p + "k_proj_weight", (kvd, d), w),
                     (p + "v_proj_weight", (kvd, d), w),
                     (p + "o_proj_weight", (d, qd), w)]
        else:
            raise ValueError(f"layer letter {letter!r}")
    spec += [("norm_f_gamma", (d,), one), ("head_weight", (v, d), w)]
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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def recurrence(x, dt, a, b, c, d):
    """The state-space recurrence, position by position.  x (R, L, H, P);
    dt (R, L, H) after its softplus; a, d (H,); b, c (R, L, G, N).  Returns
    y (R, L, H, P).  Padded positions (to a whole number of segments) have
    dt = 0 and x = 0: the state passes through them unchanged."""
    rows, length, heads, p = x.shape
    rep, n = heads // b.shape[2], b.shape[3]
    pad = (-length) % SEGMENT
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    segs = (length + pad) // SEGMENT

    def by_segment(t):      # (R, L, ...) -> (segments, SEGMENT, R, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((segs, SEGMENT) + t.shape[1:])

    def position(h, inp):
        x_t, dt_t, b_t, c_t = inp
        bh = jnp.repeat(b_t, rep, axis=1)                  # (R, H, N)
        ch = jnp.repeat(c_t, rep, axis=1)
        h = h * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * bh[:, :, None, :]
        y = jnp.einsum("rhpn,rhn->rhp", h, ch, precision=HI) \
            + d[:, None] * x_t
        return h, y

    @jax.checkpoint
    def segment(h, inps):
        return jax.lax.scan(position, h, inps)

    h0 = jnp.zeros((rows, heads, p, n), jnp.float32)
    _h, ys = jax.lax.scan(segment, h0,
                          tuple(by_segment(t) for t in (x, dt, b, c)))
    ys = ys.reshape((segs * SEGMENT,) + ys.shape[2:])
    return jnp.moveaxis(ys, 0, 1)[:, :length]


def _mamba(P, p, u, cfg, quant):
    h, hp, g, n, inner, conv_dim = _dims(cfg)
    rows, length, _ = u.shape
    zxbcdt = _mm("blc,oc->blo", u, P[p + "in_proj_weight"], quant)
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv_dim],
                  zxbcdt[..., inner + conv_dim:])
    k = cfg["conv_kernel"]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    wconv = P[p + "conv_weight"]
    xbc = sum(padded[:, i:i + length] * wconv[:, i] for i in range(k))
    xbc = jax.nn.silu(xbc + P[p + "conv_bias"])
    x = xbc[..., :inner].reshape(rows, length, h, hp)
    b = xbc[..., inner:inner + g * n].reshape(rows, length, g, n)
    c = xbc[..., inner + g * n:].reshape(rows, length, g, n)
    if quant == "fp8":
        x, b, c = _q8(x), _q8(b), _q8(c)
    dt = jax.nn.softplus(dt + P[p + "dt_bias"])
    y = recurrence(x, dt, -jnp.exp(P[p + "A_log"]), b, c, P[p + "D"])
    y = y.reshape(rows, length, inner) * jax.nn.silu(z)
    yg = y.reshape(rows, length, g, inner // g)
    yg = yg * jax.lax.rsqrt(jnp.square(yg).mean(-1, keepdims=True)
                            + cfg["layer_norm_epsilon"])
    y = yg.reshape(rows, length, inner) * P[p + "norm_gamma"]
    return _mm("blc,oc->blo", y, P[p + "out_proj_weight"], quant)


def route(P, p, u, cfg, quant=None):
    """(experts (T, k) int32, weights (T, k)) of the flat tokens u (T, d),
    over all the published experts."""
    s = jax.nn.sigmoid(_mm("tc,ec->te", u, P[p + "router_weight"], quant))
    _top, experts = jax.lax.top_k(
        s + jax.lax.stop_gradient(P[p + "e_score_correction_bias"]),
        cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return experts, w * cfg["routed_scaling_factor"]


def _experts(P, p, u, cfg, quant):
    shape = u.shape
    u = u.reshape(-1, shape[-1])
    experts, w = route(P, p, u, cfg, quant)
    out = _mm("tf,cf->tc", _relu2(_mm(
        "tc,fc->tf", u, P[p + "shared_up_weight"], quant)),
        P[p + "shared_down_weight"], quant)
    first = cfg["experts_held_first"]
    for e in range(cfg["n_routed_experts"]):
        gate = jnp.where(experts == first + e, w, 0.0).sum(-1)
        hid = _relu2(_mm("tc,cf->tf", u, P[p + "experts_up_weight"][e], quant))
        out = out + gate[:, None] * _mm(
            "tf,fc->tc", hid, P[p + "experts_down_weight"][e], quant)
    return out.reshape(shape)


def _attention(P, p, u, cfg, quant):
    rows, length, _ = u.shape
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])

    def heads(t, n):
        return t.reshape(rows, length, n, hd).transpose(0, 2, 1, 3)

    q = heads(_mm("blc,oc->blo", u, P[p + "q_proj_weight"], quant), nh)
    k = heads(_mm("blc,oc->blo", u, P[p + "k_proj_weight"], quant), nkv)
    v = heads(_mm("blc,oc->blo", u, P[p + "v_proj_weight"], quant), nkv)
    k, v = (jnp.repeat(t, nh // nkv, axis=1) for t in (k, v))
    blk = min(QUERY_BLOCK, length)
    pad = (-length) % blk
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(rows, nh, -1, blk, hd), 2, 0)
    kpos = jnp.arange(length)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = _mm("rhqd,rhkd->rhqk", qi, k, quant) / np.sqrt(hd)
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
        return _mm("rhqk,rhkd->rhqd", jax.nn.softmax(s, axis=-1), v, quant)

    o = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    o = jnp.moveaxis(o, 0, 2).reshape(rows, nh, -1, hd)[:, :, :length]
    o = o.transpose(0, 2, 1, 3).reshape(rows, length, nh * hd)
    return _mm("blc,oc->blo", o, P[p + "o_proj_weight"], quant)


MIXERS = {"M": _mamba, "E": _experts, "*": _attention}


def layer(letter, x, P, cfg, quant=None):
    """One layer: ``x + mixer(RMSNorm(x))``; P holds the layer's leaves by
    their names without the ``layer<i>_`` prefix."""
    u = _rms(x, P["norm_gamma"], cfg["layer_norm_epsilon"])
    return x + MIXERS[letter](P, "mixer_", u, cfg, quant)


def head_loss_sum(x, P, tokens, cfg, quant=None):
    """Sum of the next-token cross-entropies of rows ``tokens`` (R, L) over
    positions 0 .. L-2, from the residual stream x; one row's logits
    (L x vocabulary) at a time."""
    @jax.checkpoint
    def row(args):
        h, tok = args
        hfin = _rms(h, P["norm_f_gamma"], cfg["layer_norm_epsilon"])
        logits = _mm("lc,vc->lv", hfin, P["head_weight"], quant)
        logp = jax.nn.log_softmax(logits[:-1], axis=-1)
        return -jnp.take_along_axis(logp, tok[1:, None], -1).sum()

    return jax.lax.map(row, (x, tokens)).sum()


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


# The stack is sequential, so the backward pass is written out layer by
# layer: a layer's float32 gradient (and the float32 widening of its stored
# leaves) lives only while that layer is worked on, and from step 2 on its
# Adam update follows at once.  667 M leaves cost 2.67 GB in float32: a whole
# gradient beside the moments, the stored values and the caller's weights
# would not fit a 16 GB chip.  One compiled function per KIND of layer.
@functools.lru_cache(maxsize=32)
def _fns(cfg_items, quant):
    cfg = dict(cfg_items)

    def fwd(letter):
        return jax.jit(lambda x, P: layer(letter, x, _f32(P), cfg, quant))

    def bwd(letter):
        def run(x, P, dy):
            _y, pull = jax.vjp(
                lambda x, P32: layer(letter, x, P32, cfg, quant), x, _f32(P))
            return pull(dy)
        return jax.jit(run)

    def head(x, P, tokens):
        return jax.value_and_grad(
            lambda x, P32: head_loss_sum(x, P32, tokens, cfg, quant),
            argnums=(0, 1))(x, _f32(P))

    def embed_bwd(tokens, dx, rows):
        return jnp.zeros((rows, dx.shape[-1]), jnp.float32).at[tokens].add(dx)

    letters = set(cfg["hybrid_override_pattern"])
    return ({c: fwd(c) for c in letters}, {c: bwd(c) for c in letters},
            jax.jit(head), jax.jit(embed_bwd, static_argnums=2))


def _sub(tree, prefix):
    return {k[len(prefix):]: a for k, a in tree.items()
            if k.startswith(prefix)}


def _freeze(cfg):
    """The configuration's sizes as a hashable key of the jit cache."""
    def fz(v):
        if isinstance(v, dict):
            return tuple(sorted((k, fz(x)) for k, x in v.items()))
        if isinstance(v, list):
            return tuple(fz(x) for x in v)
        return v

    return tuple(sorted((k, fz(v)) for k, v in cfg.items()
                        if k not in ("assumed", "rehearsal", "program",
                                     "flops", "deployment", "depth",
                                     "reduced_from", "optimizer")))


def _gradient(cfg, fns, stored, blocks, each):
    """Loss sum and gradient sum over ``blocks`` (nb, R, L), handing each
    group of leaves' float32 gradient to ``each(prefix, grads)`` as soon as
    the backward pass has it."""
    fwd, bwd, head, embed_bwd = fns
    pattern = cfg["hybrid_override_pattern"]
    acts = []
    for tok in blocks:
        xs = [stored["embed_weight"].astype(jnp.float32)[tok]]
        for i, letter in enumerate(pattern):
            xs.append(fwd[letter](xs[-1], _sub(stored, f"layer{i}_")))
        acts.append(xs)
    add = functools.partial(jax.tree_util.tree_map, jnp.add)
    top = {k: stored[k] for k in ("norm_f_gamma", "head_weight")}
    total, dxs, g = 0.0, [], None
    for tok, xs in zip(blocks, acts):
        loss, (dx, gp) = head(xs.pop(), top, tok)
        total, g = total + loss, gp if g is None else add(g, gp)
        dxs.append(dx)
    each("", g)
    for i in reversed(range(len(pattern))):
        P, g = _sub(stored, f"layer{i}_"), None
        for b, xs in enumerate(acts):
            dxs[b], gp = bwd[pattern[i]](xs.pop(), P, dxs[b])
            g = gp if g is None else add(g, gp)
        each(f"layer{i}_", g)
    g = None
    for tok, dx in zip(blocks, dxs):
        gp = embed_bwd(tok, dx, stored["embed_weight"].shape[0])
        g = gp if g is None else g + gp
    each("", {"embed_weight": g})
    return total


def train(cfg, weights, tokens, seed, steps, rows_per_block, quant=None,
          rows=None, probe=None):
    """``steps`` training steps on ``tokens`` (batch, seq; the label of a
    position is the next token) from ``weights`` (stored type).  ``seed`` is
    unused: nothing here is random.  ``rows`` restricts the batch (a planted
    fault: the mean over those rows only); ``rows_per_block`` is the most
    rows worked on at once.  Returns ``loss`` per step, ``grad_norm`` per
    leaf at step 1, ``delta_norm`` per leaf after the last step and, where
    ``probe`` is given, ``grad_sketch``: what it returns for the first
    gradient."""
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
    n = len(use) * (seq - 1)
    fns = _fns(_freeze(cfg), quant)
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
            grads = {prefix + k: g / n for k, g in grads.items()}
            if t == 1:      # the whole first gradient is read before it goes
                held.update(grads)
            else:
                update(grads, t)

        losses.append(float(_gradient(cfg, fns, stored, blocks, each)) / n)
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
