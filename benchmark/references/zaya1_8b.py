"""Plain reference of next-token training of a stage of ZAYA1-8B as
``configs/zaya1_8b.json`` states it: jax.numpy, float32, matmuls at
``highest``, no kernels.  Imports nothing of the program.

The model (``model_type`` ``zaya``; equations after arXiv:2510.04476,
"Compressed Convolutional Attention", and arXiv:2511.17127, the ZAYA1
technical report): token embedding; ``len(layer_types)`` layers, each an
attention sublayer and an expert sublayer; a final RMSNorm; the embedding
as the vocabulary head.  ``u = RMSNorm(x)`` (eps ``rms_norm_eps``, a gain a
channel) before each sublayer; ``res(x, f) = (a * x + c) + f`` with learned
vectors ``a``, ``c`` a sublayer.  The router's representation ``r`` goes from
layer to layer beside ``x``; before the first held layer it is 0.

Attention (H = ``num_attention_heads`` query heads over Hkv =
``num_key_value_heads`` key-value heads of D = ``head_dim``; a key-value head
serves H / Hkv consecutive query heads): ``q0 = u W_q`` (H D wide), ``k0 = u
W_k`` (Hkv D wide), ``v = [u_t W_v1 | u_(t-1) W_v2]`` (``u_(-1)`` = 0: the
second half of the key-value heads reads the token before).  ``q1 =
conv_b(conv_a(q0))``, ``k1`` likewise with its own weights: ``conv_a``
causal, depthwise, ``cca_time0`` taps; ``conv_b`` causal, ``cca_time1`` taps,
a (D x D) matrix a head a tap; both with a bias; here as shifted sums.  ``q2 =
q1 + (q0 + rep(k0)) / 2``, ``k2 = k1 + (grp(q0) + k0) / 2`` (``rep`` repeats a
key-value head over its query heads, ``grp`` averages a group's query heads).
``q3 = sqrt(D) q2 / (|q2| + 1e-6)``, ``k3 = tau_h sqrt(D) k2 / (|k2| + 1e-6)``
a head.  Rotary position on the first ``partial_rotary_factor`` of every q
and k head (rotate-half pairing, ``rope_theta``).  ``o = causal softmax(q k^T
/ sqrt(D)) v`` dense, in row blocks, K and V repeated; ``x <- res(x, o W_o)``.

Experts (``num_experts`` of them, ``num_experts_per_tok`` a token): ``r_l = u
W_in + g_l * r_(l-1)``; ``s = W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) + b_2)
+ b_3``; ``p = softmax(s)``; the choice is the top of ``p + b`` (``b`` takes no
gradient); ``y = p_e (silu(u W_gate,e) * (u W_up,e)) W_down,e`` as a plain
loop over the held experts with a dense mask; ``x <- res(x, y)``.

DEPARTURES, where the papers and the config leave a choice (the
configuration's ``assumed`` has the same list): the order norm-then-rotary;
the SECOND half of the key-value heads is the shifted one; ``rep``/``grp`` as
the way the mean crosses unequal head counts; the convolutions carry biases;
the router's inner RMSNorm, its biases and ``gelu`` in its tanh form; the
form of ``res``; no expert that skips computation; ``tau`` and ``a`` start at
1, ``c``, ``g`` and every bias at 0, the balancing bias N(0, 1e-4) and held
fixed (with every matrix at 0.02 the probabilities are 1/16 +- 5e-4: a
larger bias would choose for every token), every matrix N(0,
``initializer_range``), the depthwise taps N(0, 0.5); no balancing update or
auxiliary loss.

Loss: mean next-token cross-entropy over positions 0 .. L-2, logits over the
held slice of the vocabulary.  Optimizer: Adam without weight decay on
parameters STORED in the configuration's type with no float32 master copy.
The backward pass is written out layer by layer (the stack is sequential),
so that no whole float32 gradient has to live beside the moments from step 2
on; the tied matrix's gradient is the sum of the head's and the look-up's.

``quant="fp8"`` is the control of the comparison: both operands of every
matmul rounded to 8-bit floats (e4m3, per-tensor scale, straight-through
gradient).  ``positions`` is a planted fault for a batch of one row.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 128   # query rows per block of the dense attention
HEAD_BLOCK = 1024   # positions per block of the head's logits


def _sizes(cfg):
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return h, hkv, d, h * d, hkv * d


def _theta(cfg):
    return cfg["rope_parameters"]["hybrid"]["rope_theta"]


def param_spec(cfg):
    """Ordered (name, shape, init) of every leaf of the program's state."""
    dm, v = cfg["hidden_size"], cfg["vocab_size"]
    w = ("normal", cfg["initializer_range"])
    one, zero = ("const", 1.0), ("const", 0.0)
    h, hkv, d, qd, kd = _sizes(cfg)
    t0, t1 = cfg["cca_time0"], cfg["cca_time1"]
    held = cfg["num_experts"]
    wide = cfg.get("num_experts_published", held)
    f, rw = cfg["moe_intermediate_size"], cfg["router_hidden_size"]
    spec = [("embed_weight", (v, dm), w)]
    for i in range(len(cfg["layer_types"])):
        p = f"layer{i}_attn_"
        spec += [(f"layer{i}_attn_norm_gamma", (dm,), one),
                 (p + "q_proj_weight", (qd, dm), w),
                 (p + "k_proj_weight", (kd, dm), w),
                 (p + "v1_proj_weight", (kd // 2, dm), w),
                 (p + "v2_proj_weight", (kd // 2, dm), w)]
        for name, width, heads in (("q", qd, h), ("k", kd, hkv)):
            spec += [(p + name + "_conv_a_weight", (width, t0),
                      ("normal", 0.5)),
                     (p + name + "_conv_a_bias", (width,), zero),
                     (p + name + "_conv_b_weight", (heads, t1, d, d), w),
                     (p + name + "_conv_b_bias", (width,), zero)]
        spec += [(p + "tau", (hkv,), one),
                 (p + "o_proj_weight", (dm, qd), w),
                 (p + "res_a", (dm,), one), (p + "res_c", (dm,), zero)]
        p = f"layer{i}_moe_"
        spec += [(f"layer{i}_moe_norm_gamma", (dm,), one),
                 (p + "router_in_weight", (rw, dm), w),
                 (p + "router_depth_gain", (rw,), zero),
                 (p + "router_norm_gamma", (rw,), one),
                 (p + "router_fc1_weight", (rw, rw), w),
                 (p + "router_fc1_bias", (rw,), zero),
                 (p + "router_fc2_weight", (rw, rw), w),
                 (p + "router_fc2_bias", (rw,), zero),
                 (p + "router_fc3_weight", (wide, rw), w),
                 (p + "router_fc3_bias", (wide,), zero),
                 (p + "router_balance_bias", (wide,), ("normal", 1e-4)),
                 (p + "experts_gate_weight", (held, dm, f), w),
                 (p + "experts_up_weight", (held, dm, f), w),
                 (p + "experts_down_weight", (held, f, dm), w),
                 (p + "load", (held,), zero),
                 (p + "load_max", (held,), zero),
                 (p + "res_a", (dm,), one), (p + "res_c", (dm,), zero)]
    spec.append(("norm_f_gamma", (dm,), one))
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


def _shift(x, n):
    """``x`` (R, L, ...) moved ``n`` positions later, zeros before."""
    if n == 0:
        return x
    pad = ((0, 0), (n, 0)) + ((0, 0),) * (x.ndim - 2)
    return jnp.pad(x, pad)[:, :x.shape[1]]


def conv_depthwise(x, w, b):
    """``y_t = sum_k w[:, k] x_(t-K+1+k) + b`` over x (R, L, C), w (C, K)."""
    k = w.shape[1]
    return sum(_shift(x, k - 1 - i) * w[:, i] for i in range(k)) + b


def conv_heads(x, w, b, quant=None):
    """``y_t[h] = sum_k x_(t-K+1+k)[h] w[h, k] + b`` over x (R, L, H D), w
    (H, K, D, D): a (D in, D out) matrix a head a tap."""
    heads, k, d, _ = w.shape
    xh = x.reshape(x.shape[:2] + (heads, d))
    out = sum(_mm("rlhd,hde->rlhe", _shift(xh, k - 1 - i), w[:, i], quant)
              for i in range(k))
    return out.reshape(x.shape) + b


def rotary(x, theta, fraction):
    """Rotary position on the first ``fraction`` of the last axis of x (R,
    L, H, D), positions along axis 1, rotate-half pairing."""
    rot = int(round(x.shape[-1] * fraction))
    half = rot // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def qkv(P, p, u, cfg, quant=None):
    """The attention sublayer up to the attention call: q (R, L, H, D), k and
    v (R, L, Hkv, D)."""
    h, hkv, d, _qd, _kd = _sizes(cfg)
    rows, length, _ = u.shape
    q0 = _mm("rlc,oc->rlo", u, P[p + "q_proj_weight"], quant)
    k0 = _mm("rlc,oc->rlo", u, P[p + "k_proj_weight"], quant)
    v1 = _mm("rlc,oc->rlo", u, P[p + "v1_proj_weight"], quant)
    v2 = _mm("rlc,oc->rlo", _shift(u, 1), P[p + "v2_proj_weight"], quant)
    q1 = conv_heads(conv_depthwise(q0, P[p + "q_conv_a_weight"],
                                   P[p + "q_conv_a_bias"]),
                    P[p + "q_conv_b_weight"], P[p + "q_conv_b_bias"], quant)
    k1 = conv_heads(conv_depthwise(k0, P[p + "k_conv_a_weight"],
                                   P[p + "k_conv_a_bias"]),
                    P[p + "k_conv_b_weight"], P[p + "k_conv_b_bias"], quant)
    q0h = q0.reshape(rows, length, hkv, h // hkv, d)
    k0h = k0.reshape(rows, length, hkv, d)
    q2 = q1.reshape(q0h.shape) + (q0h + k0h[:, :, :, None]) / 2
    k2 = k1.reshape(k0h.shape) + (q0h.mean(3) + k0h) / 2

    def unit(t):
        return np.sqrt(d) * t / (
            jnp.sqrt(jnp.square(t).sum(-1, keepdims=True)) + 1e-6)

    q3 = unit(q2).reshape(rows, length, h, d)
    k3 = unit(k2) * P[p + "tau"][:, None]
    frac = cfg["partial_rotary_factor"]
    v = jnp.concatenate([v1, v2], -1).reshape(rows, length, hkv, d)
    return rotary(q3, _theta(cfg), frac), rotary(k3, _theta(cfg), frac), v


def _attention(P, p, u, cfg, quant):
    h, hkv, d, qd, _kd = _sizes(cfg)
    rows, length, _ = u.shape
    q, k, v = (t.transpose(0, 2, 1, 3) for t in qkv(P, p, u, cfg, quant))
    k, v = (jnp.repeat(t, h // hkv, axis=1) for t in (k, v))
    blk = min(QUERY_BLOCK, length)
    pad = (-length) % blk
    qb = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qb = jnp.moveaxis(qb.reshape(rows, h, -1, blk, d), 2, 0)
    kpos = jnp.arange(length)

    @jax.checkpoint
    def block(args):
        i, qi = args
        s = _mm("rhqd,rhkd->rhqk", qi, k, quant) / np.sqrt(d)
        qpos = i * blk + jnp.arange(blk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -1e30)
        return _mm("rhqk,rhkd->rhqd", jax.nn.softmax(s, axis=-1), v, quant)

    o = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    o = jnp.moveaxis(o, 0, 2).reshape(rows, h, -1, d)[:, :, :length]
    o = o.transpose(0, 2, 1, 3).reshape(rows, length, qd)
    return _mm("rlc,oc->rlo", o, P[p + "o_proj_weight"], quant)


def route(P, p, u, r, cfg, quant=None):
    """(r_l, experts (T, k) int32, weights (T, k)) of u (R, L, d) and the
    representation before, r (R, L, router_hidden_size)."""
    r = _mm("rlc,oc->rlo", u, P[p + "router_in_weight"], quant) \
        + P[p + "router_depth_gain"] * r
    hid = _rms(r, P[p + "router_norm_gamma"], cfg["rms_norm_eps"])
    for fc in ("router_fc1_", "router_fc2_"):
        hid = jax.nn.gelu(_mm("rlc,oc->rlo", hid, P[p + fc + "weight"], quant)
                          + P[p + fc + "bias"], approximate=True)
    s = _mm("rlc,oc->rlo", hid, P[p + "router_fc3_weight"], quant) \
        + P[p + "router_fc3_bias"]
    prob = jax.nn.softmax(s.reshape(-1, s.shape[-1]), axis=-1)
    _top, experts = jax.lax.top_k(
        prob + jax.lax.stop_gradient(P[p + "router_balance_bias"]),
        cfg["num_experts_per_tok"])
    return r, experts, jnp.take_along_axis(prob, experts, axis=-1)


def _experts(P, p, u, r, cfg, quant):
    shape = u.shape
    r, experts, w = route(P, p, u, r, cfg, quant)
    u = u.reshape(-1, shape[-1])
    first = cfg.get("experts_held_first", 0)

    @jax.checkpoint
    def one(out, args):
        e, gate_w, up_w, down_w = args
        gate = jnp.where(experts == first + e, w, 0.0).sum(-1)
        hid = jax.nn.silu(_mm("tc,cf->tf", u, gate_w, quant)) \
            * _mm("tc,cf->tf", u, up_w, quant)
        return out + gate[:, None] * _mm("tf,fc->tc", hid, down_w, quant), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u), (
        jnp.arange(cfg["num_experts"]), P[p + "experts_gate_weight"],
        P[p + "experts_up_weight"], P[p + "experts_down_weight"]))
    return out.reshape(shape), r


def layer(x, r, P, cfg, quant=None):
    """One layer: ``(x, r) -> (x, r)``; P holds the layer's leaves by their
    names without the ``layer<i>_`` prefix."""
    eps = cfg["rms_norm_eps"]
    o = _attention(P, "attn_", _rms(x, P["attn_norm_gamma"], eps), cfg, quant)
    x = (P["attn_res_a"] * x + P["attn_res_c"]) + o
    y, r = _experts(P, "moe_", _rms(x, P["moe_norm_gamma"], eps), r, cfg,
                    quant)
    return (P["moe_res_a"] * x + P["moe_res_c"]) + y, r


def head_loss_sum(x, P, tokens, cfg, quant=None, positions=None):
    """Sum of the next-token cross-entropies of rows ``tokens`` (R, L) over
    positions 0 .. L-2 (the first ``positions`` of them when given), from
    the residual stream x; a block of positions' logits at a time."""
    rows, length, dm = x.shape
    n = length - 1 if positions is None else positions
    blk = min(HEAD_BLOCK, n)
    pad = (-n) % blk
    xs = jnp.pad(x[:, :n], ((0, 0), (0, pad), (0, 0)))
    labels = jnp.pad(tokens[:, 1:n + 1], ((0, 0), (0, pad)))
    live = jnp.pad(jnp.ones((rows, n)), ((0, 0), (0, pad)))

    @jax.checkpoint
    def block(args):
        h, lab, keep = args
        hfin = _rms(h, P["norm_f_gamma"], cfg["rms_norm_eps"])
        logits = _mm("rlc,vc->rlv", hfin, P["embed_weight"], quant)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -(jnp.take_along_axis(logp, lab[..., None], -1)[..., 0]
                 * keep).sum()

    def split(t):
        return jnp.moveaxis(t.reshape((rows, -1, blk) + t.shape[2:]), 1, 0)

    return jax.lax.map(block, (split(xs), split(labels), split(live))).sum()


def _f32(tree):
    return {k: a.astype(jnp.float32) for k, a in tree.items()}


# The stack is sequential, so the backward pass is written out layer by
# layer: a layer's float32 gradient (and the float32 widening of its stored
# leaves, 0.83 GB each at 207.5 M leaves) lives only while that layer is
# worked on, and from step 2 on its Adam update follows at once.  897 M
# leaves cost 3.6 GB in float32: a whole gradient beside the moments, the
# stored values and the caller's weights would not fit a 16 GB chip.
@functools.lru_cache(maxsize=32)
def _fns(cfg_key, quant, positions):
    cfg = json.loads(cfg_key)

    def fwd(x, r, P):
        return layer(x, r, _f32(P), cfg, quant)

    def bwd(x, r, P, dx, dr):
        _y, pull = jax.vjp(
            lambda x, r, P32: layer(x, r, P32, cfg, quant), x, r, _f32(P))
        return pull((dx, dr))

    def head(x, P, tokens):
        return jax.value_and_grad(
            lambda x, P32: head_loss_sum(x, P32, tokens, cfg, quant,
                                         positions),
            argnums=(0, 1))(x, _f32(P))

    def embed_bwd(tokens, dx, g_head):
        return g_head.at[tokens].add(dx)

    return jax.jit(fwd), jax.jit(bwd), jax.jit(head), jax.jit(embed_bwd)


def _sub(tree, prefix):
    return {k[len(prefix):]: a for k, a in tree.items()
            if k.startswith(prefix)}


def _freeze(cfg):
    """The configuration's sizes as a hashable key of the jit cache."""
    return json.dumps({k: v for k, v in cfg.items()
                       if k not in ("assumed", "rehearsal", "program", "flops",
                                    "deployment", "depth", "reduced_from",
                                    "optimizer")}, sort_keys=True)


def _gradient(cfg, fns, stored, blocks, each):
    """Loss sum and gradient sum over ``blocks`` (nb, R, L), handing each
    group of leaves' float32 gradient to ``each(prefix, grads)`` as soon as
    the backward pass has it; the tied matrix's last, once the look-up's
    part has joined the head's."""
    fwd, bwd, head, embed_bwd = fns
    depth = len(cfg["layer_types"])
    width = cfg["router_hidden_size"]
    acts = []
    for tok in blocks:
        x = stored["embed_weight"].astype(jnp.float32)[tok]
        xs = [(x, jnp.zeros(x.shape[:2] + (width,), jnp.float32))]
        for i in range(depth):
            xs.append(fwd(*xs[-1], _sub(stored, f"layer{i}_")))
        acts.append(xs)
    add = functools.partial(jax.tree_util.tree_map, jnp.add)
    top = {k: stored[k] for k in ("norm_f_gamma", "embed_weight")}
    total, dxs, g = 0.0, [], None
    for tok, xs in zip(blocks, acts):
        x, r = xs.pop()
        loss, (dx, gp) = head(x, top, tok)
        total, g = total + loss, gp if g is None else add(g, gp)
        dxs.append((dx, jnp.zeros_like(r)))
    tied = g.pop("embed_weight")
    each("", g)
    for i in reversed(range(depth)):
        P, g = _sub(stored, f"layer{i}_"), None
        for b, xs in enumerate(acts):
            dx, dr, gp = bwd(*xs.pop(), P, *dxs[b])
            dxs[b] = (dx, dr)
            g = gp if g is None else add(g, gp)
        each(f"layer{i}_", g)
    for tok, (dx, _dr) in zip(blocks, dxs):
        tied = embed_bwd(tok, dx, tied)
    each("", {"embed_weight": tied})
    return total


def train(cfg, weights, tokens, seed, steps, rows_per_block, quant=None,
          rows=None, probe=None, positions=None):
    """``steps`` training steps on ``tokens`` (batch, seq; the label of a
    position is the next token) from ``weights`` (stored type).  ``seed`` is
    unused: nothing here is random.  ``rows`` restricts the batch and
    ``positions`` the loss to a row's first positions (planted faults: the
    mean over what is left only); ``rows_per_block`` is the most rows worked
    on at once.  Returns ``loss`` per step, ``grad_norm`` per leaf at step 1,
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
    n = len(use) * ((seq - 1) if positions is None else int(positions))
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
                router_mult if "_moe_router_" in k else one)

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
