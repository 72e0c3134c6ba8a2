"""Plain reference of BERT masked-LM pretraining as ``configs/
bert_base_mlm.json`` states it: jax.numpy, float32, matmuls at ``highest``,
no kernels, no batching tricks.  Imports nothing of the program.

The model (Devlin et al. 2018, post-LN encoder): token + learned position
embeddings, layer norm, dropout; L layers of [fused-qkv multi-head
self-attention with dropout on the probabilities, output projection,
dropout, residual, layer norm; GELU(erf) feed-forward, dropout, residual,
layer norm]; a units x units transform with GELU and layer norm; an
independent vocabulary-wide decoder.  Loss: mean cross-entropy over every
position (the label is the input token).  Departures from the paper, all the
program's (``mxnet_tpu/models/bert.py``) and stated in the configuration
file: no token-type embedding is added when none is passed, the decoder is
not tied to the embedding, layer-norm eps is 1e-5, the pooler is unused.

Dropout stream (what "the step takes its PRNG key as an argument" fixes):
``k = PRNGKey(seed)``; each step ``k, step_key = split(k)``; inside a step,
at every dropout site in forward order (embedding; per layer: attention
probabilities, attention output, feed-forward output)
``step_key, sub = split(step_key)`` and
``mask = bernoulli(sub, 1 - p, x.shape)``, ``y = x * mask / (1 - p)``, with
attention probabilities laid out (batch * heads, query, key).

Optimizer: Adam without weight decay on parameters STORED in bfloat16 with
no float32 master copy: the update is computed in float32 from the stored
value and rounded back to bfloat16 every step.

``quant="fp8"`` is the control of the comparison: the same mathematics with
both operands of every matmul rounded to 8-bit floats (e4m3, 3 mantissa
bits, per-tensor scale; straight-through gradient), the nearest precision
below the bfloat16 the configuration states.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def param_spec(cfg):
    """Ordered (name, shape, init) of every parameter.  Weights and
    embeddings N(0, initializer_range); biases small and not zero so that a
    dropped bias shows; layer-norm gains 1, offsets 0."""
    u, h, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    std = cfg["initializer_range"]
    w, b = ("normal", std), ("normal", std)
    one, zero = ("const", 1.0), ("const", 0.0)
    spec = [("bert_word_embed_weight", (v, u), w),
            ("bert_type_embed_weight", (cfg["type_vocab_size"], u), w),
            ("bert_pos_embed_weight", (cfg["max_position_embeddings"], u), w),
            ("bert_embed_ln_gamma", (u,), one),
            ("bert_embed_ln_beta", (u,), zero)]
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert_encoder_layer{i}_"
        spec += [(p + "attn_qkv_weight", (3 * u, u), w),
                 (p + "attn_qkv_bias", (3 * u,), b),
                 (p + "attn_proj_weight", (u, u), w),
                 (p + "attn_proj_bias", (u,), b),
                 (p + "ffn_ffn1_weight", (h, u), w),
                 (p + "ffn_ffn1_bias", (h,), b),
                 (p + "ffn_ffn2_weight", (u, h), w),
                 (p + "ffn_ffn2_bias", (u,), b),
                 (p + "ln1_gamma", (u,), one), (p + "ln1_beta", (u,), zero),
                 (p + "ln2_gamma", (u,), one), (p + "ln2_beta", (u,), zero)]
    spec += [("bert_pooler_weight", (u, u), w), ("bert_pooler_bias", (u,), b),
             ("mlm_dense_weight", (u, u), w), ("mlm_dense_bias", (u,), b),
             ("mlm_ln_gamma", (u,), one), ("mlm_ln_beta", (u,), zero),
             ("decoder_weight", (v, u), w), ("decoder_bias", (v,), b)]
    return spec


#: leaves no loss reaches: the program keeps them in its state, unmoved
UNUSED = ("bert_type_embed_weight", "bert_pooler_weight", "bert_pooler_bias")


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


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0)))


class _Drop:
    """The dropout stream of one step, sliced to rows [b0, b0 + rows) of a
    batch of ``batch`` rows."""

    def __init__(self, key, p, batch, b0, rows):
        self.key, self.keep, self.batch = key, 1.0 - p, batch
        self.b0, self.rows = b0, rows

    def __call__(self, x, per_row=1):
        """``per_row``: leading entries per batch row (heads for the
        attention probabilities)."""
        if self.keep >= 1.0:
            return x
        self.key, sub = jax.random.split(self.key)
        full = (self.batch * per_row,) + x.shape[1:]
        mask = jax.random.bernoulli(sub, self.keep, full)
        mask = jax.lax.dynamic_slice_in_dim(
            mask, self.b0 * per_row, self.rows * per_row, 0)
        return x * mask.astype(x.dtype) / self.keep


def block_loss_sum(params, tokens, labels, step_key, b0, cfg, batch,
                   quant=None):
    """Sum of the per-position cross-entropies of rows [b0, b0 + R) of the
    batch, R = tokens.shape[0]; the step's loss is the sum over blocks over
    batch * seq."""
    R, T = tokens.shape
    u, H = cfg["hidden_size"], cfg["num_attention_heads"]
    hd, eps = u // H, cfg["layer_norm_eps"]
    drop = _Drop(step_key, cfg["hidden_dropout_prob"], batch, b0, R)
    pdrop = cfg["attention_probs_dropout_prob"]
    if pdrop != cfg["hidden_dropout_prob"]:
        raise ValueError("one dropout rate: the program has one")
    P = params
    x = P["bert_word_embed_weight"][tokens] + P["bert_pos_embed_weight"][:T]
    x = drop(_ln(x, P["bert_embed_ln_gamma"], P["bert_embed_ln_beta"], eps))
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert_encoder_layer{i}_"
        qkv = _mm("btc,oc->bto", x, P[p + "attn_qkv_weight"], quant) \
            + P[p + "attn_qkv_bias"]
        q, k, v = (t.reshape(R, T, H, hd).transpose(0, 2, 1, 3)
                   .reshape(R * H, T, hd) for t in jnp.split(qkv, 3, -1))
        s = _mm("nqd,nkd->nqk", q, k, quant) / np.sqrt(hd)
        a = drop(jax.nn.softmax(s, axis=-1), per_row=H)
        o = _mm("nqk,nkd->nqd", a, v, quant)
        o = o.reshape(R, H, T, hd).transpose(0, 2, 1, 3).reshape(R, T, u)
        o = _mm("btc,oc->bto", o, P[p + "attn_proj_weight"], quant) \
            + P[p + "attn_proj_bias"]
        x = _ln(x + drop(o), P[p + "ln1_gamma"], P[p + "ln1_beta"], eps)
        f = _gelu(_mm("btc,oc->bto", x, P[p + "ffn_ffn1_weight"], quant)
                  + P[p + "ffn_ffn1_bias"])
        f = _mm("btc,oc->bto", f, P[p + "ffn_ffn2_weight"], quant) \
            + P[p + "ffn_ffn2_bias"]
        x = _ln(x + drop(f), P[p + "ln2_gamma"], P[p + "ln2_beta"], eps)
    t = _gelu(_mm("btc,oc->bto", x, P["mlm_dense_weight"], quant)
              + P["mlm_dense_bias"])
    t = _ln(t, P["mlm_ln_gamma"], P["mlm_ln_beta"], eps)
    logits = _mm("btc,vc->btv", t, P["decoder_weight"], quant) \
        + P["decoder_bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], -1).sum()


@functools.lru_cache(maxsize=8)
def _block_grad(cfg_items, batch, quant):
    cfg = dict(cfg_items)

    def f(params, tokens, labels, step_key, b0):
        return block_loss_sum(params, tokens, labels, step_key, b0, cfg,
                              batch, quant)

    return jax.jit(jax.value_and_grad(f))


def _norms(tree):
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def train(cfg, weights, tokens, seed, steps, rows_per_block, quant=None,
          rows=None, probe=None):
    """``steps`` training steps on ``tokens`` (batch, seq; the label is the
    token) from ``weights`` (stored type), as the configuration states them.
    ``rows`` restricts the batch (a planted fault: the mean over those rows
    only).  Returns the numbers the comparison reads:
    ``loss`` per step, ``grad_norm`` per leaf at step 1, ``delta_norm`` per
    leaf after the last step and, where the caller gives ``probe`` (a
    function of the gradient's leaves), ``grad_sketch``: what it returns for
    the first gradient."""
    opt = cfg["optimizer"]
    lr, b1, b2, eps = (opt["learning_rate"], opt["beta1"], opt["beta2"],
                       opt["epsilon"])
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, str))))
    batch, seq = tokens.shape
    use = np.arange(batch) if rows is None else np.asarray(rows)
    fn = _block_grad(cfg_items, batch, quant)
    stored = dict(weights)
    start = {k: v.astype(jnp.float32) for k, v in stored.items()}
    m = {k: jnp.zeros(v.shape, jnp.float32) for k, v in stored.items()}
    vv = {k: jnp.zeros(v.shape, jnp.float32) for k, v in stored.items()}
    key = jax.random.PRNGKey(int(seed))
    losses, grad_norm = [], None
    tokens = jnp.asarray(tokens, jnp.int32)

    @jax.jit
    def adam(p, g, m, v, t):
        corr = jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        new = p.astype(jnp.float32) - lr * corr * m / (jnp.sqrt(v) + eps)
        return new.astype(p.dtype), m, v

    for t in range(1, steps + 1):
        key, step_key = jax.random.split(key)
        p32 = {k: v.astype(jnp.float32) for k, v in stored.items()}
        total, grads = 0.0, None
        if len(use) % rows_per_block:
            raise ValueError("rows_per_block must divide the rows used")
        for i in range(0, len(use), rows_per_block):
            blk = use[i:i + rows_per_block]
            if not np.array_equal(blk, np.arange(blk[0], blk[0] + len(blk))):
                raise ValueError("row blocks must be contiguous")
            tk = tokens[blk[0]:blk[0] + len(blk)]
            ls, g = fn(p32, tk, tk, step_key, jnp.int32(blk[0]))
            total = total + ls
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
        n = len(use) * seq
        grads = {k: g / n for k, g in grads.items()}
        losses.append(float(total) / n)
        if t == 1:
            grad_norm = _norms(grads)
            sketch = None if probe is None else jax.device_get(probe(grads))
        for k in stored:
            stored[k], m[k], vv[k] = adam(stored[k], grads[k], m[k], vv[k],
                                          jnp.float32(t))
        del grads, p32
    delta = _norms({k: stored[k].astype(jnp.float32) - start[k]
                    for k in stored})
    return {"loss": losses, "grad_norm": grad_norm, "delta_norm": delta,
            "grad_sketch": sketch}
