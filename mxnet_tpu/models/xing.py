"""The ``xing4_0`` family (Xing4.0): latent attention, a residual path of
``n`` hyper-connection streams mixed by Sinkhorn-normalised matrices,
sigmoid top-k experts beside a shared one after leading dense layers, and a
multi-token-prediction module read by the trunk's head.

The streams of a token lie side by side, ``X`` (B, L, n d)
(``ops/hc_ops.py``); a layer is two hyper-connected sublayers, each with its
own mixing parameters (docs/XING.md writes the equations out and lists what
the public config leaves open):

* ``C = coefficients(X)``; ``u = sum_i H_pre[i] X_i``; ``y = F(RMSNorm(u))``;
  ``X'_i = sum_j H_res[i, j] X_j + H_post[i] y``.
* ``F`` = latent attention: ``c_q = RMSNorm(u W_qa)``, ``q = c_q W_qb`` (H
  heads of ``nope + rope``); ``[c_kv | k_r] = u W_kva``, ``[k_n | v] =
  RMSNorm(c_kv) W_kvb`` (H heads of ``nope + v``); rotary (YaRN's
  frequencies) on every head's ``rope`` channels of q and on the ONE ``k_r``
  all heads share; causal softmax over ``nope + rope`` channels at scale
  ``(nope + rope)^-0.5 m^2``, values ``v`` wide; ``o W_o``.
* ``F`` = feed-forward: the leading ``first_k_dense`` layers a gated
  ``(silu(u W_gate) * (u W_up)) W_down``; the others sigmoid routing over
  all experts (``_contrib_moe_route``), the held experts' gated
  feed-forwards and one shared expert of the same form.

The trunk starts from ``X = [e | ... | e]`` (``e`` the token's embedding) and
ends in ``h = sum_i X_i``, a final RMSNorm and the head.  The prediction
module takes ``g_i = [RMSNorm(h_i) | RMSNorm(e_(i+1))] W_eh``, spreads it
into ``n`` streams, runs one more expert layer, sums, norms and reads the
SAME head: position ``i`` predicts token ``i + 2``.  The model returns
``[logits, logits_mtp]`` (``logits`` alone with ``mtp=False``).

A layer's boundary is the stream tensor; each layer is recomputed in the
backward pass over it.  Training only: no cache of the latent rows ``[c_kv |
k_r]`` and no drafting with the prediction module is built.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import record_aux_update
from ..ops import registry as _reg
from ..ops.moe_ops import _activate
from .common import HeldExperts, checkpointed, vocab_logits

__all__ = ["MLAttention", "HyperConnection", "GatedMLP", "XingMoE",
           "XingLayer", "XingModel", "xing"]

#: the published ``rope_scaling`` group (YaRN)
ROPE_SCALING = {"type": "yarn", "factor": 64, "beta_fast": 32, "beta_slow": 1,
                "mscale": 1, "mscale_all_dim": 1,
                "original_max_position_embeddings": 4096}


def _dense(n, in_units, prefix):
    return nn.Dense(n, flatten=False, use_bias=False, in_units=in_units,
                    prefix=prefix)


class MLAttention(HybridBlock):
    """Multi-head latent attention (DeepSeek-V2's form): queries through a
    ``q_lora_rank`` latent, keys and values through a ``kv_lora_rank`` latent
    and one rotary key all heads share; scores over ``qk_nope_head_dim +
    qk_rope_head_dim`` channels, values ``v_head_dim`` wide."""

    def __init__(self, units, num_heads=32, q_lora_rank=768, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=10000.0, rope_scaling=None, eps=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._rank = num_heads, kv_lora_rank
        self._dn, self._dr, self._dv = (qk_nope_head_dim, qk_rope_head_dim,
                                        v_head_dim)
        self._rotary = {"theta": rope_theta}
        m = 1.0
        if rope_scaling is not None:
            if rope_scaling.get("type") != "yarn":
                raise ValueError(f"MLAttention: rope_scaling {rope_scaling}")
            factor = rope_scaling["factor"]
            self._rotary.update(
                yarn_factor=factor,
                yarn_beta_fast=rope_scaling["beta_fast"],
                yarn_beta_slow=rope_scaling["beta_slow"],
                yarn_original_max=rope_scaling[
                    "original_max_position_embeddings"])
            m = 0.1 * rope_scaling.get("mscale_all_dim", 0) \
                * math.log(factor) + 1.0
        self._scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5 * m * m
        h = num_heads
        with self.name_scope():
            self.q_a_proj = _dense(q_lora_rank, units, "q_a_proj_")
            self.q_a_norm = nn.RMSNorm(epsilon=eps, in_channels=q_lora_rank,
                                       prefix="q_a_norm_")
            self.q_b_proj = _dense(h * (qk_nope_head_dim + qk_rope_head_dim),
                                   q_lora_rank, "q_b_proj_")
            self.kv_a_proj = _dense(kv_lora_rank + qk_rope_head_dim, units,
                                    "kv_a_proj_")
            self.kv_a_norm = nn.RMSNorm(epsilon=eps, in_channels=kv_lora_rank,
                                        prefix="kv_a_norm_")
            self.kv_b_proj = _dense(h * (qk_nope_head_dim + v_head_dim),
                                    kv_lora_rank, "kv_b_proj_")
            self.o_proj = _dense(units, h * v_head_dim, "o_proj_")

    def hybrid_forward(self, F, u):
        h, dn, dr = self._h, self._dn, self._dr
        with jax.named_scope("mx_mla_front"):
            q = self.q_b_proj(self.q_a_norm(self.q_a_proj(u))).reshape(
                (0, 0, h, dn + dr))
            latent = self.kv_a_proj(u)
            c_kv = F.slice_axis(latent, axis=-1, begin=0, end=self._rank)
            k_r = F.slice_axis(latent, axis=-1, begin=self._rank,
                               end=None).reshape((0, 0, 1, dr))
            kv = self.kv_b_proj(self.kv_a_norm(c_kv)).reshape(
                (0, 0, h, dn + self._dv))
            q_r = F._contrib_rotary(
                F.slice_axis(q, axis=-1, begin=dn, end=None), **self._rotary)
            k_r = F._contrib_rotary(k_r, **self._rotary)
            q = F.concat(F.slice_axis(q, axis=-1, begin=0, end=dn), q_r,
                         dim=-1)
            k = F.concat(F.slice_axis(kv, axis=-1, begin=0, end=dn),
                         F.broadcast_axis(k_r, axis=2, size=h), dim=-1)
            v = F.slice_axis(kv, axis=-1, begin=dn, end=None)
            q, k, v = (t.transpose((0, 2, 1, 3)) for t in (q, k, v))
        with jax.named_scope("mx_mla_attention"):
            o = F._contrib_flash_attention(q, k, v, causal=True,
                                           sm_scale=self._scale)
        return self.o_proj(o.transpose((0, 2, 1, 3)).reshape((0, 0, -1)))


class HyperConnection(HybridBlock):
    """The mixing parameters of one sublayer over ``n`` streams of ``units``
    (``ops/hc_ops.py``): the RMSNorm's gain over all streams, ``phi`` (2 n +
    n^2, n units), the three scalars ``a`` and the offsets ``b``.
    ``coefficients(X)`` then ``pre(X, C)`` and ``post(X, y, C)``."""

    def __init__(self, units, n=4, sinkhorn_iters=20, hc_eps=1e-6,
                 clamp_min=-30.0, clamp_max=30.0, rms_eps=1e-6, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._n = n
        self._attrs = dict(n=n, iters=sinkhorn_iters, eps=hc_eps,
                           clamp_min=clamp_min, clamp_max=clamp_max,
                           rms_eps=rms_eps)
        k = 2 * n + n * n
        with self.name_scope():
            self.norm_gamma = self.params.get("norm_gamma",
                                              shape=(n * units,), init="ones")
            self.phi = self.params.get("phi_weight", shape=(k, n * units))
            self.a = self.params.get("a", shape=(3,), init="zeros")
            self.b = self.params.get("b", shape=(k,), init="zeros")

    def hybrid_forward(self, F, streams, norm_gamma, phi, a, b):
        return F._contrib_mhc_coefficients(streams, norm_gamma, phi, a, b,
                                           **self._attrs)

    def pre(self, F, streams, coeffs):
        return F._contrib_mhc_pre(streams, coeffs, n=self._n)

    def post(self, F, streams, y, coeffs):
        return F._contrib_mhc_post(streams, y, coeffs, n=self._n)


class GatedMLP(HybridBlock):
    """``(silu(u W_gate) * (u W_up)) W_down``, the gate and the product in
    f32 and rounded once (the routed experts' own ``_activate``)."""

    def __init__(self, units, width, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.gate_proj = _dense(width, units, "gate_proj_")
            self.up_proj = _dense(width, units, "up_proj_")
            self.down_proj = _dense(units, width, "down_proj_")

    def hybrid_forward(self, F, u):
        hidden = _reg.invoke_fn(lambda g, u: _activate((g, u), "swiglu"),
                                [self.gate_proj(u), self.up_proj(u)])
        return self.down_proj(hidden)


class XingMoE(HeldExperts):
    """Sigmoid top-k routing over ``n_routed_experts`` with a correction
    bias that takes no gradient, the held experts' gated feed-forwards
    through ``_contrib_moe_experts`` and one shared expert of the same form.
    Returns ``(out, load)``."""

    def __init__(self, units, n_routed_experts=64, experts_held=None, top_k=4,
                 expert_width=1024, shared_width=1024,
                 routed_scaling_factor=2.0, norm_topk_prob=True,
                 router_lr_mult=1.0, prefix=None, params=None):
        super().__init__(units, n_routed_experts, experts_held, top_k,
                         expert_width, "swiglu", prefix=prefix, params=params)
        self._scale, self._norm = routed_scaling_factor, norm_topk_prob
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(n_routed_experts, units),
                lr_mult=router_lr_mult)
            self.correction_bias = self.params.get(
                "e_score_correction_bias", shape=(n_routed_experts,),
                init="zeros", grad_req="null")
            self.shared = GatedMLP(units, shared_width, prefix="shared_")

    def hybrid_forward(self, F, u, gate_weight, up_weight, down_weight, load,
                       load_max, router_weight, correction_bias):
        flat = u.reshape((-1, u.shape[-1]))
        experts, weights = F._contrib_moe_route(
            flat, router_weight, correction_bias, top_k=self._k,
            scaling=self._scale, norm_topk_prob=self._norm)
        routed, load = self.routed(F, flat, experts, weights, up_weight,
                                   down_weight, load, gate_weight)
        with jax.named_scope("mx_moe_shared"):
            shared = self.shared(flat)
        return (routed + shared).reshape(u.shape), load


class XingLayer(HybridBlock):
    """``X -> X`` (a dense layer) or ``X -> (X, load)`` (an expert layer):
    the attention sublayer, then the feed-forward sublayer, each over its own
    RMSNorm and inside its own hyper-connection."""

    def __init__(self, attention, feed_forward, connection, units, eps=1e-6,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn_hc = connection(prefix="attn_hc_")
            self.attn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="attn_norm_")
            self.attn = attention(prefix="attn_")
            self.ffn_hc = connection(prefix="ffn_hc_")
            self.ffn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="ffn_norm_")
            self.ffn = feed_forward(prefix="ffn_")

    def hybrid_forward(self, F, x):
        c = self.attn_hc(x)
        y = self.attn(self.attn_norm(self.attn_hc.pre(F, x, c)))
        x = self.attn_hc.post(F, x, y, c)
        c = self.ffn_hc(x)
        y = self.ffn(self.ffn_norm(self.ffn_hc.pre(F, x, c)))
        if isinstance(y, (list, tuple)):
            return [self.ffn_hc.post(F, x, y[0], c), y[1]]
        return self.ffn_hc.post(F, x, y, c)


def _spread(e, n):
    return _reg.invoke_fn(lambda t: jnp.concatenate([t] * n, -1), [e])


def _gather(x, n):
    """``sum_i X_i`` in f32, rounded once."""
    def run(t):
        d = t.shape[-1] // n
        return sum(t[..., i * d:(i + 1) * d].astype(jnp.float32)
                   for i in range(n)).astype(t.dtype)

    return _reg.invoke_fn(run, [x])


def _next_token_nll(logits, tokens, ahead):
    """Mean cross-entropy of position ``i``'s logits against token ``i +
    ahead``, a (1,) f32 that takes no gradient."""
    def run(lg, tok):
        lg = jax.lax.stop_gradient(lg[:, :-ahead].astype(jnp.float32))
        lab = tok[:, ahead:].astype(jnp.int32)
        picked = jnp.take_along_axis(lg, lab[..., None], axis=-1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - picked).reshape(1)

    return _reg.invoke_fn(run, [logits, tokens])


class XingModel(HybridBlock):
    """tokens (B, L) int32 -> ``[logits, logits_mtp]``, each (B, L,
    vocab_size) f32: ``num_layers`` layers (the first ``first_k_dense`` with
    a dense feed-forward), a final RMSNorm and the head; with ``mtp`` the
    prediction module and the same head again."""

    def __init__(self, vocab_size=131072, hidden_size=3584, num_layers=40,
                 first_k_dense=2, num_attention_heads=32, q_lora_rank=768,
                 kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
                 v_head_dim=128, rope_theta=10000.0, rope_scaling=ROPE_SCALING,
                 intermediate_size=9216, n_routed_experts=64,
                 experts_held=None, num_experts_per_tok=4,
                 moe_intermediate_size=1024, n_shared_experts=1,
                 routed_scaling_factor=2.0, norm_topk_prob=True,
                 router_lr_mult=1.0, hc_mult=4, hc_sinkhorn_iters=20,
                 hc_eps=1e-6, mhc_h_res_clamp_min=-30.0,
                 mhc_h_res_clamp_max=30.0, mtp=True, rms_norm_eps=1e-6,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        d, eps, self._n = hidden_size, rms_norm_eps, hc_mult

        def attention(prefix):
            return MLAttention(d, num_attention_heads, q_lora_rank,
                               kv_lora_rank, qk_nope_head_dim,
                               qk_rope_head_dim, v_head_dim, rope_theta,
                               rope_scaling, eps, prefix=prefix)

        def connection(prefix):
            return HyperConnection(d, hc_mult, hc_sinkhorn_iters, hc_eps,
                                   mhc_h_res_clamp_min, mhc_h_res_clamp_max,
                                   eps, prefix=prefix)

        def dense(prefix):
            return GatedMLP(d, intermediate_size, prefix=prefix)

        def experts(prefix):
            return XingMoE(d, n_routed_experts,
                           tuple(experts_held) if experts_held else None,
                           num_experts_per_tok, moe_intermediate_size,
                           n_shared_experts * moe_intermediate_size,
                           routed_scaling_factor, norm_topk_prob,
                           router_lr_mult, prefix=prefix)

        def layer(feed_forward, prefix):
            return XingLayer(attention, feed_forward, connection, d, eps,
                             prefix=prefix)

        with self.name_scope():
            self.embed_weight = self.params.get("embed_weight",
                                                shape=(vocab_size, d))
            self.layers = []
            for i in range(num_layers):
                block = layer(dense if i < first_k_dense else experts,
                              f"layer{i}_")
                self.register_child(block, f"layer{i}")
                self.layers.append(block)
            self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=d,
                                     prefix="norm_f_")
            self.head_weight = self.params.get("head_weight",
                                               shape=(vocab_size, d))
            self.mtp = None
            if mtp:
                self.mtp_hnorm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                            prefix="mtp_hnorm_")
                self.mtp_enorm = nn.RMSNorm(epsilon=eps, in_channels=d,
                                            prefix="mtp_enorm_")
                self.mtp_eh_proj = _dense(d, 2 * d, "mtp_eh_proj_")
                self.mtp = layer(experts, "mtp_layer_")
                self.mtp_norm_f = nn.RMSNorm(epsilon=eps, in_channels=d,
                                             prefix="mtp_norm_f_")
                # the second loss term's cross-entropy at the last step:
                # aux state, read at drain like the experts' load
                self.mtp_loss = self.params.get(
                    "mtp_loss", shape=(1,), init="zeros", grad_req="null")
                self.mtp_loss.telemetry = "mtp_loss"

    def _run(self, layer, x):
        out = checkpointed(layer, x)
        if isinstance(out, (list, tuple)):
            x, load = out
            layer.ffn.record_load(load)
            return x
        return out

    def hybrid_forward(self, F, tokens, embed_weight, head_weight,
                       mtp_loss=None):
        with jax.named_scope("mx_embed"):
            e = F.Embedding(tokens, embed_weight,
                            input_dim=embed_weight.shape[0],
                            output_dim=embed_weight.shape[1])
            x = _spread(e, self._n)
        for layer in self.layers:
            x = self._run(layer, x)
        h = _gather(x, self._n)
        logits = vocab_logits(self.norm_f(h), head_weight)
        if self.mtp is None:
            return logits
        with jax.named_scope("mx_mtp"):
            # the token after: moved one position earlier, the last repeated
            e_next = F.concat(F.slice_axis(e, axis=1, begin=1, end=None),
                              F.slice_axis(e, axis=1, begin=-1, end=None),
                              dim=1)
            g = self.mtp_eh_proj(F.concat(self.mtp_hnorm(h),
                                          self.mtp_enorm(e_next), dim=-1))
        x = self._run(self.mtp, _spread(g, self._n))
        logits_mtp = vocab_logits(self.mtp_norm_f(_gather(x, self._n)),
                                  head_weight)
        record_aux_update(self.mtp_loss,
                          _next_token_nll(logits_mtp, tokens, 2))
        return [logits, logits_mtp]


def xing(**kwargs) -> XingModel:
    """Xing4.0-29B-A4B at its published sizes (the constructor's defaults);
    pass ``num_layers``, ``first_k_dense``, ``vocab_size`` and
    ``experts_held`` of the stage and share a chip holds."""
    return XingModel(**kwargs)
