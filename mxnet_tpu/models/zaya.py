"""The ``zaya`` family (ZAYA1): every layer an attention sublayer in a
compressed latent with convolutional mixing (CCA) and a top-1 expert sublayer
whose router is a small MLP over a representation that is carried from layer
to layer; residuals are scaled; the vocabulary head is the embedding.

With ``u = RMSNorm(x)`` before each sublayer and ``res(x, f) = (a * x + c) +
f`` (``a``, ``c`` learned vectors a sublayer), a layer is (docs/ZAYA.md
writes the equations out and lists what the public sources leave open):

* attention: ``q0 = u W_q``, ``k0 = u W_k`` (H and Hkv heads of D, the
  latent), ``v = [u_t W_v1 | u_(t-1) W_v2]`` (the second half of the
  key-value heads reads the token before); ``q1``, ``k1``: a depthwise causal
  convolution then one that mixes a head's channels, over q0 and k0; ``q2 =
  q1 + (q0 + rep(k0)) / 2``, ``k2 = k1 + (grp(q0) + k0) / 2``; each head
  brought to length ``sqrt(D)``, k times a learned temperature a head; rotary
  position on the first ``partial_rotary_factor`` of each head; causal
  grouped-query attention; ``x <- res(x, o W_o)``.
* experts: ``r_l = u W_in + g_l * r_(l-1)`` (``r`` goes on to the next layer
  beside ``x``); ``s = MLP(RMSNorm(r_l))``; ``p = softmax(s)``; the expert is
  ``argmax(p + b)``; ``x <- res(x, p_e swiglu_e(u))``.

A layer's boundary is the pair ``(x, r)``; each layer is recomputed in the
backward pass over that pair.  The expert sublayer is TOLD which experts it
holds, as ``nemotron_h.MoELayer`` is.  Training only: no cache of the latent
keys and values or of the convolutions' one-token state is built.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops import registry as _reg
from .common import HeldExperts, checkpointed, vocab_logits

__all__ = ["CCAttention", "ZayaRouter", "ZayaExperts", "ZayaLayer",
           "ZayaModel", "zaya"]


def _dense(n, in_units, prefix, use_bias=False):
    return nn.Dense(n, flatten=False, use_bias=use_bias, in_units=in_units,
                    prefix=prefix)


def _mix_and_norm(q0, k0, q1, k1, tau, heads, kv_heads, d):
    """``q2 = q1 + (q0 + rep(k0)) / 2``, ``k2 = k1 + (grp(q0) + k0) / 2``
    and each head's L2 normalisation to ``sqrt(d)`` (k times its head's
    ``tau``), in f32; (B, L, heads * d) and (B, L, kv_heads * d) in, (B, L,
    heads, d) and (B, L, kv_heads, d) out in the inputs' type."""
    f32 = jnp.float32
    lead, group = q0.shape[:2], heads // kv_heads
    q0g = q0.astype(f32).reshape(lead + (kv_heads, group, d))
    k0h = k0.astype(f32).reshape(lead + (kv_heads, d))
    q2 = q1.astype(f32).reshape(q0g.shape) + (q0g + k0h[..., None, :]) / 2
    k2 = k1.astype(f32).reshape(k0h.shape) + (q0g.mean(-2) + k0h) / 2

    def unit(t):
        return t * (math.sqrt(d) / (
            jnp.sqrt(jnp.sum(jnp.square(t), -1, keepdims=True)) + 1e-6))

    q3 = unit(q2).reshape(lead + (heads, d))
    k3 = unit(k2) * tau.astype(f32)[:, None]
    return q3.astype(q0.dtype), k3.astype(k0.dtype)


class CCAttention(HybridBlock):
    """Compressed convolutional attention: ``num_heads`` query heads over
    ``num_kv_heads`` key-value heads of ``head_dim``, all in a latent
    ``num_heads * head_dim`` wide (the module docstring has the equations)."""

    def __init__(self, units, num_heads=8, num_kv_heads=2, head_dim=128,
                 cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
                 rope_theta=5e6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        if num_kv_heads % 2 or num_heads % num_kv_heads:
            raise ValueError(f"CCAttention: {num_heads} query heads over "
                             f"{num_kv_heads} key-value heads (half of them "
                             "read the token before)")
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._rot, self._theta = partial_rotary_factor, rope_theta
        qd, kd = num_heads * head_dim, num_kv_heads * head_dim
        with self.name_scope():
            self.q_proj = _dense(qd, units, "q_proj_")
            self.k_proj = _dense(kd, units, "k_proj_")
            self.v1_proj = _dense(kd // 2, units, "v1_proj_")
            self.v2_proj = _dense(kd // 2, units, "v2_proj_")
            for name, width, heads in (("q", qd, num_heads),
                                       ("k", kd, num_kv_heads)):
                for key, shape in (
                        ("conv_a_weight", (width, cca_time0)),
                        ("conv_a_bias", (width,)),
                        ("conv_b_weight",
                         (heads, cca_time1, head_dim, head_dim)),
                        ("conv_b_bias", (width,))):
                    setattr(self, f"{name}_{key}", self.params.get(
                        f"{name}_{key}", shape=shape,
                        init="zeros" if key.endswith("bias") else None))
            self.tau = self.params.get("tau", shape=(num_kv_heads,),
                                       init="ones")
            self.o_proj = _dense(units, qd, "o_proj_")

    def hybrid_forward(self, F, u, q_conv_a_weight, q_conv_a_bias,
                       q_conv_b_weight, q_conv_b_bias, k_conv_a_weight,
                       k_conv_a_bias, k_conv_b_weight, k_conv_b_bias, tau):
        q0, k0 = self.q_proj(u), self.k_proj(u)
        v1, v2 = self.v1_proj(u), self.v2_proj(u)
        with jax.named_scope("mx_cca_mix"):
            q1 = F._contrib_causal_conv1d_heads(
                F._contrib_causal_conv1d(q0, q_conv_a_weight, q_conv_a_bias),
                q_conv_b_weight, q_conv_b_bias)
            k1 = F._contrib_causal_conv1d_heads(
                F._contrib_causal_conv1d(k0, k_conv_a_weight, k_conv_a_bias),
                k_conv_b_weight, k_conv_b_bias)
            q3, k3 = _reg.invoke_fn(
                lambda *a: _mix_and_norm(*a, self._h, self._hkv, self._d),
                [q0, k0, q1, k1, tau])
            q = F._contrib_rotary(q3, theta=self._theta, fraction=self._rot)
            k = F._contrib_rotary(k3, theta=self._theta, fraction=self._rot)
            # the token before: shifted one position, nought at position 0
            v2 = F.concat(F.zeros_like(F.slice_axis(v2, axis=1, begin=0,
                                                    end=1)),
                          F.slice_axis(v2, axis=1, begin=0, end=-1), dim=1)
            v = F.concat(v1, v2, dim=-1).reshape((0, 0, self._hkv, self._d))
            q, k, v = (t.transpose((0, 2, 1, 3)) for t in (q, k, v))
        with jax.named_scope("mx_gqa_attention"):
            o = F._contrib_flash_attention(q, k, v, causal=True)
        return self.o_proj(o.transpose((0, 2, 1, 3)).reshape((0, 0, -1)))


class ZayaRouter(HybridBlock):
    """``r = u W_in + g * r_before``; ``s = W_3 gelu(W_2 gelu(W_1
    RMSNorm(r))))`` with biases, ``gelu`` in its tanh form.  Returns ``(r,
    s)``: the representation the next layer's router adds to, and the
    logits over all ``n_experts``."""

    def __init__(self, units, n_experts=16, router_hidden_size=256, eps=1e-5,
                 lr_mult=1.0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        w = router_hidden_size
        with self.name_scope():
            self.in_proj = _dense(w, units, "in_")
            self.depth_gain = self.params.get("depth_gain", shape=(w,),
                                              init="zeros")
            self.norm = nn.RMSNorm(epsilon=eps, in_channels=w, prefix="norm_")
            self.fc1 = _dense(w, w, "fc1_", use_bias=True)
            self.fc2 = _dense(w, w, "fc2_", use_bias=True)
            self.fc3 = _dense(n_experts, w, "fc3_", use_bias=True)
        for p in self.collect_params().values():
            p.lr_mult = lr_mult

    def hybrid_forward(self, F, u, r_before, depth_gain):
        with jax.named_scope("mx_zaya_router"):
            r = self.in_proj(u) + r_before * depth_gain
            h = F.LeakyReLU(self.fc1(self.norm(r)), act_type="gelu_tanh")
            h = F.LeakyReLU(self.fc2(h), act_type="gelu_tanh")
            return r, self.fc3(h)


class ZayaExperts(HeldExperts):
    """The router, top-1 choice by softmax with a balancing bias that takes
    no gradient, and the held experts' gated feed-forwards ``(silu(u W_gate)
    * (u W_up)) W_down`` times the chosen expert's probability.  Takes ``(u,
    r_before)``, returns ``(out, r, load)``."""

    def __init__(self, units, n_experts=16, experts_held=None, top_k=1,
                 expert_width=2048, router_hidden_size=256, eps=1e-5,
                 router_lr_mult=1.0, prefix=None, params=None):
        super().__init__(units, n_experts, experts_held, top_k, expert_width,
                         "swiglu", prefix=prefix, params=params)
        with self.name_scope():
            self.router = ZayaRouter(units, n_experts, router_hidden_size,
                                     eps, router_lr_mult, prefix="router_")
            self.balance_bias = self.params.get(
                "router_balance_bias", shape=(n_experts,), init="zeros",
                grad_req="null")

    def hybrid_forward(self, F, u, r_before, gate_weight, up_weight,
                       down_weight, load, load_max, balance_bias):
        r, logits = self.router(u, r_before)
        flat = u.reshape((-1, u.shape[-1]))
        with jax.named_scope("mx_zaya_router"):
            experts, weights = F._contrib_moe_route_softmax(
                logits.reshape((-1, self._e)), balance_bias, top_k=self._k)
        out, load = self.routed(F, flat, experts, weights, up_weight,
                                down_weight, load, gate_weight)
        return out.reshape(u.shape), r, load


class _Residual(HybridBlock):
    """``(a * x + c) + f`` with learned vectors ``a`` (ones) and ``c``
    (zeros), taken in f32 and rounded once."""

    def __init__(self, units, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.a = self.params.get("a", shape=(units,), init="ones")
            self.c = self.params.get("c", shape=(units,), init="zeros")

    def hybrid_forward(self, F, x, f, a, c):
        f32 = jnp.float32
        return _reg.invoke_fn(
            lambda x, f, a, c: ((a.astype(f32) * x.astype(f32)
                                 + c.astype(f32)) + f.astype(f32)
                                ).astype(x.dtype), [x, f, a, c])


class ZayaLayer(HybridBlock):
    """``(x, r) -> (x, r, load)``: the attention sublayer, then the expert
    sublayer, each over its own RMSNorm and into its own scaled residual."""

    def __init__(self, attention, experts, units, eps=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="attn_norm_")
            self.attn = attention(prefix="attn_")
            self.attn_res = _Residual(units, prefix="attn_res_")
            self.moe_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="moe_norm_")
            self.moe = experts(prefix="moe_")
            self.moe_res = _Residual(units, prefix="moe_res_")

    def hybrid_forward(self, F, x, r):
        x = self.attn_res(x, self.attn(self.attn_norm(x)))
        y, r, load = self.moe(self.moe_norm(x), r)
        return [self.moe_res(x, y), r, load]


class ZayaModel(HybridBlock):
    """tokens (B, L) int32 -> logits (B, L, vocab_size) f32 through
    ``num_layers`` layers, a final RMSNorm and the embedding as the head."""

    def __init__(self, vocab_size=262272, hidden_size=2048, num_layers=40,
                 num_attention_heads=8, num_key_value_heads=2, head_dim=128,
                 cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
                 rope_theta=5e6, num_experts=16, experts_held=None,
                 num_experts_per_tok=1, moe_intermediate_size=2048,
                 router_hidden_size=256, router_lr_mult=1.0,
                 rms_norm_eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        d, eps = hidden_size, rms_norm_eps
        self._router_width = router_hidden_size

        def attention(prefix):
            return CCAttention(d, num_attention_heads, num_key_value_heads,
                               head_dim, cca_time0, cca_time1,
                               partial_rotary_factor, rope_theta,
                               prefix=prefix)

        def experts(prefix):
            return ZayaExperts(d, num_experts,
                               tuple(experts_held) if experts_held else None,
                               num_experts_per_tok, moe_intermediate_size,
                               router_hidden_size, eps, router_lr_mult,
                               prefix=prefix)

        with self.name_scope():
            self.embed_weight = self.params.get("embed_weight",
                                                shape=(vocab_size, d))
            self.layers = []
            for i in range(num_layers):
                layer = ZayaLayer(attention, experts, d, eps,
                                  prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=d,
                                     prefix="norm_f_")

    def hybrid_forward(self, F, tokens, embed_weight):
        with jax.named_scope("mx_embed"):
            x = F.Embedding(tokens, embed_weight,
                            input_dim=embed_weight.shape[0],
                            output_dim=embed_weight.shape[1])
        # depth averaging starts from nought before the first held layer
        r = F.zeros_like(F.slice_axis(x, axis=-1, begin=0,
                                      end=self._router_width))
        for layer in self.layers:
            x, r, load = checkpointed(layer, x, r)
            layer.moe.record_load(load)
        # one parameter, read twice: its gradient is the sum of both uses
        return vocab_logits(self.norm_f(x), embed_weight)


def zaya(**kwargs) -> ZayaModel:
    """ZAYA1-8B at its published sizes (the constructor's defaults); pass
    ``num_layers``, ``vocab_size`` and ``experts_held`` of the stage and
    share a chip holds."""
    return ZayaModel(**kwargs)
