"""The language model of Keye-VL-2.0-30B-A3B (``KeyeVL2``) under next-token
training: a Qwen3-MoE-shaped decoder whose attention runs over a LEARNED
SPARSE selection of keys (DeepSeek-V3.2's lightning indexer and its top-k),
with the indexer trained by its own KL loss.

A layer, with input ``h`` (B, L, d) and ``u = RMSNorm(h)`` (docs/KEYE.md
writes the equations out and lists what the public config leaves open):

* the indexer (``DSAIndexer``) reads ``stop_gradient(u)``: J query heads
  ``qi = rot(u W_qI)`` of Dk, one key head ``ki = rot(LayerNorm(u W_kI))``,
  the heads' weights ``w = u W_w / sqrt(J Dk)``; ``rot`` is rotate-half
  rotary over the first half of a head's channels;
* each query's ``topk`` highest-scoring earlier keys (``ops/dsa_ops.py``
  ``select``: every earlier key while there are fewer);
* grouped-query attention with q/k norms and rotary over that selection
  (``GQAttention(sparse=True)``, the flash kernels' selection mode), into the
  residual; the experts' sublayer as SDAR's (softmax top-k, renormalised,
  experts held);
* ``KL(p_t || softmax_{S_t} I_t)`` a query, ``p_t`` the heads' mean
  attention over the selection (a target), averaged over the row.

The model returns ``[logits, the layers' KL summed]`` (B,) a row; the loss
adds the two (``benchmark/programs/next_token_dsa.py``).  The indexer's
weights get gradient from the KL alone, the trunk from the cross-entropy
alone.  Each layer is recomputed in the backward pass; the flash calls'
results, the KL's statistics and the selection's words are kept, so a
layer makes its selection once a step.  Aux state read at drain: ``dsa_kl``
(a layer's KL in the last step) and ``dsa_tile_hit`` (the share of visited
tiles that hold a selected pair).  Training only: no vision tower and no
serving path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..gluon.parameter import record_aux_update
from ..ops import dsa_ops
from ..ops import registry as _reg
from .common import checkpointed, vocab_logits
from .nemotron_h import GQAttention, MoELayer
from .sdar import CHUNK_COVER

__all__ = ["DSAIndexer", "KeyeLayer", "KeyeModel", "keye"]


class DSAIndexer(HybridBlock):
    """``u -> [qi (B, L, J, Dk), ki (B, L, Dk), w (B, L, J) f32]`` of the
    lightning indexer over ``stop_gradient(u)``; holds the aux leaves
    ``kl`` and ``tile_hit`` its layer writes."""

    def __init__(self, units, heads=16, head_dim=64, rope_theta=1e7,
                 rope_fraction=0.5, eps=1e-6, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._j, self._dk = heads, head_dim
        self._theta, self._fraction = rope_theta, rope_fraction
        self._scale = dsa_ops.indexer_scale(heads, head_dim)

        def dense(n, prefix):
            return nn.Dense(n, flatten=False, use_bias=False,
                            in_units=units, prefix=prefix)

        with self.name_scope():
            self.wq = dense(heads * head_dim, "wq_")
            self.wk = dense(head_dim, "wk_")
            self.k_norm = nn.LayerNorm(epsilon=eps, in_channels=head_dim,
                                       prefix="k_norm_")
            self.weights_proj = dense(heads, "weights_proj_")
            self.kl = self.params.get("kl", shape=(1,), init="zeros",
                                      grad_req="null")
            self.tile_hit = self.params.get("tile_hit", shape=(1,),
                                            init="zeros", grad_req="null")
        self.kl.telemetry = "dsa_kl"
        self.tile_hit.telemetry = "dsa_tile_hit"

    def hybrid_forward(self, F, u, kl, tile_hit):
        with jax.named_scope("mx_dsa_indexer"):
            u = F.stop_gradient(u)
            qi = self.wq(u).reshape((0, 0, self._j, self._dk))
            qi = F._contrib_rotary(qi, theta=self._theta,
                                   fraction=self._fraction)
            ki = F._contrib_rotary(self.k_norm(self.wk(u)),
                                   theta=self._theta, fraction=self._fraction)
            w = _reg.invoke_fn(
                lambda x: x.astype(jnp.float32) * self._scale,
                [self.weights_proj(u)])
        return [qi, ki, w]

    def record(self, kl, tile_hit):
        """The aux writes of this step's readings (outside any recomputed
        region)."""
        for leaf, value in ((self.kl, kl), (self.tile_hit, tile_hit)):
            record_aux_update(leaf, value.astype(
                leaf.data(value.context).dtype))


class KeyeLayer(HybridBlock):
    """``h -> [h, load, KL (B,), tile hit (1,)]``: the indexer and its
    selection, attention over it, then the expert sublayer, each sublayer
    over its own RMSNorm and into the residual."""

    def __init__(self, attention, indexer, experts, units, topk, eps=1e-6,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._topk = int(topk)
        with self.name_scope():
            self.attn_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                        prefix="attn_norm_")
            self.attn = attention(prefix="attn_")
            self.indexer = indexer(prefix="indexer_")
            self.moe_norm = nn.RMSNorm(epsilon=eps, in_channels=units,
                                       prefix="moe_norm_")
            self.moe = experts(prefix="moe_")

    def hybrid_forward(self, F, h):
        u = self.attn_norm(h)
        qi, ki, w = self.indexer(u)
        with jax.named_scope("mx_dsa_indexer"):
            bits, bits_t = _reg.invoke_fn(
                lambda a, b, c: dsa_ops.select(a, b, c, self._topk),
                [qi, ki, w])
        y, q, k, lse = self.attn(u, bits, bits_t)
        with jax.named_scope("mx_dsa_indexer"):
            kl = _reg.invoke_fn(
                lambda *a: jnp.mean(dsa_ops.indexer_kl(*a), axis=-1),
                [qi, ki, w, q, k, lse, bits_t])
            hit = _reg.invoke_fn(
                lambda b: dsa_ops.tile_hit(b, h.shape[1]).reshape((1,)),
                [bits])
        h = h + y
        out, load = self.moe(self.moe_norm(h))
        return [h + out, load, kl, hit]


class KeyeModel(HybridBlock):
    """tokens (B, L) int32 -> ``[logits (B, L, vocab_size) f32, the layers'
    indexer KL summed (B,) f32]`` through ``num_layers`` layers."""

    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 num_experts=128, experts_held=None, num_experts_per_tok=8,
                 moe_intermediate_size=768, norm_topk_prob=True,
                 router_lr_mult=1.0, rope_theta=1e7, rms_norm_eps=1e-6,
                 indexer_num_heads=16, indexer_head_dim=64, topk=2048,
                 indexer_rope_fraction=0.5, chunk_cover=CHUNK_COVER,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        d, eps = hidden_size, rms_norm_eps

        def attention(prefix):
            return GQAttention(d, num_attention_heads, num_key_value_heads,
                               head_dim, qk_norm=True, eps=eps,
                               rope_theta=rope_theta, sparse=True,
                               prefix=prefix)

        def indexer(prefix):
            return DSAIndexer(d, indexer_num_heads, indexer_head_dim,
                              rope_theta, indexer_rope_fraction, eps,
                              prefix=prefix)

        def experts(prefix):
            return MoELayer(d, num_experts,
                            tuple(experts_held) if experts_held else None,
                            num_experts_per_tok, moe_intermediate_size, 0,
                            1.0, norm_topk_prob, router_lr_mult,
                            scoring="softmax", activation="swiglu",
                            chunk_cover=chunk_cover, gather_sum=True,
                            prefix=prefix)

        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, d, prefix="embed_")
            self.layers = []
            for i in range(num_layers):
                layer = KeyeLayer(attention, indexer, experts, d, topk, eps,
                                  prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm_f = nn.RMSNorm(epsilon=eps, in_channels=d,
                                     prefix="norm_f_")
            self.head_weight = self.params.get("head_weight",
                                               shape=(vocab_size, d))

    def hybrid_forward(self, F, tokens, head_weight):
        h = self.embed(tokens)
        kl_sum = None
        for layer in self.layers:
            h, load, kl, hit = checkpointed(layer, h)
            layer.moe.record_load(load)
            layer.indexer.record(kl.mean().reshape((1,)), hit)
            kl_sum = kl if kl_sum is None else kl_sum + kl
        return [vocab_logits(self.norm_f(h), head_weight), kl_sum]


def keye(**kwargs) -> KeyeModel:
    """Keye-VL-2.0-30B-A3B's language model at its published sizes (the
    constructor's defaults); pass ``num_layers``, ``vocab_size`` and
    ``experts_held`` of the stage and share a chip holds."""
    return KeyeModel(**kwargs)
