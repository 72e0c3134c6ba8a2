"""One tower of the ``nemotron_h`` family: a pattern-driven stack of Mamba-2
(``M``), sparse-expert (``E``) and grouped-query attention (``*``) layers.

Every layer is pre-norm residual, ``x <- x + mixer(RMSNorm(x))``; after the
last, a final RMSNorm and an untied vocabulary head whose logits are f32.
The layer equations are those of the family's published ``config.json`` and
modelling code (docs/NEMOTRON_H.md writes them out).  No rotary position is
applied (the family applies none).  What the name "TwoTower" adds (a second,
denoising tower, adaLN, cross-tower conditioning, a block-diffusion loss) is
in no public config and is NOT built here; nor is any serving of the state
layers (no cache of convolution or scan state).

An expert layer is TOLD which experts it holds: ``experts_held = (first,
count)`` of ``n_routed_experts``.  It routes over all of them, normalises
each token's weights over all its choices, and adds only the held experts'
terms (plus the shared expert, which every chip computes alike); what the
absent experts would add is left out, and that partial result goes on to
the next layer.  With ``experts_held=None`` it holds them all.  A chip that
holds a share computes only its share of the router's gradient (the sum over
the chips that share the layer is the exchange's to make): applied alone it
pulls every token towards the held experts, so a job that runs one share
without its exchange sets ``router_lr_mult=0`` (the router's ``lr_mult``: its
gradient and moments are still computed, its values stay).  The pairs
that landed on each held expert in the last step, relative to an even
spread over all experts, and their running maximum are aux state
(``load``, ``load_max``), written the way BatchNorm writes its running
statistics: no step syncs to read them, ``DataParallelStep.drain`` hands
them to ``telemetry.record_moe_load``.

Each layer is recomputed in the backward pass (``common.checkpointed``), so a
step keeps a layer's input and what ``ops/recompute.py`` names dear to make
again (the wide products, flash attention's results), not one activation per
operator; the scan is made again (docs/NEMOTRON_H.md, Memory).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import initializer as init
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..ops import registry as _reg
from .common import HeldExperts, checkpointed, vocab_logits

#: the published ``hybrid_override_pattern``: 23 M, 23 E, 6 *
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

__all__ = ["Mamba2Mixer", "MoELayer", "GQAttention", "NemotronHLayer",
           "NemotronHModel", "nemotron_h"]


class Mamba2Mixer(HybridBlock):
    """``[z | xBC | dt] = u W_in``; ``xBC = silu(conv1d(xBC) + b)``;
    the state-space scan over ``x, B, C, dt``; ``y = RMSNorm_grouped(y *
    silu(z))``; ``out = y W_out``."""

    def __init__(self, units, num_heads=64, head_dim=64, state_size=128,
                 n_groups=8, conv_kernel=4, chunk_size=128, eps=1e-5,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._p, self._n, self._g = (num_heads, head_dim,
                                              state_size, n_groups)
        self._inner = num_heads * head_dim
        self._conv_dim = self._inner + 2 * n_groups * state_size
        self._chunk, self._eps = chunk_size, eps
        dt = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), num_heads))
        with self.name_scope():
            self.in_proj = nn.Dense(self._inner + self._conv_dim + num_heads,
                                    flatten=False, use_bias=False,
                                    in_units=units, prefix="in_proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(self._conv_dim, conv_kernel))
            self.conv_bias = self.params.get(
                "conv_bias", shape=(self._conv_dim,), init="zeros")
            self.a_log = self.params.get(
                "A_log", shape=(num_heads,),
                init=init.Constant(np.log(np.arange(1.0, num_heads + 1.0))))
            self.d = self.params.get("D", shape=(num_heads,), init="ones")
            self.dt_bias = self.params.get(
                "dt_bias", shape=(num_heads,),     # softplus^-1 of dt
                init=init.Constant(dt + np.log(-np.expm1(-dt))))
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(self._inner,), init="ones")
            self.out_proj = nn.Dense(units, flatten=False, use_bias=False,
                                     in_units=self._inner, prefix="out_proj_")

    def hybrid_forward(self, F, u, conv_weight, conv_bias, a_log, d, dt_bias,
                       norm_gamma):
        inner, gn = self._inner, self._g * self._n
        zxbcdt = self.in_proj(u)
        z = F.slice_axis(zxbcdt, axis=-1, begin=0, end=inner)
        xbc = F.slice_axis(zxbcdt, axis=-1, begin=inner,
                           end=inner + self._conv_dim)
        dt = F.slice_axis(zxbcdt, axis=-1, begin=inner + self._conv_dim,
                          end=None)
        xbc = F._contrib_causal_conv1d(xbc, conv_weight, conv_bias,
                                       activation="silu")
        x = F.slice_axis(xbc, axis=-1, begin=0, end=inner).reshape(
            (0, 0, self._h, self._p))
        b = F.slice_axis(xbc, axis=-1, begin=inner, end=inner + gn).reshape(
            (0, 0, self._g, self._n))
        c = F.slice_axis(xbc, axis=-1, begin=inner + gn, end=None).reshape(
            (0, 0, self._g, self._n))
        y = F._contrib_ssd_scan(x, dt, a_log, b, c, d, dt_bias,
                                chunk=self._chunk)
        y = F._contrib_gated_rms_norm(y.reshape((0, 0, -1)), z, norm_gamma,
                                      group_size=inner // self._g,
                                      eps=self._eps)
        return self.out_proj(y)


class MoELayer(HeldExperts):
    """Top-k routing over ``n_routed_experts``, the held experts'
    feed-forwards as one grouped product, and a shared expert where
    ``shared_width``.  ``scoring="sigmoid"``: the family's sigmoid scores
    with a correction bias and ``routed_scaling_factor``, ``relu2`` experts
    by default; ``"softmax"``: a softmax over the linear router's logits in
    f32, no bias and no scaling (``_contrib_moe_route_softmax``).  Returns
    ``(out, load)``: see the module docstring."""

    def __init__(self, units, n_routed_experts=128, experts_held=None,
                 top_k=6, expert_width=1856, shared_width=3712,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 router_lr_mult=1.0, scoring="sigmoid", activation="relu2",
                 chunk_cover=2, gather_sum=False, prefix=None, params=None):
        super().__init__(units, n_routed_experts, experts_held, top_k,
                         expert_width, activation, chunk_cover, gather_sum,
                         prefix=prefix, params=params)
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"MoELayer: scoring {scoring!r}")
        self._scale, self._norm = routed_scaling_factor, norm_topk_prob
        self._scoring, self._shared = scoring, bool(shared_width)
        with self.name_scope():
            self.router_weight = self.params.get(
                "router_weight", shape=(n_routed_experts, units),
                lr_mult=router_lr_mult)
            if scoring == "sigmoid":
                self.correction_bias = self.params.get(
                    "e_score_correction_bias", shape=(n_routed_experts,),
                    init="zeros", grad_req="null")
            if shared_width:
                self.shared_up = nn.Dense(shared_width, flatten=False,
                                          use_bias=False, in_units=units,
                                          prefix="shared_up_")
                self.shared_down = nn.Dense(units, flatten=False,
                                            use_bias=False,
                                            in_units=shared_width,
                                            prefix="shared_down_")

    def _route(self, F, flat, router_weight, correction_bias):
        if self._scoring == "sigmoid":
            return F._contrib_moe_route(
                flat, router_weight, correction_bias, top_k=self._k,
                scaling=self._scale, norm_topk_prob=self._norm)
        with jax.named_scope("mx_moe_route"):
            logits = _reg.invoke_fn(
                lambda a, w: jnp.einsum("td,ed->te", a, w,
                                        preferred_element_type=jnp.float32),
                [flat, router_weight])
        return F._contrib_moe_route_softmax(logits, top_k=self._k,
                                            norm_topk_prob=self._norm)

    def hybrid_forward(self, F, u, router_weight, up_weight, down_weight,
                       load, load_max, correction_bias=None,
                       gate_weight=None):
        flat = u.reshape((-1, u.shape[-1]))
        experts, weights = self._route(F, flat, router_weight,
                                       correction_bias)
        out, load = self.routed(F, flat, experts, weights, up_weight,
                                down_weight, load, gate_weight)
        if self._shared:
            with jax.named_scope("mx_moe_shared"):
                out = out + self.shared_down(
                    F._contrib_relu2(self.shared_up(flat)))
        return out.reshape(u.shape), load


class GQAttention(HybridBlock):
    """``softmax(q k^T / sqrt(D)) v`` with ``num_heads`` query heads over
    ``num_kv_heads`` key-value heads, no bias; causal, with no rotary
    position, by default.  ``qk_norm``: an RMSNorm of a head's ``D``
    channels over every q and k head (one gain each, ``eps``) before the
    rotation; ``rope_theta``: rotate-half rotary over all ``D`` channels;
    ``block_diffusion=b``: the input is a noisy and a clean copy of one
    sequence side by side (``2L`` positions), both copies take positions 0 ..
    L-1, and the mask is the block-diffusion mask of ``b``-position blocks
    (``ops/pallas/flash_attention.py``) in place of the causal one;
    ``sparse``: each query attends to the keys a selection chose for it
    (``ops/dsa_ops.py``), handed as packed bits beside the input, and the
    block returns ``[out, q, k, lse]``: the rotated heads and the rows'
    logsumexp, for the loss that trains the selection."""

    def __init__(self, units, num_heads=32, num_kv_heads=2, head_dim=128,
                 qk_norm=False, eps=1e-6, rope_theta=None, block_diffusion=0,
                 sparse=False, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._h, self._hkv, self._d = num_heads, num_kv_heads, head_dim
        self._theta, self._bd = rope_theta, int(block_diffusion)
        self._sparse = bool(sparse)

        def dense(n, in_units, prefix):
            return nn.Dense(n, flatten=False, use_bias=False,
                            in_units=in_units, prefix=prefix)

        with self.name_scope():
            self.q_proj = dense(num_heads * head_dim, units, "q_proj_")
            self.k_proj = dense(num_kv_heads * head_dim, units, "k_proj_")
            self.v_proj = dense(num_kv_heads * head_dim, units, "v_proj_")
            self.o_proj = dense(units, num_heads * head_dim, "o_proj_")
            self.q_norm = self.k_norm = None
            if qk_norm:
                self.q_norm = nn.RMSNorm(epsilon=eps, in_channels=head_dim,
                                         prefix="q_norm_")
                self.k_norm = nn.RMSNorm(epsilon=eps, in_channels=head_dim,
                                         prefix="k_norm_")

    def _rotate(self, F, t, n):
        """Rotary over the channels of t's ``n`` heads (B, L, n, D),
        positions along L; of each copy on its own under block diffusion."""
        if not self._bd:
            return F._contrib_rotary(t, theta=self._theta)
        copies = t.reshape((0, 2, -1, n, self._d))
        return F._contrib_rotary(copies, theta=self._theta,
                                 axis=2).reshape(t.shape)

    def hybrid_forward(self, F, u, bits=None, bits_t=None):
        def heads(t, n, norm=None, rotate=False):
            t = t.reshape((0, 0, n, self._d))
            if norm is not None:
                t = norm(t)
            if rotate:
                t = self._rotate(F, t, n)
            return t.transpose((0, 2, 1, 3))

        rotate = self._theta is not None
        q = heads(self.q_proj(u), self._h, self.q_norm, rotate)
        k = heads(self.k_proj(u), self._hkv, self.k_norm, rotate)
        v = heads(self.v_proj(u), self._hkv)
        if self._sparse:
            from ..ops import dsa_ops

            with jax.named_scope("mx_dsa_attention"):
                o, lse = _reg.invoke_fn(dsa_ops.sparse_attention,
                                        [q, k, v, bits, bits_t])
            y = self.o_proj(o.transpose((0, 2, 1, 3)).reshape((0, 0, -1)))
            return [y, q, k, lse]
        if self._bd:
            with jax.named_scope("mx_bd_attention"):
                o = F._contrib_flash_attention(q, k, v,
                                               block_diffusion=self._bd)
        else:
            with jax.named_scope("mx_gqa_attention"):
                o = F._contrib_flash_attention(q, k, v, causal=True)
        return self.o_proj(o.transpose((0, 2, 1, 3)).reshape((0, 0, -1)))


class NemotronHLayer(HybridBlock):
    """``x + mixer(RMSNorm(x))``; an expert layer also returns its load."""

    def __init__(self, mixer, eps=1e-5, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self.norm = nn.RMSNorm(epsilon=eps, prefix="norm_")
            self.mixer = mixer(prefix="mixer_")

    def hybrid_forward(self, F, x):
        out = self.mixer(self.norm(x))
        if isinstance(out, (list, tuple)):
            return [x + out[0], out[1]]
        return x + out


class NemotronHModel(HybridBlock):
    """tokens (B, L) int32 -> logits (B, L, vocab_size) f32."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 hybrid_override_pattern=PATTERN, mamba_num_heads=64,
                 mamba_head_dim=64, ssm_state_size=128, n_groups=8,
                 conv_kernel=4, chunk_size=128, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, n_routed_experts=128,
                 experts_held=None, num_experts_per_tok=6,
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 routed_scaling_factor=2.5, norm_topk_prob=True,
                 router_lr_mult=1.0, layer_norm_epsilon=1e-5, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        d, eps = hidden_size, layer_norm_epsilon
        mixers = {
            "M": functools.partial(
                Mamba2Mixer, d, mamba_num_heads, mamba_head_dim,
                ssm_state_size, n_groups, conv_kernel, chunk_size, eps),
            "E": functools.partial(
                MoELayer, d, n_routed_experts,
                tuple(experts_held) if experts_held else None,
                num_experts_per_tok, moe_intermediate_size,
                moe_shared_expert_intermediate_size, routed_scaling_factor,
                norm_topk_prob, router_lr_mult),
            "*": functools.partial(
                GQAttention, d, num_attention_heads, num_key_value_heads,
                head_dim),
        }
        unknown = set(hybrid_override_pattern) - set(mixers)
        if unknown or not hybrid_override_pattern:
            raise ValueError("hybrid_override_pattern takes M, E and *, got "
                             f"{hybrid_override_pattern!r}")
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, d, prefix="embed_")
            self.layers = []
            for i, letter in enumerate(hybrid_override_pattern):
                layer = NemotronHLayer(mixers[letter], eps,
                                       prefix=f"layer{i}_")
                self.register_child(layer, f"layer{i}")
                self.layers.append(layer)
            self.norm_f = nn.RMSNorm(epsilon=eps, prefix="norm_f_")
            self.head_weight = self.params.get("head_weight",
                                               shape=(vocab_size, d))

    def hybrid_forward(self, F, tokens, head_weight):
        x = self.embed(tokens)
        for layer in self.layers:
            out = checkpointed(layer, x)
            if isinstance(out, (list, tuple)):
                x, load = out
                layer.mixer.record_load(load)
            else:
                x = out
        h = self.norm_f(x)
        return vocab_logits(h, head_weight)


def nemotron_h(**kwargs) -> NemotronHModel:
    """The tower at the published widths of Nemotron-Labs-TwoTower-30B-A3B
    (the constructor's defaults); pass the pattern, ``vocab_size`` and
    ``experts_held`` of the share a chip holds."""
    return NemotronHModel(**kwargs)
