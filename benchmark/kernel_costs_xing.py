"""Operations and least HBM bytes of the kernels the ``xing4_0``
configurations run, for one training step at a cell's traffic, beside
``kernel_costs.py`` and under its rules: ``(cfg, traffic) -> (flops,
bytes)``, forward and backward (twice the forward), nothing recomputed
counted; bytes are those that cannot stay on the chip, in the configuration's
stored type, and the backward moves the forward's bytes again, twice."""
from benchmark import flops_xing as _f
from benchmark.kernel_costs import _ITEM, _tokens


def mhc_mix(cfg, traffic):
    """The hyper-connections of every sublayer (two a layer) at their least:
    the streams read once and written once (``n`` x hidden in, the same
    out; the coefficients, the mixed input and the sublayer's result could
    stay on chip) and the one ``n d`` by ``2 n + n^2`` product."""
    t, sublayers = _tokens(traffic), 2 * _f.layers(cfg)
    flops = 3 * sublayers * t * _f.mix_projection_fwd_flops_per_token(cfg)
    per_token = 2 * cfg["hc_mult"] * cfg["hidden_size"] * _ITEM[cfg["dtype"]]
    return flops, 3 * sublayers * t * per_token


def mla_attention(cfg, traffic):
    """Causal latent attention of every layer: the half of the score matrix
    the mask keeps, scores over plain + rotary channels and values of their
    own width; q and k (heads x (plain + rotary)) and v and o (heads x value
    width) once per token."""
    t, n = _tokens(traffic), _f.layers(cfg)
    flops = 3 * n * t * _f.attention_scores_fwd_flops_per_token(
        cfg, traffic["seq_len"])
    per_token = 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"]) * _ITEM[cfg["dtype"]]
    return flops, 3 * n * t * per_token


def gated_experts(cfg, traffic):
    """The grouped products of every expert layer for the pairs an even
    router lands here: three products a row; a pair's row in and out (hidden
    wide; the two expert-wide activations between could stay on chip), and
    the held experts' three matrices once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, n = cfg["n_routed_experts"], _f.sparse_layers(cfg)
    pairs = _tokens(traffic) * _f.pairs_per_token(cfg)
    flops = 3 * n * pairs * _f.gated_fwd_flops_per_pair(cfg)
    nbytes = 3 * n * (pairs * 2 * d + held * 3 * d * f) * _ITEM[cfg["dtype"]]
    return flops, nbytes
