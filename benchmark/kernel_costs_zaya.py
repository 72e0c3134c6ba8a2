"""Operations and least HBM bytes of the kernels the ``zaya`` configurations
run, for one training step at a cell's traffic, beside ``kernel_costs.py``
and under its rules: ``(cfg, traffic) -> (flops, bytes)``, forward and
backward (twice the forward), nothing recomputed counted; bytes are those
that cannot stay on the chip, in the configuration's stored type, and the
backward moves the forward's bytes again, twice."""
from benchmark import flops_zaya as _f
from benchmark.kernel_costs import _ITEM, _tokens


def flash_attention(cfg, traffic):
    """Causal grouped-query attention of every layer: the half of the score
    matrix the mask keeps at every query head; q and o (query heads x head)
    and k and v (key-value heads x head) once per token."""
    layers, t = len(cfg["layer_types"]), _tokens(traffic)
    flops = 3 * layers * t * _f.attention_scores_fwd_flops_per_token(
        cfg, traffic["seq_len"])
    per_token = 2 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) \
        * cfg["head_dim"] * _ITEM[cfg["dtype"]]
    return flops, 3 * layers * t * per_token


def gated_experts(cfg, traffic):
    """The grouped products of every expert layer for the pairs an even
    router lands here: three products a row; a pair's row in and out (hidden
    wide; the two expert-wide activations between could stay on chip), and
    the held experts' three matrices once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held, layers = cfg["num_experts"], len(cfg["layer_types"])
    pairs = _tokens(traffic) * _f.pairs_per_token(cfg)
    flops = 3 * layers * pairs * _f.gated_fwd_flops_per_pair(cfg)
    nbytes = 3 * layers * (pairs * 2 * d + held * 3 * d * f) \
        * _ITEM[cfg["dtype"]]
    return flops, nbytes
