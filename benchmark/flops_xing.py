"""Operations one chip's share of the ``xing4_0`` family needs, from shapes
alone, beside ``flops.py`` and under its rules: a multiply-add is two
operations, the backward pass twice the forward, nothing recomputed is
counted, and elementwise work (norms, softmax, rotary, the streams' mixes,
Sinkhorn's iterations, routing's top-k) and the embedding look-up are left
out (so the shares read a little low, never high).  Causal attention counts
the half of the score matrix the mask keeps, over the channels the scores
are taken on (plain + rotary) and the values' width; an expert layer counts
the three products of the pairs that an even spread lands on the experts
held here."""
from benchmark.flops_zaya import gated_fwd_flops_per_pair


def sparse_layers(cfg):
    """Expert layers held: the trunk's and the prediction module's."""
    return cfg["layer_types"].count("sparse") \
        + cfg["num_nextn_predict_layers"]


def layers(cfg):
    return len(cfg["layer_types"]) + cfg["num_nextn_predict_layers"]


def attention_scores_fwd_flops_per_token(cfg, seq):
    """q k^T over plain + rotary channels and p v over the values' width,
    the causal half, every head."""
    wide = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] \
        + cfg["v_head_dim"]
    return 2 * seq * cfg["num_attention_heads"] * wide / 2


def attention_products_fwd_flops_per_token(cfg):
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return 2 * (d * qr + qr * h * (dn + dr) + d * (kr + dr)
                + kr * h * (dn + dv) + h * dv * d)


def pairs_per_token(cfg):
    """(token, choice) pairs an even router lands on the experts held here
    (``n_routed_experts`` of ``n_routed_experts_published``), per token."""
    held = cfg["n_routed_experts"]
    return cfg["num_experts_per_tok"] * held / cfg.get(
        "n_routed_experts_published", held)


def mix_projection_fwd_flops_per_token(cfg):
    """One sublayer's ``n d`` by ``2 n + n^2`` product."""
    n = cfg["hc_mult"]
    return 2 * n * cfg["hidden_size"] * (2 * n + n * n)


def xing_train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of next-token training of
    the layers, experts and vocabulary rows this chip holds, the prediction
    module and both readings of the head with them."""
    d = cfg["hidden_size"]
    wide = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    every = attention_products_fwd_flops_per_token(cfg) \
        + attention_scores_fwd_flops_per_token(cfg, seq) \
        + 2 * mix_projection_fwd_flops_per_token(cfg)
    dense = 3 * 2 * d * cfg["intermediate_size"]
    sparse = 2 * d * wide \
        + cfg["n_shared_experts"] * gated_fwd_flops_per_pair(cfg) \
        + pairs_per_token(cfg) * gated_fwd_flops_per_pair(cfg)
    fwd = layers(cfg) * every + cfg["layer_types"].count("dense") * dense \
        + sparse_layers(cfg) * sparse
    heads = 1 + cfg["num_nextn_predict_layers"]
    fwd += heads * 2 * d * cfg["vocab_size"]
    fwd += cfg["num_nextn_predict_layers"] * 2 * 2 * d * d      # W_eh
    return 3 * fwd
