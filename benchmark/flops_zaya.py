"""Operations a stage of the ``zaya`` family needs, from shapes alone, beside
``flops.py`` and under its rules: a multiply-add is two operations, the
backward pass twice the forward, nothing recomputed is counted, and
elementwise work, norms, the depthwise convolution, softmax, rotary, routing's
top-k and the embedding look-up are left out (so the shares read a little
low, never high).  Causal attention counts the half of the score matrix the
mask keeps; an expert layer counts the three products of the pairs that an
even spread lands on the experts held here."""


def attention_scores_fwd_flops_per_token(cfg, seq):
    """q k^T and p v over the causal half, every query head."""
    return 2 * 2 * seq * cfg["num_attention_heads"] * cfg["head_dim"] / 2


def gated_fwd_flops_per_pair(cfg):
    return 3 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def pairs_per_token(cfg):
    """(token, choice) pairs an even router lands on the experts held here
    (``num_experts`` of ``num_experts_published``), per token."""
    held = cfg["num_experts"]
    return cfg["num_experts_per_tok"] * held / cfg.get(
        "num_experts_published", held)


def _attention_fwd(cfg, seq):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * d * (nh + 2 * nkv) * hd + 2 * nh * hd * d
    mix = 2 * cfg["cca_time1"] * (nh + nkv) * hd * hd    # a head's channels
    return proj + mix + attention_scores_fwd_flops_per_token(cfg, seq)


def _experts_fwd(cfg):
    d, rw = cfg["hidden_size"], cfg["router_hidden_size"]
    wide = cfg.get("num_experts_published", cfg["num_experts"])
    router = 2 * (d * rw + 2 * rw * rw + rw * wide)
    return router + pairs_per_token(cfg) * gated_fwd_flops_per_pair(cfg)


def zaya_train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of next-token training of
    the layers, experts and vocabulary rows this chip holds; the tied
    matrix is multiplied once (the head), the look-up is not counted."""
    layers = len(cfg["layer_types"])
    fwd = layers * (_attention_fwd(cfg, seq) + _experts_fwd(cfg))
    fwd += 2 * cfg["hidden_size"] * cfg["vocab_size"]       # head
    return 3 * fwd
