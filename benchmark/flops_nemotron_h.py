"""Operations a chip's share of the ``nemotron_h`` tower needs, from shapes
alone, beside ``flops.py`` and under its rules: a multiply-add is two
operations, the backward pass twice the forward, nothing recomputed is
counted, and elementwise work, norms, the convolution, softmax, routing's
top-k and the embedding look-up are left out (so the shares read a little
low, never high).  Causal attention counts the half of the score matrix the
mask keeps; an expert layer counts the routed products of the pairs that an
even spread lands on the experts held here."""


def _mamba_fwd(cfg):
    d = cfg["hidden_size"]
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    inner = h * p
    proj = 2 * d * (2 * inner + 2 * g * n + h) + 2 * inner * d
    return proj + ssd_scan_fwd_flops_per_token(h, p, g, n, q)


def ssd_scan_fwd_flops_per_token(h, p, g, n, q):
    """The chunked scan's four products per token: C B^T inside a chunk,
    the masked (chunk x chunk) product with x, the chunk's state, and the
    carried state read out by C."""
    return 2 * q * n * g + 2 * q * p * h + 2 * p * n * h + 2 * p * n * h


def routed_fwd_flops_per_pair(cfg):
    return 2 * 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _experts_fwd(cfg):
    d = cfg["hidden_size"]
    wide = cfg.get("n_routed_experts_published", cfg["n_routed_experts"])
    shared = 2 * 2 * d * cfg["moe_shared_expert_intermediate_size"]
    pairs = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / wide
    return shared + 2 * d * wide + pairs * routed_fwd_flops_per_pair(cfg)


def _attention_fwd(cfg, seq):
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * d * (nh + 2 * nkv) * hd + 2 * nh * hd * d
    return proj + 2 * 2 * seq * nh * hd / 2          # causal half


def nemotron_h_train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of next-token training of
    the layers, experts and vocabulary rows this chip holds."""
    per = {"M": _mamba_fwd(cfg), "E": _experts_fwd(cfg),
           "*": _attention_fwd(cfg, seq)}
    fwd = sum(per[c] for c in cfg["hybrid_override_pattern"])
    fwd += 2 * cfg["hidden_size"] * cfg["vocab_size"]       # head
    return 3 * fwd
