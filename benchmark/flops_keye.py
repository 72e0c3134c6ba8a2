"""Operations one chip's share of Keye-VL-2.0-30B-A3B's language model needs
under next-token training, from shapes alone, beside ``flops.py`` and under
its rules: a multiply-add is two operations, the backward pass twice the
forward, nothing recomputed is counted, and elementwise work (norms, softmax,
rotary, the selection's thresholds, routing's top-k) and the embedding
look-up are left out (so the shares read a little low, never high).  The
indexer's scores count every causal pair (it scores them all), the sparse
attention only the pairs the selection keeps (``pairs_selected``); the KL
target's second pass over the scores is work the algorithm chose, not the
model's, and is not counted.  An expert layer counts the three products of
the pairs an even spread lands on the experts held here."""
from benchmark.flops_sdar import attention_products_fwd_flops_per_position
from benchmark.flops_zaya import gated_fwd_flops_per_pair, pairs_per_token


def pairs_selected(length, topk):
    """Query-key pairs a row's selection keeps: every causal pair of the
    first ``topk`` queries, ``topk`` a query after."""
    first = min(length, topk)
    return first * (first + 1) // 2 + (length - first) * topk


def indexer_fwd_flops_per_token(cfg, seq):
    """The indexer's projections (J heads of Dk, one key head, J weights)
    and its scores over the causal keys, a token."""
    sa, d = cfg["sa_config"], cfg["hidden_size"]
    jh, dk = sa["indexer_num_heads"], sa["indexer_head_dim"]
    proj = 2 * d * (jh * dk + dk + jh)
    return proj + 2 * jh * dk * (seq + 1) / 2


def sparse_attention_fwd_flops_per_token(cfg, seq):
    """q k^T and p v over the selected pairs, every query head."""
    pairs = pairs_selected(seq, cfg["sa_config"]["topk"]) / seq
    return 2 * 2 * pairs * cfg["num_attention_heads"] * cfg["head_dim"]


def keye_train_flops_per_token(cfg, seq):
    """Forward + backward model FLOPs per token of next-token training of
    the layers, experts and vocabulary rows this chip holds."""
    d = cfg["hidden_size"]
    wide = cfg.get("num_experts_published", cfg["num_experts"])
    layer = attention_products_fwd_flops_per_position(cfg) \
        + indexer_fwd_flops_per_token(cfg, seq) \
        + sparse_attention_fwd_flops_per_token(cfg, seq) \
        + 2 * d * wide + pairs_per_token(cfg) * gated_fwd_flops_per_pair(cfg)
    fwd = len(cfg["layer_types"]) * layer + 2 * d * cfg["vocab_size"]
    return 3 * fwd
