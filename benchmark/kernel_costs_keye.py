"""Operations and least HBM bytes of the kernels the Keye-VL-2.0 language
model runs, for one training step at a cell's traffic, beside
``kernel_costs.py`` and under its rules: ``(cfg, traffic) -> (flops,
bytes)``, forward and backward (twice the forward), nothing recomputed
counted; bytes are those that cannot stay on the chip, in the
configuration's stored type, and the backward moves the forward's bytes
again, twice."""
from benchmark import flops_keye as _f
from benchmark.kernel_costs import _ITEM


def dsa_attention(cfg, traffic):
    """The flash calls under the selection, every layer: the SELECTED pairs
    at every query head (the kernels visit every causal tile: the distance is
    the share's), q and o (query heads x head) and k and v (key-value heads x
    head) once a position."""
    layers, rows = len(cfg["layer_types"]), traffic["batch_per_chip"]
    seq, hd = traffic["seq_len"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = 3 * layers * rows * seq \
        * _f.sparse_attention_fwd_flops_per_token(cfg, seq)
    per_position = 2 * (h + hkv) * hd * _ITEM[cfg["dtype"]]
    return flops, 3 * layers * rows * seq * per_position


def dsa_scores(cfg, traffic):
    """The indexer's scores for the selection, every layer, once (the
    selection is not differentiated; the recomputed layer's second pass is
    not counted): J heads of Dk over the causal pairs; the indexer's
    queries, key and weights read once, the selection's words written
    once."""
    sa = cfg["sa_config"]
    layers, rows = len(cfg["layer_types"]), traffic["batch_per_chip"]
    seq, jh, dk = traffic["seq_len"], sa["indexer_num_heads"], \
        sa["indexer_head_dim"]
    flops = layers * rows * 2 * jh * dk * seq * (seq + 1) / 2
    item = _ITEM[cfg["dtype"]]
    nbytes = layers * rows * (seq * (jh * dk + dk) * item + seq * jh * 4
                              + 2 * seq * seq // 8)
    return flops, nbytes


def dsa_topk(cfg, traffic):
    """Each query's threshold, every layer, once: no product (the bisection
    compares, elementwise work ``flops_keye`` leaves out); the causal half
    of the f32 scores it is handed read once, the thresholds written once.
    The distance from its time is the bisection's 32 passes."""
    layers, rows = len(cfg["layer_types"]), traffic["batch_per_chip"]
    seq = traffic["seq_len"]
    return 0.0, layers * rows * 4 * (seq * (seq + 1) // 2 + seq)


def _kl_inputs(cfg, traffic):
    """Bytes a layer's KL kernel reads once: q, k (the heads' mean
    probability), the attention's logsumexp, the indexer's queries, key and
    weights, and the selection's words; its statistics written once."""
    sa = cfg["sa_config"]
    seq, item = traffic["seq_len"], _ITEM[cfg["dtype"]]
    h, hkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    jh, dk = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return seq * ((h + hkv) * hd * item + h * 4 + (jh * dk + dk) * item
                  + jh * 4 + seq // 8 + 8 * 4)


def dsa_kl_fwd(cfg, traffic):
    """The indexer's KL, every layer: its scores over the SELECTED pairs
    (J heads of Dk); the heads' mean probability is the attention's, made
    again in the kernel, and not counted (nothing recomputed is)."""
    sa = cfg["sa_config"]
    layers, rows = len(cfg["layer_types"]), traffic["batch_per_chip"]
    pairs = _f.pairs_selected(traffic["seq_len"], sa["topk"])
    flops = layers * rows * pairs * 2 * sa["indexer_num_heads"] \
        * sa["indexer_head_dim"]
    return flops, layers * rows * _kl_inputs(cfg, traffic)


def dsa_kl_bwd(cfg, traffic):
    """The KL's gradient in the indexer's queries, key and weights: twice
    the forward's products and its bytes again, twice."""
    flops, nbytes = dsa_kl_fwd(cfg, traffic)
    return 2 * flops, 2 * nbytes
