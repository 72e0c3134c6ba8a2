"""Operations the algorithm needs, from shapes alone.

Model FLOPs count a multiply-add as two operations, the backward pass as
twice the forward, and nothing that is recomputed.  Elementwise work, layer
norms, softmax and embedding look-ups are left out (they are a few per mille
of the matmuls at these widths), so the shares reported are a little low,
never high.
"""


def _encoder_layer_fwd(units, hidden, seq):
    """One post-LN encoder layer, forward, FLOPs per token at length seq."""
    proj = 2 * units * (3 * units) + 2 * units * units      # qkv, out
    ffn = 2 * 2 * units * hidden
    attn = 2 * 2 * seq * units                              # QK^T, PV
    return proj + ffn + attn


def bert_mlm_train_flops_per_token(cfg, seq):
    """Forward + backward FLOPs per token of BERT masked-LM pretraining with
    the loss over every position (``models/bert.py``: an independent
    vocabulary-wide decoder after a units x units transform)."""
    u, h = cfg["hidden_size"], cfg["intermediate_size"]
    fwd = cfg["num_hidden_layers"] * _encoder_layer_fwd(u, h, seq)
    fwd += 2 * u * u                    # mlm transform
    fwd += 2 * u * cfg["vocab_size"]    # decoder
    return 3 * fwd
