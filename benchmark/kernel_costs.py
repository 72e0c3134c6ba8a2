"""Operations and least HBM bytes of the kernels a configuration brings, for
one training step at a cell's traffic: ``(cfg, traffic) -> (flops, bytes)``,
forward and backward (twice the forward), nothing recomputed counted.  Read
by ``metrics/kernel_peak_share.py``.

Bytes are those that cannot stay on the chip: what the kernel must read from
and write to HBM once if everything between were kept on chip (PERF.md
section 3's layer-norm lesson: count no intermediate), in the configuration's
stored type.  The backward reads the forward's inputs and the output's
cotangent and writes the inputs' cotangents: the same bytes again, twice.
"""
from benchmark import flops_nemotron_h as _f

_ITEM = {"bfloat16": 2, "float32": 4}


def _tokens(traffic):
    return traffic["batch_per_chip"] * traffic["seq_len"]


def ssd_scan(cfg, traffic):
    """The chunked scans of every M layer: x and y (heads x head_dim), B and
    C (groups x state), dt (heads) per token in and out."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    layers = cfg["hybrid_override_pattern"].count("M")
    t = _tokens(traffic)
    flops = 3 * layers * t * _f.ssd_scan_fwd_flops_per_token(h, p, g, n, q)
    per_token = (2 * h * p + 2 * g * n + h) * _ITEM[cfg["dtype"]]
    return flops, 3 * layers * t * per_token


def moe_experts(cfg, traffic):
    """The grouped products of every E layer for the pairs an even router
    lands here: a pair's row in and out (hidden wide; the expert-wide
    activation between the two products could stay on chip), and the held
    experts' weights once."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    wide = cfg.get("n_routed_experts_published", held)
    layers = cfg["hybrid_override_pattern"].count("E")
    pairs = _tokens(traffic) * cfg["num_experts_per_tok"] * held / wide
    flops = 3 * layers * pairs * _f.routed_fwd_flops_per_pair(cfg)
    item = _ITEM[cfg["dtype"]]
    nbytes = 3 * layers * (pairs * 2 * d + held * 2 * d * f) * item
    return flops, nbytes
