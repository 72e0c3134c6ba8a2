"""The whole step's share of the chip's bf16 peak: model FLOPs (from
shapes, by the function the configuration's ``flops`` group names under the
key ``args["flops"]``; no recompute, no padding) of the items done in the
window over the window's time, the chips and the published peak."""
from benchmark import loader


def read(obs, args):
    if obs["peaks"] is None or not obs["items"]:
        return None
    cfg = obs["cell"].config
    per_item = loader.factory(cfg["flops"][args["flops"]])(cfg, obs["seq_len"])
    return 100.0 * obs["items"] * per_item / obs["window_s"] / (
        obs["chips"] * obs["peaks"]["bf16_flops"])
