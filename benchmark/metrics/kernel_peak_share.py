"""A kernel's share of the chip's peak: the least time the chip could take
for the kernel's work in one step, the larger of its model operations over
the published bf16 peak and its least HBM bytes over the published
bandwidth, over the device time the trace gives the kernel per step
(``checks/<cell>.json`` ``kernels[<kernel>]`` matches its events).  The
operations and bytes come from the function ``args["cost"]`` names
(``benchmark/kernel_costs.py``): ``(cfg, traffic) -> (flops, bytes)`` of one
step, forward and backward, nothing recomputed counted.  Nothing where the
trace holds no event of the kernel."""
from benchmark import loader


def read(obs, args):
    tr, peaks = obs["trace"], obs["peaks"]
    if not tr or not tr["steps"] or peaks is None:
        return None
    secs = tr["kernel_seconds"].get(args["kernel"], 0.0)
    if not secs:
        return None
    cell = obs["cell"]
    flops, nbytes = loader.factory(args["cost"])(cell.config, cell.traffic)
    least = max(flops / peaks["bf16_flops"],
                nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (secs / tr["steps"])
