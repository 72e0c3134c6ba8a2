"""Device time of one kernel per step of the traced window: the durations of
the trace's events that ``checks/<cell>.json`` ``kernels[<kernel>]``
matches, over the whole steps (training steps, decode steps) the traced
window held.  Nothing to read where the kernel is not on the cell's path."""


def read(obs, args):
    tr = obs["trace"]
    if not tr or not tr["steps"]:
        return None
    secs = tr["kernel_seconds"].get(args["kernel"], 0.0)
    if not secs:
        return None
    return 1e3 * secs / tr["steps"]
