"""Mean host time of one call of the step in the window: the enqueue, not
the device (source: the benchmark's clock around each call)."""


def read(obs, args):
    d = obs["rec"].durations("step_call", obs["t0"], obs["t1"])
    return 1e3 * sum(d) / len(d) if d else None
