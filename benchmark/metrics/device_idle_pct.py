"""1 - the union of device-op intervals over the traced window."""


def read(obs, args):
    tr = obs["trace"]
    if not tr or not tr["busy_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
