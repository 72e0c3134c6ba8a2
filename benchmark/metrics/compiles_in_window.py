"""Programs XLA compiled or loaded from its cache inside the window
(jax.monitoring events; should be 0)."""


def read(obs, args):
    return float(obs["compiles_in_window"])
