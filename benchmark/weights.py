"""Weights from ``--seed``: every leaf in one jitted call, on the device, in
the type it is served in.  The program and the plain reference both start
from what this returns, so neither hands the other anything it has made.

A reference module gives ``param_spec(cfg)``: an ordered list of
``(name, shape, ("normal", std) | ("const", value))``.  Names are the
program's parameter names without the block's own prefix.
"""
import functools


@functools.lru_cache(maxsize=4)
def _maker(spec, dtype):
    import jax
    import jax.numpy as jnp

    def make(key):
        out = {}
        for i, (name, shape, (kind, val)) in enumerate(spec):
            if kind == "const":
                out[name] = jnp.full(shape, val, dtype)
            else:
                k = jax.random.fold_in(key, i)
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * val).astype(dtype)
        return out

    return jax.jit(make)


def freeze(spec):
    return tuple((n, tuple(s), (k, float(v))) for n, s, (k, v) in spec)


def make_weights(spec, seed, dtype, device=None):
    """name -> array on ``device`` (jax's default device when None)."""
    import jax

    key = jax.random.PRNGKey(int(seed))
    if device is not None:
        key = jax.device_put(key, device)
    return _maker(freeze(spec), str(dtype))(key)


def install(net, weights, ctx):
    """Put ``weights`` into a Gluon block's parameters without a forward
    pass and without the host: deferred shapes are taken from the arrays.
    The block must have been ``initialize``d (any initializer: what it made
    is replaced) and cast to its served type."""
    prefix = net.prefix
    params = net.collect_params()
    names = {n[len(prefix):] if n.startswith(prefix) else n: p
             for n, p in params.items()}
    if set(names) != set(weights):
        raise ValueError(
            "the program's parameters and the reference's differ: "
            f"only in the program {sorted(set(names) - set(weights))[:6]}, "
            f"only in the reference {sorted(set(weights) - set(names))[:6]}")
    for name, p in names.items():
        w = weights[name]
        p._set_shape_if_deferred(tuple(w.shape))
        p._finish_deferred_init()
        nd = p.data(ctx)
        if tuple(nd.shape) != tuple(w.shape) or nd._data.dtype != w.dtype:
            raise ValueError(
                f"{name}: program {tuple(nd.shape)} {nd._data.dtype}, "
                f"reference {tuple(w.shape)} {w.dtype}")
        nd._set_data(w)
