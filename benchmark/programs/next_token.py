"""Loss of the next-token training cells, named by a configuration's
``program.loss`` as ``module:function``; returns the callable that
``DataParallelStep`` takes."""


def next_token():
    """Cross-entropy of position t's logits against token t+1, positions
    0 .. L-2 (the driver hands the tokens as their own labels); the step
    takes the mean.  The logits come in float32 and stay so."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import registry

    def per_position(logits, labels):
        lg = logits[:, :-1].astype(jnp.float32)
        lb = labels[:, 1:].astype(jnp.int32)
        picked = jnp.take_along_axis(lg, lb[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked

    def next_token_loss(logits, labels):
        return registry.invoke_fn(per_position, [logits, labels])

    return next_token_loss
