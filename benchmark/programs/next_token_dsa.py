"""Loss of a next-token training cell whose model also returns its sparse
attention indexers' KL loss (``models/keye.py``).  Named by a
configuration's ``program.loss`` as ``module:function``; returns the callable
that ``DataParallelStep`` takes."""


def next_token_dsa():
    """Mean cross-entropy of position t's logits against token t+1
    (positions 0 .. L-2; the driver hands the tokens as their own labels)
    plus the layers' indexer KL, each the mean over a row's queries; one
    value a row (the step takes the mean).  Weight 1: the two reach disjoint
    parameters.  The logits come in float32 and stay so; the label's logit
    is picked by a one-hot product (no scatter in the gradient)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import registry

    def per_row(logits, kl, labels):
        lg = logits[:, :-1].astype(jnp.float32)
        hot = jax.nn.one_hot(labels[:, 1:].astype(jnp.int32), lg.shape[-1],
                             dtype=jnp.float32)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.sum(lg * hot, axis=-1)
        return jnp.mean(nll, axis=-1) + kl.astype(jnp.float32)

    def next_token_dsa_loss(outputs, labels):
        logits, kl = outputs
        return registry.invoke_fn(per_row, [logits, kl, labels])

    return next_token_dsa_loss
