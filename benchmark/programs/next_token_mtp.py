"""Loss of a next-token training cell whose model returns two logits
tensors: the trunk's and a multi-token-prediction module's.  Named by a
configuration's ``program.loss`` as ``module:function``; returns the callable
that ``DataParallelStep`` takes."""

MTP_LOSS_WEIGHT = 0.3


def next_token_mtp():
    """Mean cross-entropy of the trunk's position t against token t+1
    (positions 0 .. L-2) plus ``MTP_LOSS_WEIGHT`` times the mean
    cross-entropy of the module's position t against token t+2 (positions 0
    .. L-3); the driver hands the tokens as their own labels.  Returned as
    one value a row (the step takes the mean).  The logits come in float32
    and stay so.  The label's logit is picked by a one-hot product, not
    ``take_along_axis``: the gradient is then ``softmax - onehot`` in one
    elementwise pass and no scatter (PERF.md section 5: the scatter costs
    the ZAYA cell 33 ms a step)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import registry

    def mean_ce(logits, labels, ahead):
        lg = logits[:, :-ahead].astype(jnp.float32)
        hot = jax.nn.one_hot(labels[:, ahead:].astype(jnp.int32),
                             lg.shape[-1], dtype=jnp.float32)
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.sum(lg * hot, axis=-1)
        return jnp.mean(nll, axis=-1)

    def both(logits, logits_mtp, labels):
        return mean_ce(logits, labels, 1) \
            + MTP_LOSS_WEIGHT * mean_ce(logits_mtp, labels, 2)

    def next_token_mtp_loss(outputs, labels):
        if not isinstance(outputs, (list, tuple)):
            # a configuration that leaves the module out: the first term
            return registry.invoke_fn(lambda lg, lb: mean_ce(lg, lb, 1),
                                      [outputs, labels])
        logits, logits_mtp = outputs
        return registry.invoke_fn(both, [logits, logits_mtp, labels])

    return next_token_mtp_loss
