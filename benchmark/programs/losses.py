"""Loss functions of the training cells, named by a configuration's
``program.loss`` as ``module:function``; each returns the callable that
``DataParallelStep`` takes.  A later configuration brings its own file."""


def mlm_every_position():
    """Masked-LM cross-entropy taken over every position (the label is the
    token), as ``chip_smoke.py`` has it."""
    from mxnet_tpu import gluon

    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(logits, labels):
        return ce(logits.reshape(-1, logits.shape[-1]), labels.reshape(-1))

    return mlm_loss
