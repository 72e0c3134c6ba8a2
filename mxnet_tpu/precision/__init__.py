"""Precision subsystem (docs/PRECISION.md): graph-level AMP, traced
dynamic loss scaling, and calibrated int8 serving.

Three pillars over the compiled train/serve paths:

  * ``amp_pass`` — a cast-policy rewrite applied at trace time inside
    ``DataParallelStep._build``: per-op-class dispositions (matmul/conv
    compute in bf16, softmax/norm/reductions widen to f32) carried by a
    serializable :class:`~mxnet_tpu.precision.config.AmpPolicy` on the
    :class:`~mxnet_tpu.parallel.plan.Plan`;
  * ``loss_scale`` — the dynamic loss-scale state machine as device
    values inside the jitted step (scale/growth/skip state in the train
    state, non-finite steps become traced no-op updates, no host
    readback in any hot path);
  * ``quantize`` — post-training int8 for the serving engine: calibrated
    per-layer scales (reusing ``contrib/quantization``'s calibrators)
    rewrite Dense/Conv in the adapter's traced prefill/decode graphs
    onto the ``ops/quantization.py`` int8 primitives — ONE quantized
    decode executable, fingerprinted by the quant config.

Env surface (env_vars.py): MX_AMP, MX_AMP_POLICY, MX_LOSS_SCALE,
MX_QUANTIZE, MX_QUANT_CALIB, MX_SERVE_INT4, MX_QUANT_GROUP (all the
quant/AMP rewrites are registered graph passes — see ``passes/``).
"""
from .config import (AmpPolicy, LossScaleConfig, PrecisionConfig,
                     DEFAULT_LOW_OPS, DEFAULT_WIDEN_OPS)
from .amp_pass import apply_amp
from .runtime import amp_scope, quant_scope, quant_entry
from . import loss_scale
from .quantize import (QuantizedAdapter, quantize_adapter,
                       maybe_quantize_adapter, Int4WeightAdapter,
                       int4_adapter, maybe_int4_adapter)

__all__ = ["AmpPolicy", "LossScaleConfig", "PrecisionConfig",
           "DEFAULT_LOW_OPS", "DEFAULT_WIDEN_OPS", "apply_amp",
           "amp_scope", "quant_scope", "quant_entry", "loss_scale",
           "QuantizedAdapter", "quantize_adapter",
           "maybe_quantize_adapter", "Int4WeightAdapter",
           "int4_adapter", "maybe_int4_adapter"]
