"""The graph-level AMP pass: apply a cast policy to a traced block apply.

Where ``contrib/amp`` casts PARAMETERS eagerly (``block.cast('bfloat16')``
— every op then runs in bf16, including the ones that shouldn't), this
pass rewrites the PROGRAM: during the one trace ``DataParallelStep._build``
runs, every op dispatch consults the active
:class:`~mxnet_tpu.precision.config.AmpPolicy` (see
``runtime.cast_inputs``, hooked into ``ops/registry.py``):

  * ``low``-class ops (matmul/conv) trace with their f32 float inputs
    cast to the policy dtype — parameters stay f32 master copies, the
    cast is a graph edge XLA fuses into the producer;
  * ``widen``-class ops (softmax/norm/reductions) trace with any
    low-precision float inputs cast back to f32;
  * block outputs cast to f32 at the boundary, so the loss (and its
    gradient seed) is always computed in f32.

Because the policy is applied at trace time inside ``_build``, the whole
mixed-precision program lands in ONE compiled executable — it composes
with the ``Plan`` (``Plan.precision`` serializes it into checkpoint
layouts; the policy signature joins ``_fingerprint_parts``).  With no
policy the wrapped apply is returned UNCHANGED — the AMP-off program is
byte-for-byte the pre-pass program.
"""
from __future__ import annotations

from .config import AmpPolicy, LossScaleConfig, PrecisionConfig
from .runtime import amp_scope

__all__ = ["apply_amp", "amp_scope", "AmpPolicy", "LossScaleConfig",
           "PrecisionConfig"]


def apply_amp(apply_fn, policy: AmpPolicy):
    """Wrap a ``fn(params, key, *inputs) -> (out_or_list, aux)`` block
    apply so its trace runs under ``policy``, with f32 outputs at the
    boundary.  Identity when ``policy`` is None."""
    if policy is None:
        return apply_fn

    def amp_apply(params, key, *inputs):
        import jax.numpy as jnp

        def widen(arr):
            return (arr.astype(jnp.float32)
                    if jnp.issubdtype(arr.dtype, jnp.floating)
                    and arr.dtype != jnp.float32 else arr)

        with amp_scope(policy):
            out, aux = apply_fn(params, key, *inputs)
        if isinstance(out, list):
            out = [widen(o) for o in out]
        else:
            out = widen(out)
        return out, aux

    return amp_apply
