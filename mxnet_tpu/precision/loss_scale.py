"""Traced dynamic loss scaling: every transition of the scaler state
machine runs INSIDE the compiled train step as device values.

The eager reference (``contrib/amp/amp.py`` ``DynamicLossScaler``) reads
every gradient back to host per step to decide overflow — a per-step
device->host sync that would stall the async step pipeline.  Here the
whole protocol is traced:

  * the loss is multiplied by the scale before ``value_and_grad`` (small
    fp16 grads then survive the 5-bit exponent);
  * un-scaling folds into the optimizer's existing ``rescale_grad``
    multiply (``rescale / scale`` — zero extra HBM passes);
  * overflow detection is one fused ``isfinite``-all reduce over the
    gradient tree;
  * a non-finite step SELECTS the old params/optimizer state (a traced
    no-op update: weights, momenta and Adam's ``t`` all hold), halves
    the scale, and resets the growth counter;
  * ``growth_interval`` consecutive finite steps double the scale.

The scaler state — ``scale`` (f32), ``growth`` (i32 consecutive-finite
counter), ``skipped`` (i32 cumulative skip count, observability) — is
part of the step's train state: it threads through the jitted step, is
checkpointed alongside the optimizer slots (``amp.*`` keys in
``opt_state``), and survives elastic reshard (replicated scalars place trivially on any mesh).

``overflow_flag`` is the eager-path export: ONE fused reduce over a
gradient list returning a DEVICE scalar, used by the
``contrib/amp`` compatibility shim so legacy Trainer scripts stop paying
a readback per gradient (they still pay exactly one, at the shim's
python-bool boundary).  It is registered in mxlint's HOT_PATH_ENTRIES —
no host sync may ever enter it.
"""
from __future__ import annotations

from typing import Dict

from .config import LossScaleConfig

__all__ = ["init_scaler_host", "grads_finite", "scaler_update",
           "overflow_flag", "SCALER_KEYS"]

# checkpoint key order (state_dict writes `amp.<key>` opt_state entries)
SCALER_KEYS = ("scale", "growth", "skipped")


def init_scaler_host(cfg: LossScaleConfig) -> Dict[str, "object"]:
    """Fresh host-side scaler state (the caller places it on device with
    its own sharding rules — replicated scalars)."""
    import numpy as np

    return {"scale": np.float32(cfg.init_scale),
            "growth": np.int32(0),
            "skipped": np.int32(0)}


def _all_finite(arrays):
    """Traced AND-of-isfinite fold over device arrays — the one shared
    reduction both the compiled step (``grads_finite``) and the eager
    shim (``overflow_flag``) build on, so their overflow semantics can
    never drift."""
    import jax.numpy as jnp

    flags = [jnp.all(jnp.isfinite(a)) for a in arrays]
    if not flags:
        return jnp.asarray(True)
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


def grads_finite(grads: Dict[str, "object"], mults: Dict[str, tuple]):
    """Traced all-finite flag over the TRAINABLE gradients (frozen
    params — lr_mult None in ``mults`` — are excluded; their grads never
    feed an update)."""
    return _all_finite([g for name, g in grads.items()
                        if mults.get(name, (1.0, 1.0))[0] is not None])


def scaler_update(state: Dict[str, "object"], finite,
                  cfg: LossScaleConfig) -> Dict[str, "object"]:
    """One traced transition of the scaler state machine.

    finite: overflow -> scale *= backoff (floored at 1.0), growth
    counter resets, skip counter bumps.  ``growth_interval`` consecutive
    finite steps -> scale *= growth_factor, counter resets.  With
    ``dynamic=False`` the scale is pinned; only the skip counter moves.
    """
    import jax.numpy as jnp

    scale = state["scale"]
    growth = state["growth"]
    skipped = state["skipped"] + jnp.where(finite, 0, 1).astype(jnp.int32)
    if not cfg.dynamic:
        return {"scale": scale, "growth": growth, "skipped": skipped}
    grown = (growth + 1) >= cfg.growth_interval
    new_scale = jnp.where(
        finite,
        jnp.where(grown, scale * cfg.growth_factor, scale),
        jnp.maximum(scale * cfg.backoff_factor, 1.0)).astype(jnp.float32)
    new_growth = jnp.where(jnp.logical_and(finite, jnp.logical_not(grown)),
                           growth + 1, 0).astype(jnp.int32)
    return {"scale": new_scale, "growth": new_growth, "skipped": skipped}


_OVERFLOW_JIT = None


def _overflow_impl(arrays):
    import jax.numpy as jnp

    return jnp.logical_not(_all_finite(arrays))


def overflow_flag(arrays):
    """ONE fused any-non-finite reduce over a list of device arrays ->
    a DEVICE 0-d bool (True = overflow).  The eager shim's building
    block: dispatch here is async; the caller decides when (whether) to
    read the flag back."""
    global _OVERFLOW_JIT
    if _OVERFLOW_JIT is None:
        import jax

        # mxlint: disable=retrace-hazard — built once, module-cached;
        # jax's own dispatch cache keys the per-signature specializations
        _OVERFLOW_JIT = jax.jit(_overflow_impl)
    return _OVERFLOW_JIT(tuple(arrays))
