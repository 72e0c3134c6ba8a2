"""The built-in pass catalog (docs/PRECISION.md §Pass pipeline).

Absorbs the PR 15 one-offs as registered passes — ``amp`` wraps the
graph-level cast policy, ``quant_int8`` the calibrated serving rewrite —
with UNCHANGED behavior (each pass's scope is the exact precision scope
the module globals drove, so the traced programs are bitwise identical
to the pre-pipeline path), and adds the two new ones this layer
unlocked:

  * ``quant_int4`` — weight-only int4 serving (precision/quantize.py's
    int4 path): packed weights + group-wise scales dequantize in-trace;
  * ``fused_kernels`` — substitute registered Pallas kernels
    (ops/pallas/registry.py) for their op-class at the dispatch point.

Pipeline factories live here too: :func:`pipeline_for_training` (built
from a Plan's PrecisionConfig + MX_PALLAS_FUSED) and
:func:`pipeline_for_serving` (adapter-contributed passes + fused), both
subject to MX_PASSES toggles.

Import discipline: this module sits under ``passes/__init__`` on the
package import spine — precision/pallas imports stay inside methods.
"""
from __future__ import annotations

import os
from typing import Iterable, Optional, Tuple

from ..base import MXNetError
from . import hooks
from .pipeline import (GraphPass, PassPipeline, apply_env_toggles,
                       register_pass_type)

__all__ = ["AmpPass", "QuantizeInt8Pass", "QuantizeInt4Pass",
           "FusedKernelPass", "fused_kernels_from_env",
           "pipeline_for_training", "pipeline_for_serving"]


# ---------------------------------------------------------------------------
# amp
# ---------------------------------------------------------------------------
@register_pass_type
class AmpPass(GraphPass):
    """Graph-level AMP as a pipeline pass: low-class ops trace with
    policy-dtype inputs, widen-class ops with f32, block outputs widen
    at the boundary (``precision/amp_pass.apply_amp`` — the one copy of
    that lowering)."""

    name = "amp"

    def __init__(self, policy, enabled: bool = True):
        super().__init__(enabled=enabled)
        if policy is None:
            raise MXNetError("AmpPass: policy must be an AmpPolicy (a "
                             "policy-less pass is just absent — don't add "
                             "it to the pipeline)")
        self.policy = policy

    def signature(self) -> Tuple:
        return self.policy.signature()

    def scope(self):
        from ..precision.runtime import amp_scope

        return amp_scope(self.policy)

    def wrap_apply(self, apply_fn):
        from ..precision.amp_pass import apply_amp

        return apply_amp(apply_fn, self.policy)

    def metadata(self) -> dict:
        # the backward-graph seam (docs/PRECISION.md §Pass pipeline): a
        # forward op traced with cast inputs yields a jax.vjp backward in
        # the SAME dtypes — these are the facts a future quantized-grads
        # pass keys off, published here so it has a home (no behavior
        # rides on this dict)
        return {"backward": {
            "grad_dtype": self.policy.dtype,
            "low": list(self.policy.low),
            "widen": list(self.policy.widen),
            "note": "vjp of a low-class op computes its input/param "
                    "cotangents in the policy dtype; widen-class "
                    "cotangents stay f32; the loss gradient seed is f32 "
                    "(boundary widen)"}}

    def config_json(self) -> dict:
        return {"policy": self.policy.to_json()}

    @classmethod
    def from_config(cls, rec: dict) -> "AmpPass":
        from ..precision.config import AmpPolicy

        return cls(AmpPolicy.from_json(rec.get("policy") or {}))


# ---------------------------------------------------------------------------
# quantization (int8 calibrated / int4 weight-only)
# ---------------------------------------------------------------------------
class _QuantPassBase(GraphPass):
    """Shared shape of the serving quant passes: a {id(layer): twin}
    entries map activated via ``runtime.quant_scope`` (the gluon
    Dense/Conv ``hybrid_forward`` consults it — the op-CLASS substitution
    happens at the layer seam, not the dispatch point), plus a
    restart-stable per-layer signature.

    ``from_config`` rebuilds a DESCRIPTOR pass: same signature (so
    fingerprints round-trip through checkpoint layout JSON), but no
    entries — entering its scope raises, because twins hold device
    buffers only the live model can produce."""

    def __init__(self, entries, layer_sig: Tuple, enabled: bool = True):
        super().__init__(enabled=enabled)
        self._entries = entries
        self._layer_sig = tuple(layer_sig)

    def scope(self):
        if self._entries is None:
            raise MXNetError(
                f"{self.name}: descriptor-only pass (rebuilt from JSON) "
                "cannot activate — quantized twins hold device buffers; "
                "re-quantize the live adapter instead")
        from ..precision.runtime import quant_scope

        return quant_scope(self._entries)


@register_pass_type
class QuantizeInt8Pass(_QuantPassBase):
    """Calibrated int8 serving rewrite (PR 15) as a pipeline pass: the
    scope maps Dense/Conv layers onto their calibrated int8 twins inside
    the adapter's traced prefill/decode bodies."""

    name = "quant_int8"

    def __init__(self, entries, calib_mode: str, layer_sig: Tuple,
                 enabled: bool = True):
        super().__init__(entries, layer_sig, enabled=enabled)
        self.calib_mode = calib_mode

    def signature(self) -> Tuple:
        return ("int8", self.calib_mode, self._layer_sig)

    def config_json(self) -> dict:
        return {"calib_mode": self.calib_mode,
                "layers": [list(e) for e in self._layer_sig]}

    @classmethod
    def from_config(cls, rec: dict) -> "QuantizeInt8Pass":
        return cls(None, rec.get("calib_mode", "naive"),
                   tuple(tuple(e) for e in rec.get("layers", ())))


@register_pass_type
class QuantizeInt4Pass(_QuantPassBase):
    """Weight-only int4 serving rewrite: Dense/Conv weights packed 2 per
    byte with group-wise scales (MX_QUANT_GROUP), dequantized IN-TRACE
    inside the engine's prefill/decode bodies (precision/quantize.py int4
    path) — ~0.15x weight bytes, the decode-bandwidth win."""

    name = "quant_int4"

    def __init__(self, entries, group_size: int, layer_sig: Tuple,
                 enabled: bool = True):
        super().__init__(entries, layer_sig, enabled=enabled)
        self.group_size = int(group_size)

    def signature(self) -> Tuple:
        return ("int4", self.group_size, self._layer_sig)

    def config_json(self) -> dict:
        return {"group_size": self.group_size,
                "layers": [list(e) for e in self._layer_sig]}

    @classmethod
    def from_config(cls, rec: dict) -> "QuantizeInt4Pass":
        return cls(None, int(rec.get("group_size", 32)),
                   tuple(tuple(e) for e in rec.get("layers", ())))


# ---------------------------------------------------------------------------
# fused kernels
# ---------------------------------------------------------------------------
@register_pass_type
class FusedKernelPass(GraphPass, hooks.OpHook):
    """Substitute registered Pallas kernels for their op-class at the
    dispatch point (ops/pallas/registry.py, the TPP-style registry —
    arXiv:2104.05755).  The pass IS its own dispatch hook: the traced
    branch of ``_invoke_impl`` asks ``substitute(op_name, attrs)`` and
    swaps the op's FCompute when the registry carries a kernel for the
    op-class on the platform the trace targets.  Off (disabled or not in
    the pipeline) the dispatch path is untouched — bitwise the
    pre-pipeline program."""

    name = "fused_kernels"

    def __init__(self, ops: Optional[Iterable[str]] = None,
                 enabled: bool = True):
        super().__init__(enabled=enabled)
        # None = every registered kernel; a tuple restricts the set (and
        # is fingerprint identity either way, resolved at construction
        # so later registry growth can't silently change a live program)
        if ops is None:
            from ..ops.pallas import registry as kreg

            ops = kreg.registered_ops()
        self._ops = tuple(sorted(ops))

    def signature(self) -> Tuple:
        return ("fused", self._ops)

    def scope(self):
        return hooks.op_hook(self)

    def substitute(self, op_name, attrs):
        if op_name not in self._ops:
            return None
        from ..ops.pallas import registry as kreg

        return kreg.substitution(op_name)

    def config_json(self) -> dict:
        return {"ops": list(self._ops)}

    @classmethod
    def from_config(cls, rec: dict) -> "FusedKernelPass":
        ops = rec.get("ops")
        return cls(ops=tuple(ops) if ops is not None else None)


def fused_kernels_from_env(environ=None) -> Optional[FusedKernelPass]:
    """MX_PALLAS_FUSED: 'auto' (default) substitutes only where the
    kernels compile natively (TPU, and MXNET_USE_FUSION on); '1' forces
    the pass (interpret-mode kernels — the CPU test path); '0' pins the
    stock op implementations (the bitwise-parity path)."""
    environ = environ if environ is not None else os.environ
    raw = (environ.get("MX_PALLAS_FUSED") or "auto").strip().lower()
    if raw in ("0", "false", "off"):
        return None
    if raw in ("1", "true", "on"):
        return FusedKernelPass()
    if raw != "auto":
        raise MXNetError(
            f"MX_PALLAS_FUSED={raw!r}: expected auto, 1/on, or 0/off")
    from ..ops import pallas

    return FusedKernelPass() if (pallas.enabled() and pallas.use_compiled()) \
        else None


# ---------------------------------------------------------------------------
# pipeline factories
# ---------------------------------------------------------------------------
def pipeline_for_training(precision, environ=None) -> PassPipeline:
    """The pipeline ``DataParallelStep._build`` applies around the one
    traced step: the Plan's AMP policy (when set) then fused-kernel
    substitution (when MX_PALLAS_FUSED resolves on).  With neither, the
    pipeline is empty and ``wrap_apply`` is identity — the exact
    pre-pipeline program."""
    passes = []
    if precision is not None and precision.amp is not None:
        passes.append(AmpPass(precision.amp))
    fused = fused_kernels_from_env(environ)
    if fused is not None:
        passes.append(fused)
    return apply_env_toggles(PassPipeline(passes), environ)


def pipeline_for_serving(adapter, environ=None) -> PassPipeline:
    """The serving engine's pipeline: adapter-contributed passes (a
    quantized adapter exposes its quant pass via ``.passes``) then
    fused-kernel substitution.  The engine enters this scope around its
    traced decode/prefill bodies and feeds ``signature()`` into its
    fingerprint."""
    passes = list(getattr(adapter, "passes", ()) or ())
    fused = fused_kernels_from_env(environ)
    if fused is not None:
        passes.append(fused)
    return apply_env_toggles(PassPipeline(passes), environ)
