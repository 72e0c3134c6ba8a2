"""Fused-kernel registry: Pallas kernels keyed by op-class and platform.

The TPP argument (arXiv:2104.05755) applied to this repo's dispatch
layer: each entry maps a REGISTERED OP NAME (the op-class) to a Pallas
kernel with the same calling convention, tagged with the platforms it
may substitute on.  The ``fused_kernels`` graph pass
(passes/builtin.FusedKernelPass) consults :func:`substitution` from the
traced branch of ``ops/registry._invoke_impl`` and swaps the op's
FCompute in — so fusion is a PASS decision with a fingerprint, not an
if-ladder inside each op.

Platform resolution follows ``use_compiled()``'s single source of truth:
the ``compute_on`` override wins over the process default backend, and
kernels picked on a non-TPU platform run in interpret mode (the CPU test
path, forced by MX_PALLAS_FUSED=1).

Catalog: the existing fused kernels (layer_norm, flash_attention) plus
the new fused residual-add + LayerNorm block (``add_layer_norm``).
``paged_decode_attention`` stays engine-internal — it is not an op-class
(the serving engine composes it directly, gated by MX_SERVE_FLASH).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

__all__ = ["register_kernel", "registered_ops", "substitution",
           "KernelEntry"]


class KernelEntry:
    __slots__ = ("op_name", "platforms", "fn")

    def __init__(self, op_name: str, platforms: Tuple[str, ...],
                 fn: Callable):
        self.op_name = op_name
        self.platforms = tuple(platforms)
        self.fn = fn


_KERNELS: Dict[str, KernelEntry] = {}


def register_kernel(op_name: str, platforms: Tuple[str, ...] = ("cpu", "tpu")):
    """Decorator: register ``fn`` as the fused substitute for
    ``op_name`` on ``platforms``.  The fn must match the op's calling
    convention exactly (same positional arrays, same attrs) — the pass
    swaps it in blind."""

    def deco(fn: Callable) -> Callable:
        from ...base import MXNetError

        if op_name in _KERNELS:
            raise MXNetError(
                f"fused kernel for op {op_name!r} registered twice")
        _KERNELS[op_name] = KernelEntry(op_name, platforms, fn)
        return fn

    return deco


def registered_ops():
    return sorted(_KERNELS)


def substitution(op_name: str,
                 platform: Optional[str] = None) -> Optional[Callable]:
    """The kernel to substitute for ``op_name`` on ``platform`` (default:
    the platform the current trace targets), or None.  None too inside a
    TPU program that GSPMD partitions over several devices: a Mosaic call
    cannot be partitioned automatically, so the stock op stays."""
    from . import gspmd_partitioned, platform as traced_platform

    entry = _KERNELS.get(op_name)
    if entry is None:
        return None
    plat = platform if platform is not None else traced_platform()
    if plat not in entry.platforms or (plat == "tpu" and gspmd_partitioned()):
        return None
    return entry.fn


# ---------------------------------------------------------------------------
# catalog
# ---------------------------------------------------------------------------
@register_kernel("LayerNorm")
def _layer_norm_sub(data, gamma, beta, axis=-1, eps=1e-5,
                    output_mean_var=False):
    # the kernel is row-wise over the last axis; other attr combos keep
    # the stock implementation (which returns mean/var, handles any axis)
    if output_mean_var or axis not in (-1, data.ndim - 1) or data.ndim < 2:
        from ..registry import get_op

        return get_op("LayerNorm").fn(data, gamma, beta, axis=axis, eps=eps,
                                      output_mean_var=output_mean_var)
    from . import layer_norm

    out = layer_norm(data.reshape(-1, data.shape[-1]), gamma, beta, eps=eps)
    return out.reshape(data.shape)


@register_kernel("_contrib_add_layer_norm")
def _add_layer_norm_sub(data, residual, gamma, beta, eps=1e-5):
    from .fused import add_layer_norm

    c = data.shape[-1]
    out = add_layer_norm(data.reshape(-1, c), residual.reshape(-1, c),
                         gamma, beta, eps=eps)
    return out.reshape(data.shape)


@register_kernel("_contrib_flash_attention")
def _flash_attention_sub(q, k, v, causal=False, sm_scale=None):
    import math

    from ...parallel import ring_scope

    if ring_scope() is not None:
        # an active sequence-parallel scope owns attention routing —
        # defer to the stock op (ring/ulysses kernels over ppermute)
        from ..registry import get_op

        return get_op("_contrib_flash_attention").fn(
            q, k, v, causal=causal, sm_scale=sm_scale)
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    from . import flash_attention

    return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale)
