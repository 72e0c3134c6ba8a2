"""Pallas TPU kernels for the hot fused ops.

Reference parity: this package is the TPU-native replacement for the
reference's hand-written device kernels and RTC fusion:
  * src/operator/contrib/transformer.cc (interleaved_matmul_selfatt_qk /
    valatt, ~L1-300) -> flash_attention (blockwise online-softmax attention,
    a strictly stronger fusion than the reference's matmul-only fusion);
  * src/operator/nn/softmax{-inl.h,.cc,.cu} fused softmax+CE grad ->
    softmax_cross_entropy;
  * src/operator/nn/layer_norm* -> layer_norm;
  * src/operator/fusion/fused_op.cc (NVRTC pointwise fusion, env
    MXNET_USE_FUSION ~L100) -> the `enabled()` gate below: XLA already
    fuses pointwise chains, so only the blockwise kernels live here.

All kernels run in interpret mode on CPU (so the test suite exercises them
on the 8-device virtual mesh) and compile through Mosaic on TPU.  Mosaic
calls cannot be partitioned by GSPMD, so on a TPU mesh of several devices
they are selected only inside a shard_map body, where code is per-device.
"""
from .flash_attention import flash_attention
from .fused import add_layer_norm, layer_norm, softmax_cross_entropy
from .paged_attention import paged_decode_attention

import os


def enabled() -> bool:
    """MXNET_USE_FUSION gate (default on), reference env-var semantics."""
    return os.environ.get("MXNET_USE_FUSION", "1") not in ("0", "false")


from contextlib import contextmanager
from contextvars import ContextVar

# per-context so concurrent steps on meshes of different platforms can't
# bake each other's interpret flag into a traced kernel
_platform_override: ContextVar = ContextVar("pallas_platform", default=None)
_partitioned: ContextVar = ContextVar("pallas_partitioned", default=False)


def platform() -> str:
    """The platform the computation being traced will run on: an explicit
    `compute_on(...)` override (set by DataParallelStep when jitting over a
    mesh) wins over the process default backend, so a CPU mesh under a TPU
    default backend correctly gets interpret mode."""
    import jax

    return _platform_override.get() or jax.default_backend()


def gspmd_partitioned() -> bool:
    """True when the code being traced is left to GSPMD to partition over
    several devices: the dispatching step said so (`compute_on(...,
    partitioned=True)`) and the trace is not inside a shard_map body."""
    import jax

    return (_partitioned.get()
            and not jax.sharding.get_abstract_mesh().are_all_axes_manual)


def use_compiled() -> bool:
    """True when Pallas kernels should lower through Mosaic: the
    computation runs on a TPU and is not left to GSPMD to partition.

    Single source of truth for call-site gates: a gate that checks
    `enabled() and use_compiled()` selects a kernel exactly where
    `interpret()` compiles it.
    """
    return platform() == "tpu" and not gspmd_partitioned()


def interpret() -> bool:
    """The interpret flag of a pallas_call traced now: interpreted off-TPU
    (the CPU test path), compiled by Mosaic on a TPU.  A kernel reached
    inside a GSPMD-partitioned TPU program would neither lower (Mosaic
    calls cannot be partitioned automatically) nor may it quietly run
    interpreted, so that is an error: the gates above do not select it
    there, and a direct call belongs in a shard_map."""
    if platform() != "tpu":
        return True
    if gspmd_partitioned():
        from ...base import MXNetError

        raise MXNetError(
            "a Pallas kernel was called inside a TPU program that GSPMD "
            "partitions over several devices; Mosaic calls cannot be "
            "partitioned automatically: call it inside jax.shard_map")
    return False


@contextmanager
def compute_on(platform: str, partitioned: bool = False):
    """Scope within which Pallas kernels lower for `platform` ('cpu'/'tpu').
    `partitioned` says that the jit being traced spans several devices and
    leaves the partitioning to GSPMD.

    Used at trace time (the interpret flag is baked into pallas_call when
    the enclosing jit traces)."""
    tokens = (_platform_override.set(platform),
              _partitioned.set(partitioned))
    try:
        yield
    finally:
        _platform_override.reset(tokens[0])
        _partitioned.reset(tokens[1])


__all__ = ["flash_attention", "softmax_cross_entropy", "layer_norm",
           "add_layer_norm", "paged_decode_attention", "enabled",
           "use_compiled", "interpret", "platform", "gspmd_partitioned",
           "compute_on", "registry"]

# the fused-kernel registry (op-class -> Pallas kernel, per platform);
# imported last: its catalog references the kernels above
from . import registry  # noqa: E402
