"""What a recomputed layer keeps from its forward pass.

``models/common.py`` ``checkpointed`` recomputes a layer in the backward pass
under ONE rule, ``policy``: a result that is dear to make again stays from
the forward pass, everything else is made again.  Three kinds are dear:

* a kernel's results that its own backward reads, passed through
  ``kernel_out`` where the kernel's ``custom_vjp`` forward rule returns them
  (flash attention's output and row sums: 73 ms of kernel time a GB kept,
  PERF.md section 6, PR 32);
* a kernel's results that a LATER kernel's backward reads, passed through
  ``kernel_out`` where the op that makes them returns (``dsa_ops.select``'s
  packed words, read by the backward of the attention under the selection
  and of the indexer's KL: 64 MiB a layer at 16,384 positions for 13.9 ms
  of scores, bisection and packing a layer on a TPU v5e, PERF.md section 6);
* a product of activations with a weight (a ``dot_general`` with no batch
  dimensions: 16 ms a GB for the widest).

Elementwise work, norms, convolutions, relayouts and anything a site does
not name are recomputed.  Outside a ``jax.checkpoint`` a name is an identity
and compiles to nothing.

The rule counts what it keeps while a gradient is traced (``tally``):
``parallel/data_parallel.py`` hands the count to
``telemetry.record_recompute_kept`` once a traced gradient.
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass
from typing import Optional

import jax
from jax.ad_checkpoint import checkpoint_name

__all__ = ["KERNEL_OUT", "kernel_out", "policy", "tally", "layer", "Kept"]

KERNEL_OUT = "mx_kernel_out"

_named = jax.checkpoint_policies.save_only_these_names(KERNEL_OUT)
_products = jax.checkpoint_policies.dots_with_no_batch_dims_saveable


@dataclass
class Kept:
    """Layers that kept something, and the tensors and bytes they kept."""
    layers: int = 0
    tensors: int = 0
    bytes: int = 0


_kept: ContextVar[Optional[Kept]] = ContextVar("recompute_kept", default=None)


@contextmanager
def tally():
    """Scope of one traced gradient: yields the ``Kept`` that ``policy``
    adds to while layers inside the scope are differentiated."""
    kept = Kept()
    token = _kept.set(kept)
    try:
        yield kept
    finally:
        _kept.reset(token)


@contextmanager
def layer():
    """Scope of one recomputed layer's call: the layer counts in the open
    tally if ``policy`` kept something of it."""
    kept = _kept.get()
    before = kept.tensors if kept is not None else 0
    yield
    if kept is not None and kept.tensors > before:
        kept.layers += 1


def kernel_out(*xs):
    """``xs`` named as results of a kernel that a recomputed layer keeps.
    Name ALL of what the kernel's backward and the layer's later ops read of
    it, or the kernel still runs a second time."""
    return tuple(checkpoint_name(x, KERNEL_OUT) for x in xs)


def policy(prim, *avals, **params) -> bool:
    """The ``jax.checkpoint`` policy of every recomputed layer (the module
    docstring); counts into the open ``tally``.  jax asks once for every
    operation whose operands the forward pass knows, and afterwards drops a
    kept value that no backward operation reads (a product that only feeds
    a residual add), so the count is an upper bound of what the step
    holds."""
    keep = _named(prim, *avals, **params) or _products(prim, *avals, **params)
    kept = _kept.get()
    if keep and kept is not None:
        outs, _ = prim.abstract_eval(*avals, **params)
        for out in outs if prim.multiple_results else (outs,):
            kept.tensors += 1
            kept.bytes += math.prod(out.shape) * out.dtype.itemsize
    return keep
