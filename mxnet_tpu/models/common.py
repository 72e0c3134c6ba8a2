"""What the layered language models (``nemotron_h.py``, ``zaya.py``) share:
per-layer recomputation, and the expert layer's held experts with their
load counters."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import telemetry
from ..gluon.block import HybridBlock
from ..gluon.parameter import record_aux_update
from ..ndarray import NDArray
from ..ops import moe_ops
from ..ops import recompute as _recompute
from ..ops import registry as _reg

__all__ = ["checkpointed", "vocab_logits", "HeldExperts"]


def checkpointed(block, *xs):
    """``block(*xs)`` with its activations recomputed in the backward pass
    (``jax.checkpoint`` around the call) while a jitted step is being
    traced; a plain call otherwise.  What is dear to make again stays from
    the forward pass, by the one rule of ``ops/recompute.py``: a kernel's
    named results and the products with a weight.  A layer's boundary is
    whatever tensors it takes and returns: one, or several as a list."""
    if not isinstance(xs[0]._data, jax.core.Tracer):
        return block(*xs)
    ctx = xs[0].context

    def pure(*arrays):
        out = block(*(NDArray(a, ctx=ctx) for a in arrays))
        if isinstance(out, (list, tuple)):
            return tuple(o._data for o in out)
        return out._data

    with _recompute.layer():
        out = jax.checkpoint(pure, policy=_recompute.policy)(
            *(x._data for x in xs))
    if isinstance(out, tuple):
        return [NDArray(o, ctx=ctx) for o in out]
    return NDArray(out, ctx=ctx)


def vocab_logits(h, weight):
    """``h`` (B, L, d) against the rows of ``weight`` (V, d): logits (B, L,
    V) accumulated and kept in f32, under the scope ``mx_head``."""
    with jax.named_scope("mx_head"):
        return _reg.invoke_fn(
            lambda a, w: jnp.einsum("bld,vd->blv", a, w,
                                    preferred_element_type=jnp.float32),
            [h, weight])


class HeldExperts(HybridBlock):
    """The experts a chip holds of a layer's ``n_experts``, ``experts_held =
    (first, count)`` (all of them when None), as stacked matrices for
    ``_contrib_moe_experts``: ``activation="relu2"`` gives each expert an up
    and a down matrix, ``"swiglu"`` a gate matrix as well.  The pairs that
    landed on each held expert in the last step, relative to an even spread
    over all experts, and their running maximum are aux state (``load``,
    ``load_max``), written the way BatchNorm writes its running statistics:
    no step syncs to read them, ``DataParallelStep.drain`` hands them to
    ``telemetry.record_moe_load``.  A subclass adds its router and calls
    ``routed``."""

    def __init__(self, units, n_experts, experts_held, top_k, expert_width,
                 activation, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        first, count = experts_held or (0, n_experts)
        if first < 0 or count < 1 or first + count > n_experts:
            raise ValueError(f"experts_held {experts_held} of {n_experts}")
        self._e, self._first, self._count = n_experts, first, count
        self._k, self._activation = top_k, activation
        with self.name_scope():
            if activation == "swiglu":
                self.gate_weight = self.params.get(
                    "experts_gate_weight", shape=(count, units, expert_width))
            self.up_weight = self.params.get(
                "experts_up_weight", shape=(count, units, expert_width))
            self.down_weight = self.params.get(
                "experts_down_weight", shape=(count, expert_width, units))
            self.load = self.params.get("load", shape=(count,), init="zeros",
                                        grad_req="null")
            self.load_max = self.params.get("load_max", shape=(count,),
                                            init="zeros", grad_req="null")
        # DataParallelStep.drain reads what carries this mark, and hands
        # the reading to ``on_reading`` where a leaf has one
        self.load.telemetry = self.load_max.telemetry = "moe_load"
        self.load.on_reading = self._record_rows_run
        self._tokens = None         # of the last trace

    def routed(self, F, flat, experts, weights, up_weight, down_weight, load,
               gate_weight=None):
        """(what the held experts add to the flat tokens, this step's
        ``load``)."""
        mats = (up_weight, down_weight) if gate_weight is None else (
            up_weight, down_weight, gate_weight)
        out, landed = F._contrib_moe_experts(
            flat, experts, weights, *mats, first=self._first,
            activation=self._activation)
        self._tokens = flat.shape[0]
        even = self._tokens * self._k / self._e      # pairs an expert gets
        return out, (landed.astype("float32") / even).astype(load.dtype)

    def _record_rows_run(self, name, load):
        """``rows_run_over_rows`` of the last step from its ``load`` (to that
        leaf's precision), for ``telemetry.record_rows_run``."""
        if self._tokens is None:
            return
        even = self._tokens * self._k / self._e
        ratio = moe_ops.rows_run_over_rows(
            [round(x * even) for x in load], self._tokens, self._k,
            telemetry.grouped_tiles())
        if ratio is not None:
            telemetry.record_rows_run(name, ratio)

    def record_load(self, load):
        """The aux write of this step's ``load`` (outside any recomputed
        region: an aux value must belong to the step's own trace)."""
        record_aux_update(self.load, load)
        record_aux_update(self.load_max, _reg.invoke_fn(
            jnp.maximum, [self.load_max.data(load.context), load]))
