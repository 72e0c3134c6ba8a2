"""Sharding rules: parameter-name patterns -> PartitionSpec.

The reference's model parallelism is manual device placement (group2ctx ->
nnvm PlaceDevice pass); the TPU-native expression is a NamedSharding per
parameter over the mesh axes, with XLA inserting the collectives.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["ShardingRules", "replicated", "shard_batch",
           "tensor_parallel_plan"]


def _P(*args):
    from jax.sharding import PartitionSpec

    return PartitionSpec(*args)


class ShardingRules:
    """Ordered (regex, PartitionSpec) table applied to parameter names.

    Example (transformer TP over axis 'tp')::

        rules = ShardingRules([
            (r".*attention.*proj\\.weight", ("tp", None)),   # row-parallel
            (r".*(query|key|value)\\.weight", (None, "tp")), # col-parallel
            (r".*ffn_1\\.weight", (None, "tp")),
            (r".*ffn_2\\.weight", ("tp", None)),
        ])
    """

    def __init__(self, rules: Optional[Sequence[Tuple[str, Sequence]]] = None):
        self._rules = [(re.compile(pat), tuple(spec)) for pat, spec in (rules or [])]

    def to_json(self):
        """Lossless [[pattern, [spec...]], ...] form — how a Plan carries
        its per-param specs into the checkpoint ``layout`` block."""
        return [[pat.pattern,
                 [list(a) if isinstance(a, tuple) else a for a in spec]]
                for pat, spec in self._rules]

    @classmethod
    def from_json(cls, rec) -> "ShardingRules":
        return cls([(pat, tuple(tuple(a) if isinstance(a, list) else a
                                for a in spec)) for pat, spec in (rec or [])])

    def __eq__(self, other):
        return (isinstance(other, ShardingRules)
                and self.to_json() == other.to_json())

    def __hash__(self):
        # hash the same normalized form __eq__ compares (to_json turns
        # tuple entries into lists, so equal-by-eq instances — and
        # list-typed spec entries — hash consistently)
        return hash(repr(self.to_json()))

    def __bool__(self):
        return bool(self._rules)

    def spec_for(self, name: str, ndim: int):
        for pat, spec in self._rules:
            if pat.match(name):
                spec = tuple(spec)[:ndim]
                spec = spec + (None,) * (ndim - len(spec))
                return _P(*spec)
        return _P()  # replicated

    def shardings(self, mesh, named_shapes: Dict[str, Tuple[int, ...]]):
        from jax.sharding import NamedSharding

        return {
            name: NamedSharding(mesh, self.spec_for(name, len(shape)))
            for name, shape in named_shapes.items()
        }


def replicated(mesh):
    from jax.sharding import NamedSharding

    return NamedSharding(mesh, _P())


def shard_batch(mesh, axes=("dp",), ndim=2):
    """Sharding for a batch tensor: batch axis split over data axes."""
    from jax.sharding import NamedSharding

    axis = tuple(a for a in axes if a in mesh.axis_names)
    spec = (axis if len(axis) > 1 else (axis[0] if axis else None),)
    return NamedSharding(mesh, _P(*spec, *([None] * (ndim - 1))))


def tensor_parallel_plan(rules, tp, dp=0, n_devices=None, accum_steps=1):
    """Compat shim: the ShardingRules tensor-parallel strategy as a
    :class:`~mxnet_tpu.parallel.plan.Plan` (docs/PERFORMANCE.md §Plan &
    planner) — build the plan here, compile it through
    ``data_parallel.compile_step_with_plan``."""
    from .plan import tensor_parallel_plan as _tp

    return _tp(rules, tp, dp=dp, n_devices=n_devices,
               accum_steps=accum_steps)


def shard_batch_seq(mesh, ndim=2):
    """Sequence-parallel batch sharding: dim 0 over 'dp', dim 1 (sequence)
    over 'sp'.  Under pjit, GSPMD inserts the cross-device collectives the
    sequence-sharded activations need (attention over the T axis etc.) —
    the compiled analog of the reference-era all-to-all SP schemes."""
    from jax.sharding import NamedSharding

    assert ndim >= 2
    return NamedSharding(mesh, _P("dp", "sp", *([None] * (ndim - 2))))
