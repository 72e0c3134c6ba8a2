"""The unified parallelism ``Plan``: every strategy in this package as DATA.

Before this module, ``parallel/`` was a zoo of five hand-wired strategies —
dp (``data_parallel.py`` over a ``local_mesh``), tp (``sharding.py``
``ShardingRules``), pipeline (``pipeline.py`` + ``pp_microbatches``), ring
and Ulysses sequence parallelism (``ring.py``/``ulysses.py`` behind
``ring_attention=``) — each selected by a different constructor knob, each
growing its own gating logic inside ``DataParallelStep``.  A ``Plan``
captures everything those knobs expressed as one serializable value:

    mesh axis names/sizes  +  per-param PartitionSpec rules
    +  per-input batch/sequence axes  +  the SP attention mechanism
    +  pipeline microbatching  +  gradient-accumulation microbatching

``compile_step_with_plan`` (data_parallel.py) consumes ANY Plan through
the one dispatch body, so the async in-flight window, telemetry spans
and elastic resharding are written once, not five times.
The legacy strategy entry points remain as thin shims that BUILD the
equivalent Plan (``dp_plan``/``tensor_parallel_plan``/``pipeline_plan``/
``ring_plan``/``ulysses_plan`` here, re-exported by their home modules),
and ``parallel/planner.py`` chooses a Plan analytically from model shape
and mesh (docs/PERFORMANCE.md §Plan & planner).

Serialization: ``to_json``/``from_json`` round-trip losslessly —
``DataParallelStep.layout()`` embeds the Plan in the checkpoint
``meta.json`` ``layout`` block, so an elastic restore knows not just
WHERE each shard lived but WHICH strategy produced that placement
(docs/FAULT_TOLERANCE.md §Elastic resize).
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Sequence, Tuple

from ..base import MXNetError
from ..precision.config import PrecisionConfig
from .sharding import ShardingRules

__all__ = ["Plan", "dp_plan", "tensor_parallel_plan", "pipeline_plan",
           "ring_plan", "ulysses_plan", "STRATEGY_NAMES"]

# canonical mesh axis order (mesh.make_mesh): tp innermost = adjacent on
# the ICI ring, the bandwidth-optimal layout for TP collectives
_AXIS_ORDER = ("dp", "pp", "sp", "tp", "ep")

# MX_PLAN / shim strategy vocabulary (planner.plan_for resolves these)
STRATEGY_NAMES = ("auto", "dp", "tp", "pp", "sp", "ring", "ulysses")

# sequence-parallel attention mechanisms: 'gspmd' lets the compiler
# insert the K/V collectives, 'ring'/'ulysses' route fused-attention ops
# through the hand-written kernels (parallel/ring.py, parallel/ulysses.py)
_SP_MODES = ("gspmd", "ring", "ulysses")


@dataclass(frozen=True)
class Plan:
    """One parallelism layout, strategy-agnostic and serializable.

    ``mesh_axes``: ordered (name, size) pairs; the product is the device
    count the plan targets.  ``rules``: per-param PartitionSpec patterns
    (the tensor-parallel payload; empty = every param replicated).
    ``batch_axes``: mesh axes the input batch dim shards over.
    ``seq_axis``: None (auto-detect), 1 (force SP on dim 1) or -1
    (disable) — the per-input sequence-dim contract of
    ``DataParallelStep._input_shardings``.  ``sp_attention``: which
    mechanism services attention over a sequence-sharded axis.
    ``pp_microbatches``: GPipe microbatch count when a pp>1 axis is
    present.  ``accum_steps``: gradient-accumulation microbatching
    inside the compiled step.  ``predicted``: the planner's cost
    breakdown when this plan was chosen analytically (rides into the
    ``plan`` telemetry event; never part of equality/serial identity of
    the layout itself)."""

    mesh_axes: Tuple[Tuple[str, int], ...]
    rules: ShardingRules = field(default_factory=ShardingRules)
    batch_axes: Tuple[str, ...] = ("dp", "sp")
    seq_axis: Optional[int] = None
    sp_attention: str = "gspmd"
    pp_microbatches: int = 4
    accum_steps: int = 1
    # the precision story travels WITH the layout (docs/PRECISION.md):
    # an elastic restore must rebuild not just where each shard lived but
    # what dtype program produced the checkpointed values
    precision: Optional[PrecisionConfig] = None
    predicted: Optional[dict] = field(default=None, compare=False)

    def __post_init__(self):
        object.__setattr__(self, "mesh_axes",
                           tuple((str(n), int(s)) for n, s in self.mesh_axes))
        object.__setattr__(self, "batch_axes", tuple(self.batch_axes))
        self.validate()

    # -- validation ----------------------------------------------------
    def validate(self) -> None:
        names = [n for n, _ in self.mesh_axes]
        if len(set(names)) != len(names):
            raise MXNetError(f"Plan: duplicate mesh axes {names}")
        for n, s in self.mesh_axes:
            if s < 1:
                raise MXNetError(f"Plan: axis {n!r} has size {s} < 1")
        unknown = [a for a in self.batch_axes if a not in names]
        if unknown:
            raise MXNetError(
                f"Plan: batch_axes {unknown} not among mesh axes {names}")
        if self.seq_axis not in (None, 1, -1):
            raise MXNetError("Plan: seq_axis must be None (auto), 1 "
                             "(force SP on dim 1) or -1 (disable)")
        if self.sp_attention not in _SP_MODES:
            raise MXNetError(f"Plan: sp_attention must be one of "
                             f"{_SP_MODES}, got {self.sp_attention!r}")
        if self.pp_microbatches < 1:
            raise MXNetError(f"Plan: pp_microbatches must be >= 1, got "
                             f"{self.pp_microbatches}")
        if self.accum_steps < 1:
            raise MXNetError(f"Plan: accum_steps must be >= 1, got "
                             f"{self.accum_steps}")
        if self.sp_attention != "gspmd" and self.axis_size("sp") < 2 \
                and self.seq_axis != 1:
            # a ring/ulysses plan with no sp axis would silently run the
            # plain GSPMD path — a mis-built plan, not a preference
            raise MXNetError(
                f"Plan: sp_attention={self.sp_attention!r} needs an sp "
                f"axis > 1 (mesh: {dict(self.mesh_axes)})")

    # -- accessors -----------------------------------------------------
    def axis_size(self, name: str) -> int:
        for n, s in self.mesh_axes:
            if n == name:
                return s
        return 1

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.mesh_axes:
            n *= s
        return n

    @property
    def strategy(self) -> str:
        """Derived dominant-strategy label (telemetry/bench attribution;
        composite layouts join with '+', pure dp is 'dp')."""
        parts = []
        if self.axis_size("tp") > 1:
            parts.append("tp")
        if self.axis_size("pp") > 1:
            parts.append("pp")
        if self.axis_size("sp") > 1 or self.seq_axis == 1:
            parts.append(self.sp_attention if self.sp_attention != "gspmd"
                         else "sp")
        if self.axis_size("dp") > 1 or not parts:
            parts.insert(0, "dp")
        return "+".join(parts)

    def describe(self) -> str:
        mesh = "x".join(f"{n}{s}" for n, s in self.mesh_axes if s > 1) \
            or "1dev"
        return (f"Plan[{self.strategy}] {mesh} accum={self.accum_steps} "
                f"pp_micro={self.pp_microbatches}")

    # -- mesh / step construction --------------------------------------
    def build_mesh(self, devices=None):
        """A jax Mesh realizing this plan's axes (canonical axis order,
        tp innermost).  ``devices`` defaults to all local devices; their
        count must equal the plan's axis product."""
        from .mesh import device_mesh

        import jax

        if devices is None:
            devices = jax.devices()
        if len(devices) != self.n_devices:
            raise MXNetError(
                f"Plan covers {self.n_devices} devices "
                f"({dict(self.mesh_axes)}) but {len(devices)} were given")
        names = [n for n, _ in self.mesh_axes]
        sizes = [s for _, s in self.mesh_axes]
        return device_mesh(tuple(names), tuple(sizes), devices)

    def matches_mesh(self, mesh) -> bool:
        """Whether ``mesh`` realizes this plan.  Size-1 axes are
        placement-neutral (a dp8 plan runs fine on a plain ("dp",)
        local mesh), so only the non-trivial axes must agree — in
        order, since axis order is the device-to-position mapping."""
        mine = tuple((n, s) for n, s in self.mesh_axes if s > 1)
        theirs = tuple((n, int(s)) for n, s in mesh.shape.items() if s > 1)
        return mine == theirs

    # -- serialization (the meta.json `layout.plan` block) -------------
    def to_json(self) -> dict:
        return {
            "mesh_axes": [[n, s] for n, s in self.mesh_axes],
            "rules": self.rules.to_json(),
            "batch_axes": list(self.batch_axes),
            "seq_axis": self.seq_axis,
            "sp_attention": self.sp_attention,
            "pp_microbatches": self.pp_microbatches,
            "accum_steps": self.accum_steps,
            "precision": (self.precision.to_json()
                          if self.precision is not None else None),
            "strategy": self.strategy,  # derived; informational on disk
        }

    @classmethod
    def from_json(cls, rec: dict) -> "Plan":
        ba = rec.get("batch_axes")
        return cls(
            mesh_axes=tuple((n, int(s)) for n, s in rec["mesh_axes"]),
            rules=ShardingRules.from_json(rec.get("rules") or []),
            # an explicitly-empty batch_axes (a mesh with no dp/sp axes)
            # must round-trip as empty, not regrow the default
            batch_axes=tuple(ba) if ba is not None else ("dp", "sp"),
            seq_axis=rec.get("seq_axis"),
            sp_attention=rec.get("sp_attention", "gspmd"),
            pp_microbatches=int(rec.get("pp_microbatches", 4)),
            accum_steps=int(rec.get("accum_steps", 1)),
            precision=PrecisionConfig.from_json(rec.get("precision")),
        )

    def with_predicted(self, predicted: dict) -> "Plan":
        return replace(self, predicted=dict(predicted))


def _axes(dp: int, tp: int = 1, pp: int = 1, sp: int = 1,
          ep: int = 1) -> Tuple[Tuple[str, int], ...]:
    sizes = {"dp": dp, "pp": pp, "sp": sp, "tp": tp, "ep": ep}
    return tuple((n, int(sizes[n])) for n in _AXIS_ORDER)


def _resolve_dp(dp: int, n_devices: Optional[int], fixed: int) -> int:
    """dp=0 means "whatever is left" of ``n_devices`` (the make_mesh
    contract); explicit dp passes through."""
    if dp not in (0, None):
        return int(dp)
    if n_devices is None:
        import jax

        n_devices = len(jax.devices())
    if n_devices % fixed:
        raise MXNetError(
            f"{n_devices} devices not divisible by tp*pp*sp={fixed}")
    return n_devices // fixed


# ---------------------------------------------------------------------------
# the five legacy strategies as Plan producers (compat shims re-export
# these from their home modules: data_parallel/sharding/pipeline/ring/
# ulysses)
# ---------------------------------------------------------------------------
def dp_plan(dp: int = 0, n_devices: Optional[int] = None,
            accum_steps: int = 1) -> Plan:
    """Pure data parallelism — the ``KVStore('device')``/``local_mesh``
    strategy: batch sharded over every device, params replicated."""
    dp = _resolve_dp(dp, n_devices, 1)
    return Plan(mesh_axes=_axes(dp=dp), accum_steps=accum_steps)


def tensor_parallel_plan(rules: ShardingRules, tp: int, dp: int = 0,
                         n_devices: Optional[int] = None,
                         accum_steps: int = 1) -> Plan:
    """Tensor parallelism via per-param PartitionSpec rules (the
    ``sharding.ShardingRules`` strategy), composed with dp over the
    remaining devices."""
    if tp < 2:
        raise MXNetError(f"tensor_parallel_plan: tp must be >= 2, got {tp}")
    dp = _resolve_dp(dp, n_devices, tp)
    return Plan(mesh_axes=_axes(dp=dp, tp=tp), rules=rules,
                accum_steps=accum_steps)


def pipeline_plan(pp: int, microbatches: int = 4, dp: int = 0,
                  n_devices: Optional[int] = None,
                  rules: Optional[ShardingRules] = None,
                  accum_steps: int = 1) -> Plan:
    """GPipe pipeline parallelism over a pp axis (stacked-encoder models
    route through ``pipeline.pipeline_apply``), composed with dp."""
    if pp < 2:
        raise MXNetError(f"pipeline_plan: pp must be >= 2, got {pp}")
    dp = _resolve_dp(dp, n_devices, pp)
    return Plan(mesh_axes=_axes(dp=dp, pp=pp),
                rules=rules or ShardingRules(),
                pp_microbatches=microbatches, accum_steps=accum_steps)


def ring_plan(sp: int, dp: int = 0, n_devices: Optional[int] = None,
              rules: Optional[ShardingRules] = None,
              accum_steps: int = 1) -> Plan:
    """Ring-attention sequence parallelism: sequence dim sharded over
    sp, fused attention lowered to the ppermute K/V rotation."""
    if sp < 2:
        raise MXNetError(f"ring_plan: sp must be >= 2, got {sp}")
    dp = _resolve_dp(dp, n_devices, sp)
    return Plan(mesh_axes=_axes(dp=dp, sp=sp),
                rules=rules or ShardingRules(),
                sp_attention="ring", accum_steps=accum_steps)


def ulysses_plan(sp: int, dp: int = 0, n_devices: Optional[int] = None,
                 rules: Optional[ShardingRules] = None,
                 accum_steps: int = 1) -> Plan:
    """Ulysses sequence parallelism: one all-to-all reshards heads so
    attention runs locally over the full sequence."""
    if sp < 2:
        raise MXNetError(f"ulysses_plan: sp must be >= 2, got {sp}")
    dp = _resolve_dp(dp, n_devices, sp)
    return Plan(mesh_axes=_axes(dp=dp, sp=sp),
                rules=rules or ShardingRules(),
                sp_attention="ulysses", accum_steps=accum_steps)
