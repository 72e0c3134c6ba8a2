"""Fused distributed training step: forward + backward + optimizer in ONE
XLA program over a device mesh.

This is the performance path that replaces the reference's per-batch chain
of engine pushes (CachedOp forward -> backward -> kvstore push/reduce ->
optimizer kernels -> broadcast; SURVEY §3.3).  Here the whole chain is a
single jit: XLA overlaps the gradient reduce-scatter/all-reduce with the
backward pass over ICI and fuses the optimizer update into the gradient
buffers — strictly less launch overhead and less HBM traffic than the
eager path.

Works with any Gluon HybridBlock: its forward is traced into the step
function via the same parameter-substitution trace the CachedOp uses.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from .. import fault
from .. import memwatch
from .. import telemetry
from ..base import MXNetError
from .async_loss import AsyncLoss, InflightRing, compiled_step_window
from .plan import Plan, dp_plan
from .sharding import ShardingRules, replicated, shard_batch

__all__ = ["DataParallelStep", "make_train_step", "compile_step_with_plan",
           "dp_plan"]

# scope_map's compile names an option (at its default: the program is the
# same) so that jax looks the executable up anew instead of handing back the
# running one, whose metadata may be an older tree's (_scope_source)
_OWN_COMPILE = {"xla_dump_max_hlo_modules": -1}

def _global_put(arr, sharding):
    """device_put that also works on multi-process (multi-controller)
    meshes: every process passes the same host-global value and installs
    only its addressable shards (the pjit pod-input pattern; the
    reference's analog is each worker feeding its own data slice to its
    local executor)."""
    import jax

    if sharding.is_fully_addressable:
        return jax.device_put(arr, sharding)
    # mxlint: disable=hot-sync — materializes the host INPUT batch for
    # per-shard placement; never a readback of device compute
    host = np.asarray(arr)
    return jax.make_array_from_callback(
        host.shape, sharding, lambda idx: host[idx])


def _maybe_put(arr, sharding):
    """(placed_array, was_preplaced): skip the transfer when ``arr`` is
    already a device array carrying exactly the target sharding — the
    prefetcher/step handshake.  ``io.DevicePrefetchIter`` stages batches
    through ``DataParallelStep.stage()`` onto these same shardings from a
    background thread, and the step must not pay the H2D again."""
    if getattr(arr, "sharding", None) == sharding:
        return arr, True
    return _global_put(arr, sharding), False


def _shard_index_key(idx, shape) -> tuple:
    """Canonical hashable key for one shard's global index: a tuple of
    ``(start, stop)`` per dimension with the open-ended slices jax hands
    back (``slice(None)``) normalized against the array shape, so the
    same shard is the same key no matter which device reported it."""
    key = []
    for dim, s in enumerate(idx):
        start = 0 if s.start is None else int(s.start)
        stop = int(shape[dim]) if s.stop is None else int(s.stop)
        key.append((start, stop))
    return tuple(key)


def _local_shard_split(arr, rank: int, nprocs: int):
    """Split one (possibly sharded) array into its deduplicated shard
    set with a deterministic owner rank per shard — computed ENTIRELY
    from local metadata (``devices_indices_map`` enumerates every
    device's slice on every process), so all ranks derive the identical
    manifest without a single collective.

    Returns ``(shards, payloads)``: ``shards`` is the manifest entry
    (``[{"rank", "j", "slice"}]``, ordered by slice), ``payloads`` the
    ``[(j, ndarray)]`` this rank must persist (empty when it owns none).
    Replicas dedup to one owner: the minimal ``(process_index, id)``
    device holding the shard.  Process-local arrays (a single-device
    scalar every rank holds its own copy of — adam's ``t``) canonicalize
    to one rank-0 full-shape shard so the manifest stays rank-invariant."""
    shape = tuple(int(s) for s in np.shape(arr))
    full = tuple((0, int(s)) for s in shape)
    if nprocs > 1 and getattr(arr, "is_fully_addressable", True):
        shards = [{"rank": 0, "j": 0, "slice": [list(p) for p in full]}]
        if rank != 0:
            return shards, []
        import jax

        # mxlint: disable=hot-sync — checkpoint host snapshot
        return shards, [(0, np.asarray(jax.device_get(arr)))]
    if getattr(arr, "is_fully_replicated", False) or not hasattr(
            arr, "sharding"):
        shards = [{"rank": 0, "j": 0, "slice": [list(p) for p in full]}]
        if rank != 0:
            return shards, []
        if hasattr(arr, "addressable_shards"):
            # mxlint: disable=hot-sync — checkpoint host snapshot
            host = np.asarray(arr.addressable_shards[0].data)
        else:
            host = np.asarray(arr)
        return shards, [(0, host)]
    owners: Dict[tuple, tuple] = {}
    for dev, idx in arr.sharding.devices_indices_map(shape).items():
        key = _shard_index_key(idx, shape)
        cand = (int(dev.process_index), int(dev.id))
        if key not in owners or cand < owners[key]:
            owners[key] = cand
    local = {}
    for sh in arr.addressable_shards:
        local.setdefault(_shard_index_key(sh.index, shape), sh)
    shards, payloads = [], []
    counters: Dict[int, int] = {}
    for key in sorted(owners):
        owner_rank = owners[key][0]
        j = counters.get(owner_rank, 0)
        counters[owner_rank] = j + 1
        shards.append({"rank": owner_rank, "j": j,
                       "slice": [list(p) for p in key]})
        if owner_rank == rank:
            # mxlint: disable=hot-sync — checkpoint host snapshot
            payloads.append((j, np.asarray(local[key].data)))
    return shards, payloads


def _lazy_put(lazy, sharding):
    """Place a lazily-readable sharded-checkpoint value (anything with
    ``read_slice(idx) -> ndarray``) onto ``sharding`` WITHOUT ever
    composing the full array on this host: the callback reads exactly
    the slice each addressable device needs, straight out of the shard
    files that cover it — the N->M elastic restore path at TB scale."""
    import jax

    shape = tuple(int(s) for s in lazy.shape)
    return jax.make_array_from_callback(
        shape, sharding, lambda idx: lazy.read_slice(idx))


def _host_scalar(loss):
    """A replicated (possibly non-fully-addressable) loss -> host scalar
    array via this process's local shard."""
    if getattr(loss, "is_fully_addressable", True):
        return loss
    return np.asarray(loss.addressable_shards[0].data)


def _params_arrays(step):
    """memwatch provider: the sharded parameter buffers this step owns."""
    return list((step.params or {}).values())


def _opt_state_arrays(step):
    """memwatch provider: optimizer-state buffers (momenta/Adam moments)."""
    if step.opt_state is None:
        return ()
    import jax

    return jax.tree_util.tree_leaves(step.opt_state)


def _block_apply_fn(block, ctx, train: bool):
    """Build a pure fn(params_dict, key, *inputs) -> outputs from a Gluon
    block (same mechanism as gluon.block.CachedOp)."""
    from .. import autograd
    from .. import random as _random
    from ..gluon.parameter import begin_trace, end_trace
    from ..ndarray import NDArray

    param_items = list(block.collect_params().items())
    name_of = {p: name for name, p in param_items}

    def fn(param_arrays: Dict[str, Any], key, *input_arrays):
        param_map = {p: NDArray(param_arrays[name], ctx=ctx)
                     for name, p in param_items}
        nd_inputs = [NDArray(a, ctx=ctx) for a in input_arrays]
        prev_trace = begin_trace(param_map, ctx)
        prev_rec = autograd.set_recording(False)
        prev_train = autograd.set_training(train)
        prev_key = _random.set_trace_key_provider(_random._TraceKeyProvider(key))
        try:
            with block.trace_scope():
                out = block.forward(*nd_inputs)
        finally:
            state = end_trace(prev_trace)
            autograd.set_recording(prev_rec)
            autograd.set_training(prev_train)
            _random.set_trace_key_provider(prev_key)
        aux = [(name_of[p], v._data) for p, v in state["aux"]]
        if isinstance(out, (list, tuple)):
            return [o._data for o in out], aux
        return out._data, aux

    return fn, param_items


def _sgd_tree_update(params, grads, momenta, lr, momentum, wd, rescale, mults,
                     clip=None):
    import jax.numpy as jnp

    new_params, new_momenta = {}, {}
    for name, w in params.items():
        lr_mult, wd_mult = mults.get(name, (1.0, 1.0))
        if lr_mult is None:  # frozen (grad_req='null'): leave untouched
            new_params[name] = w
            new_momenta[name] = momenta[name]
            continue
        g = grads[name].astype(jnp.float32) * rescale
        if clip is not None:  # Optimizer.clip_gradient: after rescale, pre-wd
            g = jnp.clip(g, -clip, clip)
        g = g + wd * wd_mult * w.astype(jnp.float32)
        m = momentum * momenta[name] - lr * lr_mult * g
        new_params[name] = (w.astype(jnp.float32) + m).astype(w.dtype)
        new_momenta[name] = m
    return new_params, new_momenta


def _adam_tree_update(params, grads, state, lr, beta1, beta2, eps, wd, rescale,
                      mults, clip=None):
    import jax.numpy as jnp

    means, vars_, t = state
    t = t + 1
    corr = jnp.sqrt(1 - beta2**t) / (1 - beta1**t)
    new_p, new_m, new_v = {}, {}, {}
    for name, w in params.items():
        lr_mult, wd_mult = mults.get(name, (1.0, 1.0))
        if lr_mult is None:  # frozen
            new_p[name] = w
            new_m[name] = means[name]
            new_v[name] = vars_[name]
            continue
        g = grads[name].astype(jnp.float32) * rescale
        if clip is not None:
            g = jnp.clip(g, -clip, clip)
        g = g + wd * wd_mult * w.astype(jnp.float32)
        m = beta1 * means[name] + (1 - beta1) * g
        v = beta2 * vars_[name] + (1 - beta2) * jnp.square(g)
        new_p[name] = (w.astype(jnp.float32)
                       - lr * lr_mult * corr * m / (jnp.sqrt(v) + eps)).astype(w.dtype)
        new_m[name] = m
        new_v[name] = v
    return new_p, (new_m, new_v, t)


class DataParallelStep:
    """Compiled train step for a Gluon block over a mesh.

    Parameters live as sharded jax arrays owned by this object (master fp32
    optionally); sync_to_block() writes them back into the Gluon parameters.
    """

    _instance_counter = 0

    def __init__(self, block, loss_fn: Callable, mesh=None,
                 optimizer: str = "sgd", optimizer_params: Optional[Dict] = None,
                 rules: Optional[ShardingRules] = None,
                 batch_axes: Sequence[str] = ("dp", "sp"),
                 seq_axis: Optional[int] = None,
                 donate: bool = True, remat: bool = False,
                 ring_attention: bool = False, accum_steps: int = 1,
                 clip_global_norm: Optional[float] = None,
                 pp_microbatches: int = 4,
                 plan: Optional[Plan] = None,
                 precision=None):
        """seq_axis: which input dim is the sequence dim for sequence
        parallelism over an 'sp' mesh axis.  None (default) auto-detects:
        dim 1 is treated as the sequence dim only when it is divisible by
        the sp axis size; otherwise (e.g. NCHW/NHWC image batches) the
        batch dim is sharded over dp*sp as plain data parallelism.  Pass
        seq_axis=1 to force SP, seq_axis=-1 to disable it.

        remat: rematerialize the forward in the backward pass
        (jax.checkpoint over the block apply) — trades ~1 extra forward of
        FLOPs for not storing activations, the HBM lever for large
        per-chip batches (reference analog: MXNet memonger/mirror).

        ring_attention: with an active sp>1 axis, fused-attention ops in
        the model lower to a sequence-parallel kernel instead of GSPMD's
        K/V all-gather.  True/'ring': K/V rotate over ICI via ppermute
        (online softmax, per-device attention memory O((L/sp)^2)).
        'ulysses': one all-to-all reshards heads so attention runs
        locally over the full sequence (constant collective count; head
        count must divide by sp).

        clip_global_norm: clip the rescaled gradients to this global L2
        norm INSIDE the fused program (gluon.utils.clip_global_norm
        semantics, but compiled: one fused norm reduction over every
        trainable gradient, then one scalar scale).  Composable with the
        per-element Optimizer `clip_gradient` (optimizer_params), which
        applies after it, matching Trainer-then-optimizer order.

        pp_microbatches: GPipe microbatch count when the mesh has a pp>1
        axis.  Models built on a stacked encoder (models/bert_pp.py)
        consult the pipeline scope this step activates and route their
        layer stack through the compiled ppermute schedule; models
        without a stacked encoder simply ignore the scope (their pp-axis
        devices then duplicate dp work — shard params over pp via rules
        only with a pipeline-capable model).  pp currently composes with
        dp (batch dim); not with active sequence parallelism.

        accum_steps: gradient accumulation INSIDE the fused step — the
        batch is split into accum_steps contiguous microbatches, each
        forward/backward runs in turn (activation memory is one
        microbatch's), gradients average, then ONE optimizer update.
        Statically unrolled in the XLA program; combine with remat=True
        for maximum effective batch per chip (reference analog:
        grad_req='add' + delayed Trainer.step).

        precision: a :class:`~mxnet_tpu.precision.config.PrecisionConfig`
        — the graph-level AMP cast policy and/or traced dynamic loss
        scaling (docs/PRECISION.md).  Carried on the Plan (so it rides
        into checkpoint layouts and elastic restores); ``MX_AMP`` /
        ``MX_LOSS_SCALE`` provide the env default when neither the plan
        nor this kwarg sets one.  With no precision config, the built
        step program is byte-for-byte the pre-precision f32 program.

        plan: a :class:`~mxnet_tpu.parallel.plan.Plan` carrying ALL of
        the strategy knobs above (rules/batch_axes/seq_axis/
        ring_attention/accum_steps/pp_microbatches) as one value — the
        unified path ``compile_step_with_plan`` uses; the individual
        kwargs then must stay at their defaults.  Without a plan, this
        constructor is itself the dp-era compat shim: it builds the
        equivalent Plan from its kwargs, so every step — legacy or
        plan-built — flows through the same plan-driven dispatch."""
        import jax

        from ..context import current_context

        if plan is not None:
            clash = [kw for kw, val, dflt in (
                ("rules", rules, None),
                ("batch_axes", tuple(batch_axes), ("dp", "sp")),
                ("seq_axis", seq_axis, None),
                ("ring_attention", ring_attention, False),
                ("accum_steps", accum_steps, 1),
                ("pp_microbatches", pp_microbatches, 4),
                ("precision", precision, None),
            ) if val != dflt]
            if clash:
                raise MXNetError(
                    f"DataParallelStep: both plan= and strategy kwargs "
                    f"{clash} given — the Plan already carries them")
            if mesh is None:
                mesh = plan.build_mesh()
            elif not plan.matches_mesh(mesh):
                raise MXNetError(
                    f"Plan axes {dict(plan.mesh_axes)} do not match the "
                    f"given mesh {dict(mesh.shape)}")
        else:
            if mesh is None:
                from .mesh import local_mesh

                mesh = local_mesh()
            if ring_attention not in (True, False, "ring", "ulysses"):
                raise MXNetError("ring_attention must be bool, 'ring' or "
                                 f"'ulysses', got {ring_attention!r}")
            sp_mode = ("gspmd" if ring_attention is False
                       else "ring" if ring_attention is True
                       else ring_attention)
            if sp_mode != "gspmd" and dict(mesh.shape).get("sp", 1) < 2 \
                    and seq_axis != 1:
                # legacy tolerance: ring_attention on a mesh with no sp
                # axis was inert (the scope only activates with a
                # sequence-sharded input) — keep it inert, not an error
                sp_mode = "gspmd"
            plan = Plan(
                mesh_axes=tuple(mesh.shape.items()),
                rules=rules or ShardingRules(),
                # shard_batch ignores absent axes; the Plan is strict
                # about naming only real ones
                batch_axes=tuple(a for a in batch_axes
                                 if a in mesh.axis_names),
                seq_axis=seq_axis,
                sp_attention=sp_mode,
                pp_microbatches=int(pp_microbatches),
                accum_steps=int(accum_steps),
                precision=precision)
        if plan.precision is None:
            # env default (MX_AMP / MX_AMP_POLICY / MX_LOSS_SCALE), read
            # ONCE here: the resolved config becomes part of the Plan —
            # and therefore of checkpoint layouts and executable
            # fingerprints — so a mid-run env flip cannot silently split
            # the program from its recorded identity
            from dataclasses import replace as _dc_replace

            from ..precision.config import PrecisionConfig

            env_precision = PrecisionConfig.from_env()
            if env_precision is not None:
                plan = _dc_replace(plan, precision=env_precision)
        self.plan = plan
        self._precision = plan.precision
        self._loss_scale_cfg = (plan.precision.loss_scale
                                if plan.precision is not None else None)
        # the training pass pipeline (passes/builtin): the Plan's AMP
        # policy + fused-kernel substitution (MX_PALLAS_FUSED), subject
        # to MX_PASSES toggles.  _build wraps the block apply with it,
        # and its ONE signature joins the executable fingerprint below.
        from ..passes.builtin import pipeline_for_training

        self._pipeline = pipeline_for_training(plan.precision)
        self.mesh = mesh
        self.block = block
        self.loss_fn = loss_fn
        opt_params = dict(optimizer_params or {})
        self._lr = opt_params.get("learning_rate", 0.01)
        # lr is a DEVICE SCALAR ARGUMENT of the compiled step (not a trace
        # constant), so schedules/manual set_learning_rate never retrace
        self._lr_scheduler = opt_params.get("lr_scheduler")
        if self._lr_scheduler is not None:
            self._lr_scheduler.base_lr = self._lr
        self._clip_gradient = opt_params.get("clip_gradient")
        self._clip_global = clip_global_norm
        self._momentum = opt_params.get("momentum", 0.9)
        self._wd = opt_params.get("wd", 0.0)
        self._beta1 = opt_params.get("beta1", 0.9)
        self._beta2 = opt_params.get("beta2", 0.999)
        self._eps = opt_params.get("epsilon", 1e-8)
        self._rescale = opt_params.get("rescale_grad", 1.0)
        self._optimizer = optimizer
        self._donate = donate
        self._remat = remat

        ctx = current_context()
        self._ctx = ctx
        self._apply, self._param_items = _block_apply_fn(block, ctx, train=True)
        # frozen params (grad_req='null') are marked with lr_mult=None and
        # skipped by the tree updates; others carry their lr/wd multipliers
        self._mults = {
            n: ((None, None) if p.grad_req == "null"
                else (p.lr_mult, p.wd_mult))
            for n, p in self._param_items
        }

        # aux leaves a block marks for telemetry (an expert layer's load, a
        # second head's loss term): read at drain, never inside a step
        self._aux_reading_kinds = {
            n: p.telemetry for n, p in self._param_items
            if getattr(p, "telemetry", None)}
        # what a marked leaf's block derives from the same reading
        self._aux_readers = {
            n: p.on_reading for n, p in self._param_items
            if n in self._aux_reading_kinds and hasattr(p, "on_reading")}

        if optimizer not in ("sgd", "adam"):
            raise MXNetError(f"fused step supports sgd/adam, got {optimizer}")
        # per-instance telemetry key: two fused steps over same-class
        # blocks must not pool retrace signatures (false-storm warnings)
        DataParallelStep._instance_counter += 1
        self._tele_name = (f"DataParallelStep:{type(block).__name__}"
                           f"#{DataParallelStep._instance_counter}")
        self.params = None
        self.opt_state = None
        # traced loss-scale state (docs/PRECISION.md): replicated device
        # scalars {scale, growth, skipped} threaded through the jitted
        # step; None when the plan carries no loss-scale config
        self.scaler_state = None
        self._shardings = None
        self._jitted = None
        self._step_count = 0
        # bounded async dispatch window (MX_ASYNC_INFLIGHT handles pending
        # at once); the device prefetcher's staging thread and step() may
        # both trigger first-use state init, hence the lock
        self._inflight = InflightRing(self._tele_name)
        self._state_lock = threading.Lock()
        # deferred compile record: _step_impl (the hot path — which must
        # never run memory/analysis APIs, mxlint hot-sync) stamps what it
        # knows at the traced call; step() hands it to memwatch after
        self._pending_compile: Optional[Dict[str, Any]] = None
        # scope_map(): the batch side of the traced call's signature (shape
        # mirrors, stamped with it) and the map once somebody asked
        self._scope_args = None
        self._scope_map: Optional[Dict[str, dict]] = None
        # compiled allgather for state_dict's sharded->host baseline,
        # built lazily once per step object
        self._gather_jit = None
        # live-array census attribution (docs/OBSERVABILITY.md §Memory):
        # weak registration — the watchdog never keeps this step alive
        memwatch.register("params", self, _params_arrays)
        memwatch.register("optimizer", self, _opt_state_arrays)

    def _ensure_state(self, example_inputs):
        """Gather params (resolving deferred init via one eager forward) and
        shard them per the rules.  Thread-safe: a DevicePrefetchIter's
        background stage() may race the first step() here."""
        import jax

        if self.params is not None:
            return
        with self._state_lock:
            if self.params is not None:
                return
            from .. import autograd
            from ..gluon.parameter import DeferredInitializationError

            try:
                for _, p in self._param_items:
                    p.data()
            except DeferredInitializationError:
                with autograd.pause(train_mode=True):
                    self.block(*example_inputs)
            names = [n for n, _ in self._param_items]
            shapes = {n: tuple(p.data().shape) for n, p in self._param_items}
            self._shardings = self.plan.rules.shardings(self.mesh, shapes)
            # a COPY of the block's parameters: the step donates its
            # params from step 1 on, and device_put hands back the source
            # buffer (as the whole array on one device, as the source
            # device's shard on a mesh, whatever may_alias says), which
            # would leave the Gluon Parameter holding a deleted array
            params = {
                n: _global_put(jax.numpy.copy(p.data()._data),
                               self._shardings[n])
                for n, p in self._param_items
            }
            if self._donate and next(
                    iter(self.mesh.devices.flat)).platform != "cpu":
                # where the step donates, the block is a stale copy from
                # step 1 on and nothing writes its gradient buffers: free
                # them (a model's worth of device memory) until
                # sync_to_block() hands the state back
                for _, p in self._param_items:
                    p.release_grad()
            if self._optimizer == "sgd":
                self.opt_state = {
                    n: _global_put(np.zeros(shapes[n], np.float32),
                                   self._shardings[n])
                    for n in names
                }
            else:
                z = {n: _global_put(np.zeros(shapes[n], np.float32),
                                    self._shardings[n]) for n in names}
                z2 = {n: _global_put(np.zeros(shapes[n], np.float32),
                                     self._shardings[n]) for n in names}
                # on the mesh like every other leaf: an off-mesh counter
                # comes back mesh-typed from step 1, and step 2 would
                # retrace and recompile the whole program
                self.opt_state = (z, z2, _global_put(
                    np.zeros((), np.int32), replicated(self.mesh)))
            if self._loss_scale_cfg is not None and \
                    self.scaler_state is None:
                from ..precision import loss_scale as _ls

                repl = replicated(self.mesh)
                self.scaler_state = {
                    k: _global_put(v, repl)
                    for k, v in _ls.init_scaler_host(
                        self._loss_scale_cfg).items()
                }
            # publish params LAST: it is the unlocked fast-path check
            self.params = params

    # ------------------------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp

        from jax.sharding import NamedSharding, PartitionSpec

        from ..ops import recompute as _recompute

        apply_fn = self._apply
        if self._remat:
            # jax.checkpoint only accepts JAX-typed outputs: strip the
            # static aux NAMES (strings) out of the rematerialized region
            # and re-pair them outside — they're trace-stable for a block
            base, names_cell = apply_fn, []

            def _arrays_only(params, key, *xs):
                out, aux = base(params, key, *xs)
                if not names_cell:
                    names_cell.append([n for n, _ in aux])
                return out, [v for _, v in aux]

            ck = jax.checkpoint(_arrays_only)

            def apply_fn(params, key, *xs):
                out, vals = ck(params, key, *xs)
                return out, list(zip(names_cell[0], vals))
        # the pass pipeline wraps the block apply (docs/PRECISION.md
        # §Pass pipeline): AMP's policy scope is active during THIS
        # trace only, so the whole mixed-precision program lands in the
        # one compiled executable (outputs widen to f32 at the
        # boundary); fused-kernel substitution swaps Pallas kernels at
        # the dispatch point.  An empty pipeline returns apply_fn
        # itself — the bitwise pre-pipeline program.
        apply_fn = self._pipeline.wrap_apply(apply_fn)
        loss_fn = self.loss_fn
        opt = self._optimizer
        momentum, wd, rescale = self._momentum, self._wd, self._rescale
        beta1, beta2, eps = self._beta1, self._beta2, self._eps
        clip_elem, clip_global = self._clip_gradient, self._clip_global
        mults = self._mults

        ctx = self._ctx

        def loss_of(params, key, data, label):
            from ..ndarray import NDArray

            out, aux = apply_fn(params, key, *data)  # data: tuple of arrays
            out_nd = (NDArray(out, ctx=ctx) if not isinstance(out, list)
                      else [NDArray(o, ctx=ctx) for o in out])
            with jax.named_scope("mx_loss"):
                loss = loss_fn(out_nd, NDArray(label, ctx=ctx))
                larr = loss._data if isinstance(loss, NDArray) else loss
                return jnp.mean(larr.astype(jnp.float32)), aux

        accum = self.plan.accum_steps
        ls_cfg = self._loss_scale_cfg

        def _update_core(params, opt_state, key, lr, data, label, scale):
            """ONE copy of the grad/accum/clip/optimizer body shared by
            ``step`` and ``scaled_step``.  ``scale=None`` is the plain
            f32 program — no scaling op is emitted, so the unscaled
            trace stays byte-identical to the pre-AMP step (pinned by
            the AMP-off bitwise test).  A device ``scale`` folds the
            loss multiply in before value_and_grad and the un-scale into
            the optimizer's rescale multiply (zero extra HBM passes over
            the gradient buffers).  Returns grads too, for the caller's
            overflow check."""
            if scale is None:
                vg_target = loss_of
            else:
                def vg_target(params, key, data, label):
                    loss, aux = loss_of(params, key, data, label)
                    return loss * scale, (loss, aux)

            def run_vg(p, k, d, l):
                with _recompute.tally() as kept:
                    out, grads = jax.value_and_grad(
                        vg_target, has_aux=True)(p, k, d, l)
                if kept.layers:     # trace time: once a traced gradient
                    telemetry.record_recompute_kept(
                        kept.layers, kept.tensors, kept.bytes)
                loss, aux = out if scale is None else out[1]
                return loss, aux, grads

            if accum == 1:
                loss, aux, grads = run_vg(params, key, data, label)
            else:
                # statically-unrolled microbatch loop.  STRIDED slices
                # (rows i::accum): each microbatch draws an equal share of
                # every device's dp shard, so no per-microbatch resharding
                # collective and no idle devices (a contiguous B/accum
                # block would live on only dp/accum of the devices)
                keys = jax.random.split(key, accum)
                grads, loss, aux_sums = None, 0.0, {}
                for i in range(accum):
                    def mb(a, _i=i):
                        return a[_i::accum]
                    l_i, aux, g_i = run_vg(
                        params, keys[i], tuple(mb(a) for a in data),
                        mb(label))
                    loss = loss + l_i / accum
                    # aux (BN batch stats) averages over ALL microbatches,
                    # keeping the "global batch average" contract below
                    for name, val in aux:
                        prev = aux_sums.get(name)
                        aux_sums[name] = val if prev is None else prev + val
                    grads = (g_i if grads is None else jax.tree_util.tree_map(
                        lambda a, b: a + b, grads, g_i))
                grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
                aux = [(n, v / accum) for n, v in aux_sums.items()]
            with jax.named_scope("mx_update"):
                base_rescale = rescale if scale is None else rescale / scale
                eff_rescale = base_rescale
                if clip_global is not None:
                    # ONE fused global-norm reduction over the rescaled grads
                    # of the trainable params, folded into the per-param
                    # rescale
                    sq = sum(
                        jnp.sum(jnp.square(grads[n].astype(jnp.float32)
                                           * base_rescale))
                        for n in grads
                        if mults.get(n, (1.0, 1.0))[0] is not None)
                    gnorm = jnp.sqrt(sq)
                    eff_rescale = base_rescale * jnp.minimum(
                        1.0, clip_global / (gnorm + 1e-12))
                if opt == "sgd":
                    new_params, new_state = _sgd_tree_update(
                        params, grads, opt_state, lr, momentum, wd,
                        eff_rescale, mults, clip_elem)
                else:
                    new_params, new_state = _adam_tree_update(
                        params, grads, opt_state, lr, beta1, beta2, eps, wd,
                        eff_rescale, mults, clip_elem)
            # aux (BN stats): already averaged over the global batch by XLA
            for name, val in aux:
                new_params[name] = val.astype(new_params[name].dtype)
            return new_params, new_state, loss, grads

        def step(params, opt_state, key, lr, data, label):
            new_params, new_state, loss, _grads = _update_core(
                params, opt_state, key, lr, data, label, None)
            return new_params, new_state, loss

        def scaled_step(params, opt_state, scaler, key, lr, data, label):
            """The loss-scaled twin of ``step`` (docs/PRECISION.md):
            same ``_update_core`` with the scale folded in, overflow
            detection is one fused isfinite reduce, and a non-finite
            step SELECTS the old params/opt_state — a traced no-op
            update.  The scaler state machine transitions as device
            values; no host readback ever enters this body."""
            from ..precision import loss_scale as _ls

            new_params, new_state, loss, grads = _update_core(
                params, opt_state, key, lr, data, label, scaler["scale"])
            # skip-step selection: weights, momenta, Adam's t AND the
            # forward's aux stats all hold when any grad is non-finite
            def hold(new, old):
                return jax.tree_util.tree_map(
                    lambda a, b: jnp.where(finite, a, b), new, old)

            with jax.named_scope("mx_update"):
                finite = _ls.grads_finite(grads, mults)
                new_params = hold(new_params, params)
                new_state = hold(new_state, opt_state)
                new_scaler = _ls.scaler_update(scaler, finite, ls_cfg)
            return new_params, new_state, new_scaler, loss

        repl = replicated(self.mesh)
        # XLA:CPU's runtime aliasing check rejects a donated param whose
        # incoming layout/sharding differs from its out_sharding
        # ("INTERNAL: Expected aliased input ... to have the same size",
        # seen on dp×tp CPU meshes).  Donation only saves device memory,
        # so keep it for accelerators and skip it on CPU hosts.
        mesh_platform = next(iter(self.mesh.devices.flat)).platform
        donate = (0, 1) if (self._donate and mesh_platform != "cpu") else ()
        # built ONCE per step object (guarded by `self._jitted is None`
        # in _step_impl); ls_cfg is construction-time state, so exactly
        # one of the two programs ever exists per step object
        if ls_cfg is None:
            # mxlint: disable=retrace-hazard — built once per step object
            self._jitted = jax.jit(
                step,
                out_shardings=(self._shardings, None, repl),
                donate_argnums=donate,
            )
        else:
            # mxlint: disable=retrace-hazard — built once per step object
            self._jitted = jax.jit(
                scaled_step,
                out_shardings=(self._shardings, None, None, repl),
                donate_argnums=donate,
            )

    # ------------------------------------------------------------------
    def _input_shardings(self, data_arrs, label_arr):
        """Per-input shardings for one batch -> (data_shardings,
        label_sharding, sp_active).  Shared by step() and the prefetcher's
        stage() so both place inputs identically (the handshake contract).

        With an active 'sp' axis, the sequence dim (1) shards over it:
        true sequence parallelism — GSPMD emits the cross-device
        collectives for attention over the sharded T axis.  Gated (r3
        advisor): only when the caller opted in via seq_axis=1, or in auto
        mode when dim 1 is actually divisible by the sp size — image
        batches (NCHW: dim 1 = 3 channels) fall back to plain dp*sp batch
        sharding."""
        sp_active = (
            "sp" in self.mesh.axis_names
            and self.mesh.shape["sp"] > 1
            and "sp" in self.plan.batch_axes
            and self.plan.seq_axis != -1
            and any(np.ndim(a) >= 2 for a in data_arrs)
        )
        if sp_active and self.plan.seq_axis is None:
            sp_active = all(np.shape(a)[1] % self.mesh.shape["sp"] == 0
                            for a in data_arrs if np.ndim(a) >= 2)
        if self.plan.seq_axis == 1 and sp_active:
            # explicit SP opt-in: a non-divisible seq dim is a caller error,
            # not something to silently decline (the ring scope and the
            # shard specs must agree on what was sequence-sharded)
            bad = [np.shape(a) for a in data_arrs
                   if np.ndim(a) >= 2
                   and np.shape(a)[1] % self.mesh.shape["sp"] != 0]
            if bad:
                raise MXNetError(
                    f"seq_axis=1: sequence dims of {bad} are not divisible "
                    f"by sp={self.mesh.shape['sp']}")

        def _shard_one(arr):
            if (sp_active and np.ndim(arr) >= 2
                    and np.shape(arr)[1] % self.mesh.shape["sp"] == 0):
                from .sharding import shard_batch_seq

                return shard_batch_seq(self.mesh, np.ndim(arr))
            if sp_active:  # rank-1 (or ragged) input under SP: dp only
                return shard_batch(self.mesh, ("dp",), np.ndim(arr))
            return shard_batch(self.mesh, self.plan.batch_axes, np.ndim(arr))

        return (tuple(_shard_one(a) for a in data_arrs),
                _shard_one(label_arr), sp_active)

    def stage(self, data, label):
        """Pre-place one batch onto this step's input shardings (the
        device-side prefetch half of the pipeline) -> (data_tuple, label)
        of device-backed NDArrays.  Called from ``io.DevicePrefetchIter``'s
        background thread while the current step computes; a later
        ``step()`` recognizes the placement and skips its own transfer.
        Values are bit-identical either way — staging only moves WHEN the
        H2D copy happens."""
        from ..ndarray import NDArray

        datas = tuple(data) if isinstance(data, (tuple, list)) else (data,)
        datas = tuple(d if isinstance(d, NDArray)
                      else NDArray(d, ctx=self._ctx) for d in datas)
        self._ensure_state(datas)
        data_arrs = tuple(d._data for d in datas)
        label_arr = (label._data if isinstance(label, NDArray) else label)
        data_sh, label_sh, _sp = self._input_shardings(data_arrs, label_arr)
        staged = tuple(
            NDArray(_maybe_put(a, s)[0], ctx=self._ctx)
            for a, s in zip(data_arrs, data_sh))
        staged_label = (None if label is None else
                        NDArray(_maybe_put(label_arr, label_sh)[0],
                                ctx=self._ctx))
        return staged, staged_label

    def step(self, data, label):
        """One fused training step; returns a lazy :class:`AsyncLoss`.

        Dispatch is non-blocking (jax queues the execution): the handle's
        ``float()`` / ``.asnumpy()`` / ``.wait()`` force the host readback,
        so compute for step N overlaps host prep for step N+1.  At most
        ``MX_ASYNC_INFLIGHT`` steps may be pending (unset: 2, growing to at
        most 8 while a step ends under 1.25 s after its dispatch) — admitting
        one more blocks on the oldest first; ``MX_ASYNC_INFLIGHT=0``
        forces every step at dispatch (the old synchronous behavior, same
        numbers: asynchrony never changes what is computed).

        `data` may be a single NDArray or a tuple/list of NDArrays for
        multi-input blocks (e.g. the seq2seq Transformer's (src, tgt)).

        With telemetry spans on (the recorder, or a running jax.profiler
        trace: docs/OBSERVABILITY.md §Tracing), the whole call is a
        ``train_step`` span with ``block_wait`` / ``input_stage`` /
        ``step_prep`` / ``dispatch`` sub-spans — the per-phase timing
        ``tools/trace_report.py`` aggregates into the gang-wide step
        breakdown, and ``mx:<name>`` events in the profiler's trace.
        Spans observe only; the computation is bitwise identical with
        ``MX_TELEMETRY_SPANS=0``."""
        with telemetry.span("train_step", executor=self._tele_name,
                            step_num=self._step_count + 1):
            handle = self._step_impl(data, label)
            self._book_pending_compile()
            memwatch.on_step(self._step_count)
        return handle

    def _book_pending_compile(self) -> None:
        """Land the deferred compile record stamped by the hot dispatch
        body — HERE, outside it: note_compile may retrace for cost
        analysis, which is a once-per-executable fact, not a per-step
        one."""
        pend, self._pending_compile = self._pending_compile, None
        if pend is None:
            return
        memwatch.note_compile(self._tele_name, pend["parts"],
                              pend["wall_s"], site="data_parallel",
                              jitted=self._jitted, args=pend["args"])

    def _step_impl(self, data, label):
        import jax

        from .. import random as _random
        from ..ndarray import NDArray

        t0 = time.perf_counter()
        datas = tuple(data) if isinstance(data, (tuple, list)) else (data,)
        datas = tuple(d if isinstance(d, NDArray) else NDArray(d, ctx=self._ctx)
                      for d in datas)
        # retrace detection: jit specializes on input shapes/dtypes, so a
        # new signature on an already-built step means XLA recompiles —
        # report it (telemetry warns after the limit) and tag this step's
        # wall time as compile, not steady-state execute.
        name = self._tele_name
        if telemetry.retrace_enabled():
            traced = telemetry.note_signature(
                name, self._sig_of(datas, label))
        else:  # detection off: still split the first-call compile out
            traced = self._jitted is None
        if self.plan.accum_steps > 1:
            label_dim0 = (label.shape[0] if hasattr(label, "shape") else
                          np.shape(label)[0])
            for dim0 in [d.shape[0] for d in datas] + [label_dim0]:
                if dim0 % self.plan.accum_steps:
                    raise MXNetError(
                        f"batch {dim0} not divisible by "
                        f"accum_steps={self.plan.accum_steps}")
        self._ensure_state(datas)
        if self._jitted is None:
            self._build()
        # bounded window: block on the OLDEST pending step only when the
        # ring is full, BEFORE paying this batch's placement — the
        # remaining in-flight steps keep the device busy meanwhile
        limit, deep = compiled_step_window()
        block_wait_s = 0.0
        if limit > 0:
            # a live span, open while the host waits.  wait_span=False:
            # this IS the step's wait; the inner wait emitting loss_wait
            # over the same wall would double-count the phase breakdown
            with telemetry.span("block_wait"):
                block_wait_s = self._inflight.make_room(
                    limit, wait_span=False, deep=deep)
        with telemetry.span("input_stage"):
            data_arrs = tuple(d._data for d in datas)
            label_arr = label._data if isinstance(label, NDArray) else label
            data_sh, label_sh, sp_active = self._input_shardings(
                data_arrs, label_arr)
            overlapped = 0
            placed = []
            for a, s in zip(data_arrs, data_sh):
                arr, pre = _maybe_put(a, s)
                placed.append(arr)
                if pre:
                    overlapped += int(getattr(arr, "nbytes", 0))
            data_arrs = tuple(placed)
            label_arr, pre = _maybe_put(label_arr, label_sh)
            if pre:
                overlapped += int(getattr(label_arr, "nbytes", 0))
        # the key draw is an eager device dispatch of its own every step:
        # under its own span, train_step's self time is Python bookkeeping
        with telemetry.span("step_prep"):
            key = _random.next_key()
            lr_val = np.float32(self._current_lr(self._step_count + 1))
        with telemetry.span("dispatch", step=self._step_count + 1,
                            traced=traced):
            scaled = self.scaler_state is not None
            call_args = ((self.params, self.opt_state, self.scaler_state,
                          key, lr_val, data_arrs, label_arr) if scaled
                         else (self.params, self.opt_state, key, lr_val,
                               data_arrs, label_arr))
            outs = self._plan_dispatch(call_args, self._step_count + 1,
                                       sp_active)
            if scaled:
                (self.params, self.opt_state, self.scaler_state,
                 loss) = outs
            else:
                self.params, self.opt_state, loss = outs
        if traced:
            self._scope_args = (memwatch.shape_structs(
                (key, lr_val, data_arrs, label_arr)), sp_active)
            self._scope_map = None
        if traced and telemetry.enabled():
            # what step() needs to book the compile once the hot body is
            # done: structural fingerprint parts + arg shape mirrors
            # (metadata only — the placed buffers are not kept alive)
            self._pending_compile = {
                "parts": self._fingerprint_parts(
                    self._sig_of(data_arrs, label_arr)),
                "wall_s": time.perf_counter() - t0,
                "args": memwatch.shape_structs(
                    (self.params, self.opt_state, key, lr_val,
                     data_arrs, label_arr)),
            }
        self._step_count += 1
        handle = AsyncLoss(loss, step=self._step_count, executor=name,
                           ring=self._inflight, host_fn=_host_scalar)
        depth = self._inflight.admit(handle) if limit > 0 else 0
        if telemetry.enabled():
            samples = int(np.shape(label_arr)[0]) if np.ndim(label_arr) else 1
            xfer = sum(int(getattr(a, "nbytes", 0))
                       for a in data_arrs + (label_arr,))
            telemetry.record_step(name, step=self._step_count,
                                  wall_s=time.perf_counter() - t0,
                                  samples=samples, transfer_bytes=xfer,
                                  traced=traced, h2d_overlapped=overlapped,
                                  inflight_depth=depth,
                                  block_wait_ms=round(block_wait_s * 1e3, 3))
            # (no record_block_wait here: make_room's internal wait()
            # already recorded the blocked time — recording the returned
            # duration again would double the rollup)
            # heartbeat advances at DISPATCH, not readback: a supervisor
            # watching a deeply pipelined rank must see it making progress
            telemetry.heartbeat(self._step_count)
        if limit == 0:
            handle.wait()  # synchronous mode: errors surface right here
        return handle

    # ------------------------------------------------------------------
    # signature/fingerprint/scope helpers
    # ------------------------------------------------------------------
    @staticmethod
    def _sig_of(arrs, label):
        """Canonical (shapes, dtypes) signature of one batch — keys the
        retrace detector and the restart-stable fingerprint.  Accepts
        NDArrays or raw arrays."""
        def one(a):
            data = getattr(a, "_data", a)
            return (tuple(np.shape(data)),
                    str(np.dtype(getattr(data, "dtype", np.float32))))

        return (tuple(one(a) for a in arrs), one(label))

    def _fingerprint_parts(self, shape_sig) -> Tuple:
        """Structural name of one step executable (shapes/dtypes/static
        hypers/mesh axes — no object ids, restart-stable) for
        ``memwatch.fingerprint``: compile telemetry events carry it.  A
        name, not a key: the block's own configuration and the code are
        not in it."""
        # hypers baked into the trace as CONSTANTS split the name too:
        # two steps differing only in momentum (or remat, or the loss
        # class) compile different programs
        hyper_sig = (self._momentum, self._wd, self._rescale,
                     self._beta1, self._beta2, self._eps,
                     self._clip_gradient, self._clip_global,
                     self._remat, self.plan.sp_attention,
                     self.plan.pp_microbatches,
                     self.plan.batch_axes, self.plan.seq_axis,
                     type(self.loss_fn).__name__,
                     tuple(sorted(self._mults.items())),
                     # the AMP policy + loss-scale config
                     self._precision.signature()
                     if self._precision is not None else None,
                     # the ONE pass-pipeline signature: any config or
                     # order change (pass toggled, fused set grown, AMP
                     # policy swapped) changes the fingerprint
                     self._pipeline.signature())
        return ("DataParallelStep", type(self.block).__name__,
                self._optimizer, self.plan.accum_steps, hyper_sig,
                tuple(self.mesh.shape.items()), shape_sig)

    def _plan_dispatch(self, call_args, step_no, sp_active):
        """THE dispatch body: every compiled-step execution, whatever
        strategy the Plan encodes (dp/tp/pp/ring/ulysses and their
        compositions), runs through here.  The chaos/fault hook fires
        (`oom:step=N` raises a synthetic RESOURCE_EXHAUSTED exactly
        where a real HBM exhaustion would); the plan's trace-time
        scopes activate (pallas platform override, ring/ulysses SP
        routing, pipeline microbatch schedule); the profiler wrap and
        the OOM post-mortem close the loop."""
        from ..ops import pallas as _pk

        from .. import profiler

        # Pallas kernels must lower for the platform the MESH runs on
        # (a CPU mesh under a TPU default backend needs interpret
        # mode), and over several devices this jit leaves partitioning
        # to GSPMD, which cannot split a Mosaic call; both facts are
        # baked in at trace time, so scope them around the jit call.
        ring_cm, pp_cm = self._dispatch_scopes(sp_active)
        mesh_platform = next(iter(self.mesh.devices.flat)).platform
        try:
            fault.on_dispatch(step_no)
            with _pk.compute_on(mesh_platform, self.mesh.size > 1), \
                    ring_cm, pp_cm:
                if profiler.is_recording():
                    return profiler.timed_call(
                        f"FusedStep:{type(self.block).__name__}",
                        self._jitted, *call_args)
                return self._jitted(*call_args)
        except Exception as e:
            if memwatch.is_resource_exhausted(e):
                # land the post-mortem (census, largest category, top
                # executables, window depth) on disk before dying
                memwatch.emit_oom_report(
                    executor=self._tele_name, step=step_no,
                    inflight_depth=self._inflight.depth)
            raise

    def _dispatch_scopes(self, sp_active):
        """(ring_cm, pp_cm) trace-time scopes for one dispatch."""
        import contextlib

        from .scope import ring_attention_scope

        # ring routing only when THIS step actually sequence-sharded the
        # inputs (honors seq_axis=-1 / the auto-detect decline); the
        # batch-dim axes travel with the scope so the ring's shard_map
        # spec matches the activations' real sharding (dp batch + tp
        # heads on the collapsed B*H dim)
        if self.plan.sp_attention != "gspmd" and sp_active:
            dim0_axes = tuple(
                a for a in (tuple(x for x in self.plan.batch_axes if x != "sp")
                            + ("tp",))
                if a in self.mesh.axis_names and self.mesh.shape[a] > 1)
            ring_cm = ring_attention_scope(self.mesh, dim0_axes,
                                           mode=self.plan.sp_attention)
        else:
            ring_cm = contextlib.nullcontext()
        # pipeline scope: stacked-encoder models route their layer stack
        # through the GPipe schedule over 'pp'; batch stays dp-sharded
        if ("pp" in self.mesh.axis_names and self.mesh.shape["pp"] > 1
                and not sp_active):
            from .scope import pipeline_parallel_scope

            pp_axes = tuple(a for a in self.plan.batch_axes
                            if a != "sp" and a in self.mesh.axis_names
                            and self.mesh.shape[a] > 1)
            pp_cm = pipeline_parallel_scope(self.mesh, pp_axes,
                                            self.plan.pp_microbatches)
        else:
            pp_cm = contextlib.nullcontext()
        return ring_cm, pp_cm

    def drain(self) -> None:
        """Force every in-flight step (epoch end, pre-checkpoint, exit);
        raises the first deferred failure."""
        self._inflight.drain()
        self._record_aux_readings()
        # the MEANS to the scope map, not the map: whoever reads a trace of
        # this step asks telemetry, maybe after the step object is gone
        source = self._scope_map or self._scope_source()
        if source is not None:
            telemetry.record_scope_map(self._tele_name, source)

    def scope_map(self) -> Dict[str, dict]:
        """``{instruction name: {"scope", "block", "dir", "entry",
        "mixed"}}`` for every instruction of the executable this step runs
        that can be an event of a device trace: the Gluon blocks and
        ``mx_*`` scopes it lies under (``Block.trace_scope``; this step
        adds ``mx_loss`` and ``mx_update``), its pass (``fwd``, ``remat``,
        ``bwd``), whether it is in the ENTRY computation, and what XLA fused
        into it from another block (``hlo_scopes`` has the rules).  Built
        from the compiled module's text on the FIRST ask and kept; a
        ``step()`` never builds it.  Also handed to
        ``telemetry.record_scope_map``.  Empty before the first step."""
        if self._scope_map is None:
            source = self._scope_source()
            if source is None:
                return {}
            self._scope_map = source()
        telemetry.record_scope_map(self._tele_name, self._scope_map)
        return self._scope_map

    def _scope_source(self):
        """A function of no arguments that makes ``scope_map``'s dict, or
        None before the first step.  It holds the traced program (jax's own
        record of the last traced call: no second trace) and shapes, no
        parameter, no block and not this object, so ``drain`` can hand it to
        telemetry and the map of a step that is gone can still be asked for.
        The compile behind it is a load from the persistent cache wherever
        this program's own compile is there: metadata is in the key for
        this one lookup, because an entry that an older tree wrote under
        the usual key holds that tree's scopes."""
        if self._jitted is None or self._scope_args is None:
            return None
        from jax._src import config as _jax_config

        from ..ops import pallas as _pk

        batch, sp_active = self._scope_args
        state = memwatch.shape_structs(
            (self.params, self.opt_state) if self.scaler_state is None
            else (self.params, self.opt_state, self.scaler_state))
        ring_cm, pp_cm = self._dispatch_scopes(sp_active)
        mesh_platform = next(iter(self.mesh.devices.flat)).platform
        with _pk.compute_on(mesh_platform, self.mesh.size > 1), \
                ring_cm, pp_cm:
            traced = self._jitted.trace(*state, *batch)

        def make():
            from ..hlo_scopes import scope_map_of

            with _jax_config.compilation_cache_include_metadata_in_key(True):
                compiled = traced.lower().compile(compiler_options=_OWN_COMPILE)
            return scope_map_of(compiled.as_text())

        return make

    def _record_aux_readings(self) -> None:
        """Hand the aux leaves marked for telemetry (state the steps wrote
        on the device) to it: a sync, so only here, where every step in
        flight has just been forced."""
        if not self._aux_reading_kinds or self.params is None:
            return
        import jax

        from .. import telemetry

        values = jax.device_get(
            {n: self.params[n] for n in self._aux_reading_kinds})
        for name, v in values.items():
            reading = [float(x) for x in v]
            telemetry.record_aux_reading(self._aux_reading_kinds[name], name,
                                         reading)
            if name in self._aux_readers:
                self._aux_readers[name](name, reading)

    @property
    def inflight_depth(self) -> int:
        """Dispatched-but-unforced steps currently pending."""
        return self._inflight.depth

    def _current_lr(self, num_update: int) -> float:
        if self._lr_scheduler is not None:
            # mxlint: disable=hot-sync — python lr schedule, host scalar
            return float(self._lr_scheduler(num_update))
        # mxlint: disable=hot-sync — host python scalar, never on device
        return float(self._lr)

    @property
    def learning_rate(self) -> float:
        """The lr the NEXT step will use (Trainer.learning_rate analog)."""
        return self._current_lr(self._step_count + 1)

    def set_learning_rate(self, lr: float) -> None:
        """Manual lr override; no retrace (lr is a step argument)."""
        if self._lr_scheduler is not None:
            raise MXNetError(
                "set_learning_rate conflicts with an lr_scheduler "
                "(Trainer semantics: mutate the scheduler instead)")
        self._lr = float(lr)

    # ------------------------------------------------------------------
    def sync_to_block(self) -> None:
        """Write the sharded training state back into the Gluon parameters.
        Drains the in-flight window first so a deferred step failure
        surfaces here (named) instead of as a bare error mid-copy."""
        import jax

        self.drain()
        for name, p in self._param_items:
            host = np.asarray(jax.device_get(self.params[name]))
            p.set_data(host)
            if p.grad_req != "null" and p._grad is None:
                p._init_grad()           # released in _ensure_state

    # ------------------------------------------------------------------
    # checkpointable sharded state (docs/FAULT_TOLERANCE.md §Elastic
    # resize): the save-time layout travels with the snapshot so a
    # restore onto a DIFFERENT mesh (N->M ranks, or a reordered device
    # assignment) reshards instead of silently mis-placing shards.
    # ------------------------------------------------------------------
    def _struct_names(self) -> Dict[str, str]:
        """collect_params name -> structural name ('0.weight'), the
        scope-independent scheme checkpoints key on (a fresh process's
        gluon name counters may differ); identity mapping when the block
        doesn't expose structural names."""
        if not hasattr(self.block, "_collect_params_with_prefix"):
            return {n: n for n, _ in self._param_items}
        by_param = {id(p): sname for sname, p in
                    self.block._collect_params_with_prefix().items()}
        return {n: by_param.get(id(p), n) for n, p in self._param_items}

    def layout(self) -> dict:
        """JSON-serializable sharding layout of this step's training
        state: world size, mesh axes, the mesh's device assignment, and
        each parameter's PartitionSpec — what ``checkpoint.py`` records
        in ``meta.json`` and what ``load_state_dict`` compares against
        the current mesh to decide whether a restore must reshard."""
        import jax

        specs = {}
        if self._shardings is not None:
            smap = self._struct_names()
            for name, sh in self._shardings.items():
                specs[smap.get(name, name)] = [
                    list(a) if isinstance(a, tuple) else a
                    for a in tuple(sh.spec)]
        return {
            "world_size": int(jax.process_count()),
            "mesh_axes": [[n, int(s)] for n, s in self.mesh.shape.items()],
            "device_ids": [int(d.id) for d in self.mesh.devices.flat],
            "platform": next(iter(self.mesh.devices.flat)).platform,
            "specs": specs,
            # the full strategy Plan rides with the placement: an elastic
            # restore knows WHICH strategy produced these specs, and
            # Plan.from_json(layout["plan"]) rebuilds it on the new world
            # (docs/FAULT_TOLERANCE.md §Elastic resize)
            "plan": self.plan.to_json(),
            # the pass-pipeline config rides with the layout too: a
            # restore can rebuild descriptor passes
            # (passes.PassPipeline.from_json) and compare fingerprints
            # against the env it restarts under
            "passes": self._pipeline.to_json(),
        }

    def _to_host_full(self, arr, allow_collective: bool = True):
        """Full (global) host value of a possibly-sharded array — the
        gather-to-host correctness baseline of the resharding story.
        Fully-addressable arrays read directly and fully-replicated ones
        read their local shard (both collective-free, hence safe in the
        SIGTERM preemption path); a genuinely sharded multi-process
        array pays ONE compiled allgather (jit identity onto a
        replicated out_sharding), so every rank must call in lockstep —
        which scheduled checkpoints do by construction.
        ``allow_collective=False`` (the preemption path, where only ONE
        rank may be running this) raises instead of hanging the gather."""
        import jax

        if getattr(arr, "is_fully_addressable", True):
            return np.asarray(jax.device_get(arr))
        if getattr(arr, "is_fully_replicated", False):
            return np.asarray(arr.addressable_shards[0].data)
        if not allow_collective:
            raise MXNetError(
                "state_dict: a cross-process-sharded array needs an "
                "allgather, which a rank-local (preemption) snapshot must "
                "not run — resume from the last scheduled checkpoint "
                "instead")
        if self._gather_jit is None:
            # mxlint: disable=retrace-hazard — built once per step object
            self._gather_jit = jax.jit(
                lambda x: x, out_shardings=replicated(self.mesh))
        rep = self._gather_jit(arr)
        return np.asarray(rep.addressable_shards[0].data)

    def snapshot_requires_collective(self) -> bool:
        """Whether :meth:`state_dict` must run a gang-lockstep allgather
        (any cross-process-sharded, non-replicated array).  Non-writer
        ranks of a shared-dir gang consult this to skip building a full
        host snapshot they would only discard — the common replicated-dp
        case never needs their participation."""
        import jax

        arrs = list((self.params or {}).values())
        arrs += jax.tree_util.tree_leaves(self.opt_state)
        return any(
            not getattr(a, "is_fully_addressable", True)
            and not getattr(a, "is_fully_replicated", False)
            for a in arrs)

    def state_dict(self, allow_collective: bool = True) -> dict:
        """Host snapshot of the sharded training state, keyed by
        structural parameter names: ``{"params": {name: ndarray},
        "opt_state": {slot.name: ndarray}, "optimizer": ...}``.  Does NOT
        force the in-flight window — jax arrays are futures, and the
        host reads below block on exactly the values the dispatched
        steps produce."""
        if self.params is None:
            raise MXNetError(
                "state_dict: step holds no state yet (no step/stage ran)")
        smap = self._struct_names()

        def host(a):
            return self._to_host_full(a, allow_collective=allow_collective)

        params = {smap.get(n, n): host(a) for n, a in self.params.items()}
        opt: Dict[str, np.ndarray] = {}
        if self._optimizer == "sgd":
            for n, a in self.opt_state.items():
                opt[f"mom.{smap.get(n, n)}"] = host(a)
        else:
            import jax

            means, vars_, t = self.opt_state
            for n, a in means.items():
                opt[f"mean.{smap.get(n, n)}"] = host(a)
            for n, a in vars_.items():
                opt[f"var.{smap.get(n, n)}"] = host(a)
            opt["t"] = np.asarray(jax.device_get(t))
        if self.scaler_state is not None:
            # traced loss-scale state rides with the optimizer slots
            # (replicated scalars: collective-free host reads), so a
            # restore — same world or elastically resharded — resumes
            # the scale trajectory instead of restarting at init_scale
            for k in self.scaler_state:
                opt[f"amp.{k}"] = host(self.scaler_state[k])
        return {"params": params, "opt_state": opt,
                "optimizer": self._optimizer}

    def shard_state_dict(self) -> dict:
        """Rank-LOCAL shard snapshot: each entry carries only the shards
        this process's devices hold, plus the full (rank-invariant)
        shard manifest every rank derives from metadata alone.  ZERO
        collectives — unlike :meth:`state_dict` on cross-process-sharded
        state, this never gathers, so it is safe on the preemption path
        and its wall/bytes scale with the per-rank shard set, not the
        global param count (docs/FAULT_TOLERANCE.md §Shard-granular
        checkpoints).

        Returns ``{"params": {name: [(j, ndarray)]}, "opt_state":
        {slot: [(j, ndarray)]}, "manifest": {...}, "optimizer", "rank",
        "nprocs"}`` — slot naming matches :meth:`state_dict`
        (``mom.*``/``mean.*``/``var.*``/``t``/``amp.*``), so restore
        code downstream of either format sees the same key space."""
        if self.params is None:
            raise MXNetError(
                "shard_state_dict: step holds no state yet "
                "(no step/stage ran)")
        import jax

        rank = int(jax.process_index())
        nprocs = int(jax.process_count())
        smap = self._struct_names()
        manifest: Dict[str, dict] = {"params": {}, "opt_state": {}}
        local: Dict[str, dict] = {"params": {}, "opt_state": {}}

        def add(section, sname, arr):
            shards, payloads = _local_shard_split(arr, rank, nprocs)
            manifest[section][sname] = {
                "shape": [int(s) for s in np.shape(arr)],
                "dtype": str(arr.dtype),
                "shards": shards}
            if payloads:
                local[section][sname] = payloads

        for n, a in self.params.items():
            add("params", smap.get(n, n), a)
        if self._optimizer == "sgd":
            for n, a in self.opt_state.items():
                add("opt_state", f"mom.{smap.get(n, n)}", a)
        else:
            means, vars_, t = self.opt_state
            for n, a in means.items():
                add("opt_state", f"mean.{smap.get(n, n)}", a)
            for n, a in vars_.items():
                add("opt_state", f"var.{smap.get(n, n)}", a)
            add("opt_state", "t", t)
        if self.scaler_state is not None:
            for k in self.scaler_state:
                add("opt_state", f"amp.{k}", self.scaler_state[k])
        return {"params": local["params"], "opt_state": local["opt_state"],
                "manifest": manifest, "optimizer": self._optimizer,
                "rank": rank, "nprocs": nprocs}

    def load_state_dict(self, state: dict,
                        saved_layout: Optional[dict] = None) -> dict:
        """Install a host state snapshot onto THIS step's mesh,
        resharding when the save-time layout differs — the elastic
        N->M resume path (shrink and grow alike).

        Every parameter (and optimizer slot) is placed through
        ``_global_put``, which materializes ONLY the shards addressable
        to this process: on a resized or reordered mesh each rank moves
        exactly the shard set it now owns, nothing else — the
        shard-granular fast path over the gather-to-host baseline the
        snapshot itself is.  When ``saved_layout`` matches the current
        :meth:`layout` the placement is recorded as layout-stable (no
        reshard telemetry); a world-size change additionally records a
        ``resize`` event.  Returns an info dict (``resharded``,
        ``old_world``, ``new_world``, ``n_params``)."""
        saved_opt = state.get("optimizer") or (saved_layout or {}).get(
            "optimizer")
        if saved_opt and saved_opt != self._optimizer:
            raise MXNetError(
                f"checkpoint optimizer state was saved from a "
                f"{saved_opt!r} step but this step runs "
                f"{self._optimizer!r} — restoring would silently "
                "zero-fill every optimizer slot")
        params_host = state["params"]
        smap = self._struct_names()
        local_of = {v: k for k, v in smap.items()}
        # serialized against a DevicePrefetchIter's background stage()
        # racing first-use _ensure_state: whichever runs second must see
        # the other's published state, never interleave half-built dicts
        # (a late _ensure_state overwriting the restored params would
        # silently resume from re-initialized weights)
        with self._state_lock:
            if self._shardings is None:
                # fresh process, no step taken yet: build the shardings
                # from the snapshot's shapes — restore must not require a
                # warm-up step (it would advance the RNG and optimizer
                # state)
                shapes = {local_of.get(sname, sname): tuple(np.shape(v))
                          for sname, v in params_host.items()}
                self._shardings = self.plan.rules.shardings(self.mesh, shapes)
            cur = self.layout()
            same = (saved_layout is not None
                    and _layouts_equal(saved_layout, cur))
            new_params = {}
            for n, p in self._param_items:
                sname = smap.get(n, n)
                if sname not in params_host:
                    raise MXNetError(
                        f"checkpoint missing parameter {sname}")
                raw = params_host[sname]
                if hasattr(raw, "read_slice") and \
                        not self._shardings[n].is_fully_addressable:
                    # sharded-checkpoint lazy value onto a
                    # cross-process-sharded target: place per-shard
                    # straight from the shard files — NO host ever
                    # materializes the full array (the Gluon block keeps
                    # its init data; self.params is the authority, as it
                    # already is for every multi-process run)
                    new_params[n] = _lazy_put(raw, self._shardings[n])
                    continue
                host = np.asarray(raw)
                new_params[n] = _global_put(host, self._shardings[n])
                # keep the Gluon block in agreement (sync_to_block
                # parity, and a later eager forward must see the
                # restored weights)
                p.set_data(host)
            opt = dict(state.get("opt_state") or {})
            # scaler state travels under amp.* keys: pop it out before
            # the per-param slot logic (it is not a parameter slot, and
            # the partial-missing-slot check must not see it)
            amp_state = {k[len("amp."):]: opt.pop(k)
                         for k in list(opt) if k.startswith("amp.")}
            if not opt:
                # legitimate (a params-only / legacy Block checkpoint)
                # but never silent: momentum/Adam moments restart at zero
                import logging

                logging.getLogger("mxnet_tpu.data_parallel").warning(
                    "load_state_dict: checkpoint carries no optimizer "
                    "state — resuming with FRESH (zeroed) %s slots",
                    self._optimizer)

            def slot(prefix, n):
                sname = f"{prefix}.{smap.get(n, n)}"
                if sname in opt:
                    return opt[sname]
                if opt:
                    # a PARTIALLY missing slot is a renamed/mismatched
                    # param, not a fresh start — zero-filling just this
                    # one would silently corrupt the trajectory
                    raise MXNetError(
                        f"checkpoint optimizer state is missing slot "
                        f"{sname!r} (has: {sorted(opt)[:8]}...)")
                return np.zeros(np.shape(new_params[n]), np.float32)

            def place_slot(val, sharding):
                # same lazy fast path as the params loop above
                if hasattr(val, "read_slice") and \
                        not sharding.is_fully_addressable:
                    return _lazy_put(val, sharding)
                return _global_put(np.asarray(val), sharding)

            if self._optimizer == "sgd":
                opt_state = {
                    n: place_slot(slot("mom", n), self._shardings[n])
                    for n, _ in self._param_items}
            else:
                m = {n: place_slot(slot("mean", n), self._shardings[n])
                     for n, _ in self._param_items}
                v = {n: place_slot(slot("var", n), self._shardings[n])
                     for n, _ in self._param_items}
                t = _global_put(np.asarray(opt.get("t", 0), np.int32),
                                replicated(self.mesh))
                opt_state = (m, v, t)
            if self._loss_scale_cfg is not None:
                from ..precision import loss_scale as _ls

                import logging

                fresh = _ls.init_scaler_host(self._loss_scale_cfg)
                if not amp_state:
                    # params-only / pre-precision checkpoint: resume
                    # with a fresh scaler, loudly — the scale re-warms
                    # from init_scale instead of its learned value
                    logging.getLogger("mxnet_tpu.data_parallel").warning(
                        "load_state_dict: checkpoint carries no amp.* "
                        "loss-scale state — resuming with a FRESH scaler "
                        "(scale=%s)", fresh["scale"])
                host_scaler = {
                    k: np.asarray(amp_state.get(k, fresh[k])).astype(
                        np.asarray(fresh[k]).dtype)
                    for k in _ls.SCALER_KEYS}
                repl = replicated(self.mesh)
                self.scaler_state = {
                    k: _global_put(v, repl)
                    for k, v in host_scaler.items()}
            elif amp_state:
                import logging

                logging.getLogger("mxnet_tpu.data_parallel").warning(
                    "load_state_dict: checkpoint carries amp.* loss-scale "
                    "state but this step runs without loss scaling — "
                    "ignoring it")
            # publish params LAST (the unlocked _ensure_state fast-path
            # check)
            self.opt_state = opt_state
            self.params = new_params
        old_world = (saved_layout or {}).get("world_size")
        info = {"resharded": bool(saved_layout is not None and not same),
                "old_world": old_world,
                "new_world": cur["world_size"],
                "n_params": len(new_params)}
        if info["resharded"] and telemetry.enabled():
            telemetry.record("reshard", executor=self._tele_name,
                             n_params=len(new_params),
                             old_world=old_world,
                             new_world=cur["world_size"])
            if old_world is not None and old_world != cur["world_size"] \
                    and not os.environ.get("MX_ELASTIC") \
                    and not os.environ.get("MX_PREV_NUM_PROCS"):
                # the segment marker the report tools key on — but ONLY
                # for manual (supervisor-less) resizes.  Under --elastic
                # the rendezvous already recorded it off
                # MX_PREV_NUM_PROCS, and a LATER same-size restart that
                # re-restores the old-world checkpoint (died before its
                # first post-resize save) must not mint a second marker
                # for the same logical resize — the stream already
                # carries the first incarnation's
                telemetry.record("resize", old_world=old_world,
                                 new_world=cur["world_size"],
                                 source="restore")
        return info


def _layouts_equal(a: dict, b: dict) -> bool:
    """Whether two :meth:`DataParallelStep.layout` descriptions denote the
    SAME placement: world size, mesh axes, per-param specs AND the device
    assignment — shard ownership keys on device ids, so a same-shape
    mesh over reordered devices is a different layout."""
    keys = ("world_size", "mesh_axes", "device_ids", "specs")
    return all(a.get(k) == b.get(k) for k in keys)


def make_train_step(block, loss_fn, mesh=None, **kwargs) -> DataParallelStep:
    return DataParallelStep(block, loss_fn, mesh=mesh, **kwargs)


def compile_step_with_plan(block, loss_fn, plan: Plan, mesh=None,
                           **kwargs) -> DataParallelStep:
    """THE single compile path of the parallelism zoo: consume ANY
    :class:`~mxnet_tpu.parallel.plan.Plan` — dp, tp, pipeline, ring or
    Ulysses SP, or any composition the planner enumerated — and return
    the compiled :class:`DataParallelStep` for it.  The async in-flight
    window, telemetry spans and elastic resharding all ride along: they
    are features of the one dispatch body (``_plan_dispatch``), not of
    any single strategy.

    ``mesh`` defaults to ``plan.build_mesh()`` over all devices; pass an
    explicit mesh (it must match the plan's axes) to pin devices.
    Remaining kwargs (optimizer/optimizer_params/donate/remat/
    clip_global_norm) pass through — they are training-config, not
    layout, so they live outside the Plan.

    Records one ``plan`` telemetry event carrying the plan and, when the
    planner chose it, the predicted cost breakdown —
    ``tools/trace_report.py`` can then compare predicted step wall
    against the measured ``step`` events of the same stream
    (docs/PERFORMANCE.md §Plan & planner)."""
    step = DataParallelStep(block, loss_fn, mesh=mesh, plan=plan, **kwargs)
    if telemetry.enabled():
        telemetry.record(
            "plan", executor=step._tele_name, strategy=plan.strategy,
            plan=plan.to_json(),
            # the pass pipeline this step compiles under: names + its
            # fingerprint — a trace reader can tie a slow/fast step
            # stream to the exact rewrite config that produced it
            passes=step._pipeline.names(),
            pass_fingerprint=step._pipeline.fingerprint(),
            predicted=plan.predicted)
    return step
