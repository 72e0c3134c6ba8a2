"""Declarative operator registry.

Reference parity: NNVM op registry (3rdparty/tvm/nnvm/include/nnvm/op.h) +
imperative dispatch (src/imperative/imperative.cc Imperative::Invoke ~L90,
imperative_utils.h PushFCompute ~L400).

TPU-native design:
  * an Operator's FCompute is a pure jax function ``fn(*arrays, **attrs)``;
  * eager calls go through a per-(op, attrs) ``jax.jit`` cache — jax's own
    C++ dispatch then caches per input signature, which plays the role of
    the reference's engine push fast-path;
  * shape/dtype inference falls out of jax abstract evaluation — there are
    no separate FInferShape/FInferType functions to keep in sync;
  * gradients come from ``jax.vjp`` captured at execution time (autograd.py),
    replacing per-op FGradient registrations;
  * inside a HybridBlock trace the inputs are jax tracers: the op function
    is inlined into the outer jaxpr (CachedOp), with no tape recording —
    exactly the reference split between Imperative::Invoke and CachedOp.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..base import MXNetError, canonical_kwargs
from .. import engine
from ..passes import hooks as _pass_hooks

__all__ = ["Operator", "register", "get_op", "invoke", "list_ops"]

_OPS: Dict[str, "Operator"] = {}


class Operator:
    """A registered op: name, pure jax FCompute, and differentiability."""

    def __init__(self, name: str, fn: Callable, differentiable: bool = True,
                 doc: Optional[str] = None):
        self.name = name
        self.fn = fn
        self.differentiable = differentiable
        self.__doc__ = doc or fn.__doc__
        self._jit_cache: Dict[Any, Callable] = {}
        self._bwd_cache: Dict[Any, Callable] = {}

    def jitted(self, attrs: dict,
               platform: Optional[str] = None) -> Callable:
        """The cached eager jit for this attr combo.  ``platform`` is
        "cpu" for a host context and None for the process's default
        backend: an op that picks a Pallas kernel decides at trace time,
        and jax's trace cache does not see devices, so on a TPU host the
        host contexts get a jit (and a kernel decision) of their own."""
        key = (canonical_kwargs(attrs), platform)
        jfn = self._jit_cache.get(key)
        if jfn is None:
            # first sight of this attr combo: typed validation (reference
            # dmlc::Parameter::Init at op instantiation); cache hits skip it
            from . import params as _params

            _params.validate_known(self.name, attrs)
            fn = self.fn

            @functools.wraps(fn)
            def call(*arrays):
                if platform is None:
                    return fn(*arrays, **attrs)
                from .pallas import compute_on

                with compute_on(platform):
                    return fn(*arrays, **attrs)

            import jax

            jfn = jax.jit(call)
            jfn._canonical_key = key
            self._jit_cache[key] = jfn
        return jfn

    def bwd_jitted(self, jfn: Callable, mask: tuple) -> Callable:
        """Compiled backward for this (attrs, detach-mask) signature.

        The eager tape defers vjp construction to backward time (recording
        an op costs one cached-jit forward, ~15µs, instead of a ~650µs
        jax.vjp re-trace per call); the vjp itself runs through this cached
        jit — forward is recomputed inside it (remat-style), which XLA
        dead-code-eliminates down to the residuals the backward needs.

        `jfn` must come from self.jitted() (its canonical key is reused so
        the hot path canonicalizes attrs exactly once).
        """
        key = (jfn._canonical_key, mask)
        bwd = self._bwd_cache.get(key)
        if bwd is None:
            import jax

            fwd = _wrap_masked(jfn, mask)

            def bwd_fn(xs, ct):
                return jax.vjp(fwd, *xs)[1](ct)

            bwd = jax.jit(bwd_fn)
            self._bwd_cache[key] = bwd
        return bwd

    def __repr__(self):
        return f"<Operator {self.name}>"


def register(name: Optional[str] = None, differentiable: bool = True):
    """Decorator: register a pure jax function as an operator."""

    def deco(fn: Callable) -> Callable:
        opname = name or fn.__name__
        if opname in _OPS:
            raise MXNetError(f"op {opname!r} registered twice")
        _OPS[opname] = Operator(opname, fn, differentiable=differentiable)
        return fn

    return deco


def get_op(name: str) -> Operator:
    try:
        return _OPS[name]
    except KeyError:
        raise MXNetError(f"unknown operator {name!r}") from None


def list_ops() -> List[str]:
    return sorted(_OPS)


def _is_tracer(x) -> bool:
    import jax

    return isinstance(x, jax.core.Tracer)


def _is_float(arr) -> bool:
    from ..base import is_float_dtype

    return is_float_dtype(arr.dtype)


def invoke(op: Operator, inputs: Sequence, out=None, ctx=None, **attrs):
    """Execute `op` on NDArray inputs; returns NDArray or list of NDArrays.

    This is the single dispatch point shared by eager mode, autograd
    recording, and HybridBlock tracing (reference: MXImperativeInvokeEx).
    `ctx` only matters for zero-input (creation) ops; otherwise outputs
    follow their inputs' device, as in the reference.
    """
    from .. import profiler

    if profiler.is_recording() and not any(_is_tracer(x._data)
                                           for x in inputs):
        # per-op aggregate stats (reference: ThreadedEngine profiler
        # brackets -> aggregate_stats.cc).  Blocking for the timing
        # serializes dispatch — profiling overhead, as in the reference.
        return profiler.timed_call(op.name, _invoke_impl, op, inputs,
                                   out=out, ctx=ctx, **attrs)
    return _invoke_impl(op, inputs, out=out, ctx=ctx, **attrs)


def _invoke_impl(op: Operator, inputs: Sequence, out=None, ctx=None, **attrs):
    from ..ndarray import NDArray
    from .. import autograd

    # THE pass-pipeline consultation (docs/PRECISION.md §Pass pipeline):
    # the ONE module global dispatch reads.  Empty tuple when no pass is
    # active — that falsy check is the entire passes-off cost, exactly
    # the contract the PR 15 AMP global established.  Active hooks (the
    # AMP cast pass, ...) rewrite this call's inputs; trace-time kernel
    # substitution consults the same tuple on the traced branch below.
    # mxlint pins this: any OTHER module-global consultation added here
    # is a pass-outside-pipeline finding.
    op_hooks = _pass_hooks._OP_HOOKS
    if op_hooks and inputs:
        for h in op_hooks:
            inputs = h.rewrite_inputs(op.name, inputs)
    arrays = [x._data for x in inputs]
    if inputs:
        ctx = inputs[0].context
    elif ctx is None:
        from ..context import current_context

        ctx = current_context()

    traced = any(_is_tracer(a) for a in arrays)
    if traced:
        # hybridized trace: same typed validation as the eager jit-miss
        # path (once per trace, not per step)
        from . import params as _params

        _params.validate_known(op.name, attrs)
        arrays = _stop_detached(arrays, inputs)
        fn = op.fn
        if op_hooks:
            # fused-kernel substitution (passes/builtin.FusedKernelPass):
            # inside a trace an active pass may swap this op-class's
            # FCompute for a registered Pallas kernel; eager dispatch
            # never consults the kernel registry
            for h in op_hooks:
                alt = h.substitute(op.name, attrs)
                if alt is not None:
                    fn = alt
        outs = fn(*arrays, **attrs)
    elif not arrays:
        # creation op: place the result on ctx's device
        import jax

        with jax.default_device(ctx.jax_device):
            outs = op.jitted(attrs, _host_platform(ctx))()
    else:
        jfn = op.jitted(attrs, _host_platform(ctx))
        if (op.name == "Embedding" and attrs.get("sparse_grad")
                and autograd.is_recording()):
            # row_sparse backward: record a custom pullback that yields a
            # (indices, values) cotangent for the weight instead of a
            # dense vocab-sized scatter (reference: EmbeddingOpBackward
            # row_sparse path, src/operator/tensor/indexing_op.h)
            outs = jfn(*arrays)
            data_arr, weight_arr = arrays
            vocab, dim = weight_arr.shape

            def sparse_vjp(ct):
                import jax.numpy as jnp

                ids = jnp.clip(data_arr.astype(jnp.int32), 0,
                               vocab - 1).reshape(-1)
                vals = ct.reshape(-1, dim)
                return [None, autograd._RowSparseCT(ids, vals,
                                                    weight_arr.shape)]

            autograd.record_node(sparse_vjp, arrays, [outs],
                                 input_nds=inputs)
        elif (
            autograd.is_recording()
            and op.differentiable
            and arrays
            and any(_is_float(a) for a in arrays)
        ):
            # fast recording: forward through the cached jit (same cost as
            # un-recorded eager); the vjp is DEFERRED to backward time and
            # runs through a per-(op, attrs, mask) compiled backward —
            # recording no longer pays a jax.vjp re-trace per call
            mask = _detach_mask(inputs)
            wrapped = _wrap_masked(jfn, mask)
            outs = wrapped(*arrays)
            bwd = op.bwd_jitted(jfn, mask)
            in_arrays = tuple(arrays)

            def vjp_fn(ct, _bwd=bwd, _xs=in_arrays):
                return _bwd(_xs, ct)

            seq = isinstance(outs, (tuple, list))
            out_list = list(outs) if seq else [outs]
            # identity-like ops (e.g. SVMOutput's forward) can return an
            # INPUT array object unchanged; the tape keys nodes by
            # id(array), so an aliased output would both seed the head
            # cotangent and receive the op's vjp — break the alias
            in_ids = {id(a) for a in arrays}
            if any(id(o) in in_ids for o in out_list):
                import jax.numpy as jnp

                out_list = [jnp.copy(o) if id(o) in in_ids else o
                            for o in out_list]
                outs = type(outs)(out_list) if seq else out_list[0]
            autograd.record_node(vjp_fn, arrays, out_list, input_nds=inputs,
                                 fwd_fn=wrapped)
        else:
            outs = jfn(*arrays)
        if engine.is_naive():
            import jax

            jax.block_until_ready(outs)

    multi = isinstance(outs, (tuple, list))
    out_list = list(outs) if multi else [outs]
    results = [NDArray(o, ctx=ctx) for o in out_list]
    if out is not None:
        if multi:
            raise MXNetError(f"out= not supported for multi-output op {op.name}")
        out._set_data(results[0]._data)
        return out
    return results if multi else results[0]


def _host_platform(ctx) -> Optional[str]:
    """``Operator.jitted``'s platform argument for an eager call on ctx."""
    return "cpu" if ctx.device_type.startswith("cpu") else None


def _vjp(jfn, arrays):
    import jax

    return jax.vjp(jfn, *arrays)


def _stop_detached(arrays, inputs):
    import jax

    return [
        jax.lax.stop_gradient(a) if getattr(nd, "_detached", False) else a
        for a, nd in zip(arrays, inputs)
    ]


def _detach_mask(inputs):
    return tuple(bool(getattr(nd, "_detached", False)) for nd in inputs)


def _wrap_masked(fn, mask):
    """Stop gradient flow through the mask-selected arguments (the single
    implementation both the forward wrapper and the compiled backward use,
    so detach semantics can't drift between them)."""
    if not any(mask):
        return fn
    import jax

    def wrapped(*arrays):
        return fn(*[
            jax.lax.stop_gradient(a) if m else a for a, m in zip(arrays, mask)
        ])

    return wrapped


def _wrap_detached(fn, inputs):
    """Stop gradient flow through inputs marked detach()ed, without copying
    their buffers or changing their tape identity."""
    return _wrap_masked(fn, _detach_mask(inputs))


def invoke_by_name(name: str, inputs, out=None, **attrs):
    return invoke(get_op(name), inputs, out=out, **attrs)


def invoke_fn(fn, inputs, out=None):
    """Execute an ad-hoc pure jax function on NDArray inputs with full
    autograd-recording / tracing support but no jit cache (used by NDArray
    indexing and other closures whose attrs aren't hashable)."""
    from ..ndarray import NDArray
    from .. import autograd

    arrays = [x._data for x in inputs]
    ctx = inputs[0].context if inputs else None

    traced = any(_is_tracer(a) for a in arrays)
    if not traced and autograd.is_recording() and any(_is_float(a) for a in arrays):
        wrapped = _wrap_detached(fn, inputs)
        outs, vjp_fn = _vjp(wrapped, arrays)
        out_list = outs if isinstance(outs, (tuple, list)) else [outs]
        autograd.record_node(vjp_fn, arrays, list(out_list), input_nds=inputs,
                             fwd_fn=wrapped)
    else:
        if traced:
            arrays = _stop_detached(arrays, inputs)
        outs = fn(*arrays)
        if not traced and engine.is_naive():
            import jax

            jax.block_until_ready(outs)

    multi = isinstance(outs, (tuple, list))
    out_list = list(outs) if multi else [outs]
    results = [NDArray(o, ctx=ctx) for o in out_list]
    if out is not None:
        if multi:
            raise MXNetError("out= not supported for multi-output functions")
        out._set_data(results[0]._data)
        return out
    return results if multi else results[0]
