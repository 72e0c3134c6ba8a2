"""Gluon Block / HybridBlock and the CachedOp graph executor.

Reference parity: python/mxnet/gluon/block.py (Block.__call__ ~L500,
HybridBlock.hybridize ~L700, _build_cache ~L750) over src/imperative/
cached_op.cc (CachedOp::Forward ~L700, GetForwardGraph ~L200).

TPU-native design: hybridize() does not build an nnvm graph — calling a
hybridized block traces its eager forward (all NDArray ops hit the traced
branch of ops.registry) into a jaxpr, which jax.jit compiles into ONE XLA
executable.  XLA performs the memory planning, fusion and bulking that
PlanMemory / FusedOp / engine bulk-exec do in the reference.  The
per-input-signature executable cache that CachedOp keeps (GetForwardGraph
re-planning on new shapes) is exactly jax.jit's signature cache.

Mutable-state parity: parameter reads inside the trace are substituted with
traced values (see parameter.begin_trace); BatchNorm-style aux mutations are
collected during the trace, returned as extra outputs, and applied by buffer
swap after each call; dropout RNG becomes an explicit key argument threaded
through the traced function (random.set_trace_key_provider).
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from ..base import MXNetError
from ..context import Context, cpu, current_context
from .. import autograd
from .. import random as _random
from .parameter import (DeferredInitializationError, Parameter, ParameterDict,
                        begin_trace, end_trace, trace_active)

__all__ = ["Block", "HybridBlock", "SymbolBlock", "CachedOp"]


class _BlockScope(threading.local):
    """Name-scope manager (reference: block.py _BlockScope)."""

    def __init__(self):
        self._current: Optional["Block"] = None
        self._counters: Dict[str, int] = {}

    def create(self, prefix, params, hint):
        current = self._current
        if current is None:
            if prefix is None:
                count = self._counters.get(hint, 0)
                self._counters[hint] = count + 1
                prefix = f"{hint}{count}_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._scope_counters.get(hint, 0)
            current._scope_counters[hint] = count + 1
            prefix = f"{hint}{count}_"
        if params is None:
            parent = current._params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current.prefix + prefix, params


_scope = _BlockScope()


class _NameScopeCtx:
    def __init__(self, block):
        self._block = block
        self._prev = None

    def __enter__(self):
        self._prev = _scope._current
        _scope._current = self._block
        return self

    def __exit__(self, *exc):
        _scope._current = self._prev
        return False


class Block:
    """Base building block (reference: gluon/block.py Block)."""

    def __init__(self, prefix: Optional[str] = None,
                 params: Optional[ParameterDict] = None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _scope.create(prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") else self._prefix
        # what a trace calls this block's ops: class, and the name it has
        # inside its parent (docs/OBSERVABILITY.md, Device time by scope)
        parent = _scope._current
        local = self._prefix[len(parent.prefix) if parent else 0:]
        self._trace_name = f"{type(self).__name__}.{local}"
        self._scope_counters: Dict[str, int] = {}
        self._children: "OrderedDict[str, Block]" = OrderedDict()
        self._reg_params: Dict[str, Parameter] = {}
        self._forward_hooks: List = []
        self._forward_pre_hooks: List = []

    def _alias(self) -> str:
        return self.__class__.__name__.lower()

    # ------------------------------------------------------------------
    @property
    def prefix(self) -> str:
        return self._prefix

    @property
    def name(self) -> str:
        return self._name

    @property
    def params(self) -> ParameterDict:
        return self._params

    def name_scope(self):
        return _NameScopeCtx(self)

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join(
            f"  ({key}): {_indent(repr(block), 2)}"
            for key, block in self._children.items())
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block: "Block", name: Optional[str] = None) -> None:
        self._children[name or str(len(self._children))] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    # ------------------------------------------------------------------
    def collect_params(self, select: Optional[str] = None) -> ParameterDict:
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pattern = re.compile(select)
            ret._params.update(
                {k: v for k, v in self._params.items() if pattern.match(k)})
        for child in self._children.values():
            ret.update(child.collect_params(select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False) -> None:
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def _collect_params_with_prefix(self, prefix: str = "") -> Dict[str, Parameter]:
        """Structural dot-names ('0.weight', 'body.1.bias', ...) — the
        scope-independent naming save_parameters uses (reference block.py
        _collect_params_with_prefix ~L380)."""
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename: str, deduplicate: bool = False) -> None:
        """Save with structural names (reference gluon/block.py
        save_parameters ~L400: format is independent of name scopes)."""
        from .. import ndarray as nd

        params = self._collect_params_with_prefix()
        arg_dict = {}
        seen = {}
        for name, param in params.items():
            if deduplicate and id(param) in seen:
                continue
            seen[id(param)] = name
            arg_dict[name] = param._reduce()
        nd.save(filename, arg_dict)

    def load_parameters(self, filename: str, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current") -> None:
        from .. import ndarray as nd

        loaded = nd.load(filename)
        params = self._collect_params_with_prefix()
        if loaded and params and not any(k in params for k in loaded):
            # legacy full-name format (save_params): go through ParameterDict
            self.collect_params().load(filename, ctx, allow_missing,
                                       ignore_extra,
                                       restore_prefix=self.prefix,
                                       loaded=loaded)
            return
        if not allow_missing:
            for name in params:
                if name not in loaded:
                    raise MXNetError(
                        f"parameter {name} missing in {filename}")
        for name, value in loaded.items():
            if name not in params:
                if ignore_extra:
                    continue
                raise MXNetError(f"parameter {name} in file not in model")
            params[name]._load_init(value, ctx, cast_dtype=cast_dtype)

    # legacy names
    def save_params(self, filename: str) -> None:
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.load_parameters(filename, ctx, allow_missing, ignore_extra)

    def cast(self, dtype) -> None:
        for child in self._children.values():
            child.cast(dtype)
        for param in self._params.values():
            param.cast(dtype)

    def hybridize(self, active: bool = True, **kwargs) -> None:
        for child in self._children.values():
            child.hybridize(active, **kwargs)

    # ------------------------------------------------------------------
    def __call__(self, *args):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if trace_active():
            with self.trace_scope():
                out = self.forward(*args)
        else:
            out = self.forward(*args)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def trace_scope(self):
        """The scope of this block's ops in a traced program: the Gluon
        hierarchy is the scope path of every compiled instruction
        (``DataParallelStep.scope_map``).  Metadata alone; entered only
        while a trace is active."""
        import jax

        return jax.named_scope(self._trace_name)

    def forward(self, *args):
        raise NotImplementedError

    def summary(self, *inputs):
        raise NotImplementedError(
            "summary() lands with the visualization module")


def _indent(s, n):
    pad = " " * n
    return ("\n" + pad).join(s.split("\n"))


class CachedOp:
    """The hybridization executor: block forward as ONE jitted function.

    Reference: src/imperative/cached_op.cc.  Signature cache and memory
    planning are delegated to jax.jit / XLA; we keep one traced+jitted
    callable per train-mode flag (dropout/BN change the traced program).
    """

    _instance_counter = 0

    def __init__(self, block: "HybridBlock", flags: Dict[str, Any]):
        self.block = block
        self.flags = flags
        # retrace tracking is per-instance: a model holding many
        # same-class blocks of different widths must not pool their (one
        # each, perfectly stable) signatures into a false retrace storm
        CachedOp._instance_counter += 1
        self._tele_name = (f"CachedOp:{type(block).__name__}"
                           f"#{CachedOp._instance_counter}")
        # keyed by (train, input treedef): inputs may be arbitrary pytrees of
        # NDArrays (e.g. RNN layers take (x, [h, c]))
        self._jitted: Dict[Any, Any] = {}
        self._param_items: Optional[List] = None  # [(name, Parameter)]
        self._aux_params: Dict[Any, List[Parameter]] = {}
        self._out_treedef: Dict[Any, Any] = {}
        self._n_out: Dict[Any, int] = {}

    def _ensure_params(self, ctx):
        if self._param_items is None:
            params = self.block.collect_params()
            self._param_items = list(params.items())
        # triggers deferred-init errors before tracing
        return [p.data(ctx) for _, p in self._param_items]

    @staticmethod
    def _flatten(args):
        import jax.tree_util as jtu

        from ..ndarray import NDArray

        leaves, treedef = jtu.tree_flatten(
            list(args), is_leaf=lambda x: isinstance(x, NDArray))
        return leaves, treedef

    def _build(self, cache_key, train: bool, ctx, in_treedef):
        import jax
        import jax.tree_util as jtu

        block = self.block
        param_list = [p for _, p in self._param_items]
        cached = self

        def fn(param_arrays, key, *input_arrays):
            from ..ndarray import NDArray

            param_map = {
                p: NDArray(arr, ctx=ctx)
                for p, arr in zip(param_list, param_arrays)
            }
            nd_leaves = [NDArray(a, ctx=ctx) for a in input_arrays]
            nd_inputs = jtu.tree_unflatten(in_treedef, nd_leaves)
            prev_trace = begin_trace(param_map, ctx)
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(train)
            prev_key = _random.set_trace_key_provider(
                _random._TraceKeyProvider(key))
            try:
                out = block.forward(*nd_inputs)
            finally:
                state = end_trace(prev_trace)
                autograd.set_recording(prev_rec)
                autograd.set_training(prev_train)
                _random.set_trace_key_provider(prev_key)
            out_nds, out_treedef = jtu.tree_flatten(
                out, is_leaf=lambda x: isinstance(x, NDArray))
            cached._out_treedef[cache_key] = out_treedef
            cached._n_out[cache_key] = len(out_nds)
            cached._aux_params[cache_key] = [p for p, _ in state["aux"]]
            aux_vals = [v._data for _, v in state["aux"]]
            return tuple(o._data for o in out_nds) + tuple(aux_vals)

        return jax.jit(fn)

    def __call__(self, *inputs):
        import jax.tree_util as jtu

        from ..ndarray import NDArray

        in_nds, in_treedef = self._flatten(inputs)
        ctx = in_nds[0].context
        param_nds = self._ensure_params(ctx)
        train = autograd.is_training()
        cache_key = (train, in_treedef)
        jfn = self._jitted.get(cache_key)
        was_cold = jfn is None
        if jfn is None:
            jfn = self._build(cache_key, train, ctx, in_treedef)
            self._jitted[cache_key] = jfn

        # telemetry retrace detection: jax.jit re-traces (and XLA
        # recompiles) this block for every new input shape/dtype/treedef —
        # shape-churning data pipelines silently spend their time compiling
        from .. import telemetry

        shape_sig = None
        if telemetry.retrace_enabled():
            # note_signature returns True for a NEW signature = this call
            # traces + XLA-compiles; OR with was_cold so a second
            # executor over a seen signature still books its compile.
            # With detection OFF, traced falls back to the first build
            # per cache key only — per-shape respecializations then go
            # unbooked, by design: the kill switch exists to remove the
            # per-call signature probe that would detect them
            shape_sig = tuple((tuple(x.shape), str(x._data.dtype))
                              for x in in_nds)
            traced = telemetry.note_signature(
                self._tele_name, (train, str(in_treedef), shape_sig)) \
                or was_cold
        else:
            traced = was_cold

        key = _random.next_key()
        arrays = tuple(p._data for p in param_nds)
        in_arrays = [x._data for x in in_nds]
        import time as _time

        # timed only when a compile event can fire: the warm steady-state
        # path (cached jit, detection off) must pay nothing here
        t0 = _time.perf_counter() if traced else 0.0

        recording = autograd.is_recording()
        if recording:
            import jax

            outs, vjp_fn = jax.vjp(jfn, arrays, key, *in_arrays)
            flat_inputs = list(arrays) + [key] + in_arrays

            def adapter(cots):
                pc, kc, *ic = vjp_fn(cots if isinstance(cots, tuple) else (cots,))
                return list(pc) + [kc] + list(ic)

            n_params = len(arrays)

            def flat_fwd(*flat, _jfn=jfn, _np_=n_params):
                # flat-args twin of jfn for create_graph re-linearization
                return _jfn(tuple(flat[:_np_]), flat[_np_],
                            *flat[_np_ + 1:])

            autograd.record_node(adapter, flat_inputs, list(outs),
                                 input_nds=param_nds + in_nds,
                                 fwd_fn=flat_fwd)
        else:
            outs = jfn(arrays, key, *in_arrays)

        if traced:
            # one compile event per specialized executable of this block
            # (per train flag + treedef + input signature) — never
            # re-emitted on the cached steady-state path
            from .. import memwatch

            if shape_sig is None:  # detection off: built only on compile
                shape_sig = tuple((tuple(x.shape), str(x._data.dtype))
                                  for x in in_nds)
            memwatch.note_compile(
                self._tele_name,
                ("CachedOp", type(self.block).__name__, train,
                 str(in_treedef), shape_sig,
                 tuple((tuple(a.shape), str(a.dtype)) for a in arrays)),
                wall_s=_time.perf_counter() - t0, site="cached_op",
                jitted=jfn,
                args=(memwatch.shape_structs(arrays),
                      memwatch.shape_structs(key),
                      *memwatch.shape_structs(tuple(in_arrays))))

        n_out = self._n_out[cache_key]
        out_nds = [NDArray(o, ctx=ctx) for o in outs[:n_out]]
        # apply collected aux-state updates by buffer swap
        for p, new in zip(self._aux_params[cache_key], outs[n_out:]):
            target = p._data.get(ctx)
            if target is not None:
                target._set_data(new)
        return jtu.tree_unflatten(self._out_treedef[cache_key], out_nds)


class HybridBlock(Block):
    """A Block compilable into one XLA executable via hybridize()."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._flags: Dict[str, Any] = {}
        self._cached_op: Optional[CachedOp] = None

    def hybridize(self, active: bool = True, static_alloc: bool = False,
                  static_shape: bool = False, inline_limit: int = 2,
                  forward_bulk_size: Optional[int] = None,
                  backward_bulk_size: Optional[int] = None) -> None:
        self._active = active
        self._flags = {"static_alloc": static_alloc,
                       "static_shape": static_shape}
        self._cached_op = None
        super().hybridize(active, static_alloc=static_alloc,
                          static_shape=static_shape)

    def _clear_cached_op(self) -> None:
        self._cached_op = None

    def infer_shape(self, *args) -> None:
        """Shape-inference hook for deferred parameter init.  Built-in layers
        override this; composite blocks rely on their children."""
        raise MXNetError(
            f"{type(self).__name__} has deferred-initialized parameters but "
            "no infer_shape(); initialize with explicit shapes or override "
            "infer_shape")

    def _deferred_infer_shape(self, *args) -> None:
        self.infer_shape(*args)
        for param in self._reg_params.values():
            if param._deferred is not None:
                param._finish_deferred_init()

    def cast(self, dtype):
        self._clear_cached_op()
        super().cast(dtype)

    def __call__(self, *args):
        from .. import symbol as _sym

        if args and isinstance(args[0], _sym.Symbol):
            # symbol trace: bypass hooks/cached-op, compose the graph
            return self.forward(*args)
        # inside an active trace, always run the eager path (ops see tracers)
        if self._active and not trace_active():
            try:
                return self._call_cached_op(*args)
            except DeferredInitializationError:
                self._infer_and_retry_params(*args)
                return self._call_cached_op(*args)
        return super().__call__(*args)

    def _call_cached_op(self, *args):
        if self._cached_op is None:
            self._cached_op = CachedOp(self, self._flags)
        from .. import profiler

        if profiler.is_recording():
            return profiler.timed_call(f"CachedOp:{type(self).__name__}",
                                       self._cached_op, *args)
        return self._cached_op(*args)

    def _infer_and_retry_params(self, *args) -> None:
        # Run one eager forward: each leaf layer resolves its own deferred
        # params via its infer_shape on the way through.
        with autograd.pause(train_mode=autograd.is_training()):
            super().__call__(*args)

    def forward(self, x, *args):
        """Dispatch to hybrid_forward with params bound (reference ~L750)."""
        from .. import symbol as _sym

        if isinstance(x, _sym.Symbol):
            # symbol trace (export path): params become named variables
            params = {name: p.var()
                      for name, p in self._reg_params.items()}
            return self.hybrid_forward(_sym, x, *args, **params)
        ctx = x.context
        try:
            params = {name: p.data(ctx) for name, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer_shape(x, *args)
            params = {name: p.data(ctx) for name, p in self._reg_params.items()}
        from .. import ndarray as F

        return self.hybrid_forward(F, x, *args, **params)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    def export(self, path: str, epoch: int = 0, input_names=("data",)):
        """Emit {path}-symbol.json + {path}-{epoch:04d}.params (reference:
        gluon/block.py export ~L900): trace hybrid_forward with Symbol
        proxies, then save parameters keyed arg:/aux: by graph role, so
        SymbolBlock.imports / Module.load round-trip.  Multi-input blocks
        (seq2seq src/tgt, ...) pass their input names via `input_names`."""
        from .. import symbol as _sym
        from ..ndarray import save as nd_save

        out = self(*[_sym.var(n) for n in input_names])
        if isinstance(out, (list, tuple)):
            out = _sym.Group(out)
        out.save(f"{path}-symbol.json")

        aux_names = set(out.list_auxiliary_states())
        save_dict = {}
        for param in self.collect_params().values():
            if param._data is None:
                raise MXNetError(
                    f"export: parameter {param.name!r} is not initialized "
                    "(run one forward to resolve deferred shapes first)")
            arr = param._reduce()
            key = (f"aux:{param.name}" if param.name in aux_names
                   else f"arg:{param.name}")
            save_dict[key] = arr
        nd_save(f"{path}-{epoch:04d}.params", save_dict)
        return out


class SymbolBlock(HybridBlock):
    """Construct a block from a symbol graph (reference: gluon/block.py
    SymbolBlock.imports ~L900).

    The symbol's whole graph runs as one pure jax function through the
    imperative dispatch layer, so autograd recording, tracing inside an
    outer HybridBlock, and jit all work unchanged."""

    def __init__(self, outputs, inputs, params=None):
        super().__init__(prefix="", params=params)
        from .. import symbol as _sym

        if isinstance(outputs, (list, tuple)):
            outputs = _sym.Group(outputs)
        if not isinstance(inputs, (list, tuple)):
            inputs = [inputs]
        self._sym = outputs
        self._sym_input_names = [s.name for s in inputs]
        arg_names = outputs.list_arguments()
        self._sym_aux_names = list(outputs.list_auxiliary_states())
        self._sym_param_names = [n for n in arg_names
                                 if n not in self._sym_input_names]
        for n in self._sym_param_names:
            p = self.params.get(n, grad_req="write", allow_deferred_init=True)
            self._reg_params[n] = p
        for n in self._sym_aux_names:
            p = self.params.get(n, grad_req="null", allow_deferred_init=True)
            self._reg_params[n] = p

    @staticmethod
    def imports(symbol_file, input_names, param_file=None, ctx=None):
        from .. import symbol as _sym
        from ..context import current_context

        sym = _sym.load(symbol_file)
        if isinstance(input_names, str):
            input_names = [input_names]
        inputs = [_sym.var(n) for n in input_names]
        ret = SymbolBlock(sym, inputs)
        if param_file is not None:
            from .. import ndarray as nd

            raw = nd.load(param_file)
            arg, aux = {}, {}
            for k, v in raw.items():
                tp, _, name = k.partition(":")
                (aux if tp == "aux" else arg)[name if tp in ("arg", "aux")
                                              else k] = v
            ctx = ctx or current_context()
            for name, val in {**arg, **aux}.items():
                if name in ret._reg_params:
                    ret._reg_params[name]._load_init(val, ctx=ctx)
        return ret

    def _infer_sym_param_shapes(self, *args):
        shapes = {n: a.shape
                  for n, a in zip(self._sym_input_names, args)}
        arg_shapes, _, aux_shapes = self._sym.infer_shape(**shapes)
        arg_names = self._sym.list_arguments()
        for name, shp in zip(arg_names, arg_shapes):
            if name in self._reg_params:
                self._reg_params[name]._set_shape_if_deferred(shp)
                self._reg_params[name]._finish_deferred_init()
        for name, shp in zip(self._sym_aux_names, aux_shapes):
            self._reg_params[name]._set_shape_if_deferred(shp)
            self._reg_params[name]._finish_deferred_init()

    def forward(self, x, *args):
        from .. import autograd
        from .. import random as _rng
        from .. import symbol as _sym
        from ..ops import registry as _reg
        from ..symbol.symbol import build_graph_eval

        if isinstance(x, _sym.Symbol):
            # symbol trace (re-export path): splice the stored graph onto
            # the incoming symbols by input-variable name
            mapping = dict(zip(self._sym_input_names, [x, *args]))
            return self._sym(**mapping)

        ctx = x.context
        try:
            params = {n: p.data(ctx) for n, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._infer_sym_param_shapes(x, *args)
            params = {n: p.data(ctx) for n, p in self._reg_params.items()}

        training = autograd.is_training()
        eval_fn = build_graph_eval(self._sym._entries, training)
        key = _rng.next_key()
        data_nds = [x, *args]
        names = (self._sym_input_names
                 + [n for n in params])
        input_nds = data_nds + [params[n] for n in params]
        aux_upd = list(self._sym_aux_names) if training else []
        n_out = len(self._sym.list_outputs())

        def fn(*arrays):
            vals = dict(zip(names, arrays))
            outs, aux_updates = eval_fn(vals, key)
            flat = tuple(outs) + tuple(aux_updates.get(n, vals[n])
                                       for n in aux_upd)
            # single output unwraps: the tape passes a bare cotangent for
            # one-output nodes, so the vjp structure must match
            return flat[0] if len(flat) == 1 else flat

        results = _reg.invoke_fn(fn, input_nds)
        if not isinstance(results, (list, tuple)):
            results = [results]
        outs, aux_vals = results[:n_out], results[n_out:]
        for n, v in zip(aux_upd, aux_vals):
            self._reg_params[n].set_data(v.detach())
        return outs[0] if n_out == 1 else list(outs)

    def hybrid_forward(self, F, *args, **kwargs):
        raise NotImplementedError
