"""Gluon Parameter / ParameterDict.

Reference parity: python/mxnet/gluon/parameter.py (Parameter deferred init
~L300, per-context replication, grad_req handling; ParameterDict ~L500).

TPU-native notes: a Parameter holds one NDArray per context (data-parallel
replication, as the reference does for multi-GPU); each NDArray is an
immutable jax buffer mutated by swap, so optimizer updates never invalidate
in-flight readers.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import List, Optional

import numpy as np

from ..base import MXNetError, dtype_np
from ..context import Context, cpu, current_context
from .. import initializer as init_mod

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError"]


class DeferredInitializationError(MXNetError):
    """Parameter accessed before its shape is known (reference ~L40)."""


# ---------------------------------------------------------------------------
# CachedOp trace substitution: while a HybridBlock trace is active, Parameter
# .data() returns the traced value instead of the concrete buffer, and aux
# mutations (BatchNorm running stats) are collected instead of applied.
# This replaces the reference's symbol-proxy tracing (gluon/block.py
# _build_cache ~L750) with jaxpr tracing.
# ---------------------------------------------------------------------------
import threading as _threading


class _TraceState(_threading.local):
    def __init__(self):
        self.active = None  # None or dict with 'params', 'aux', 'ctx'


_trace = _TraceState()


def trace_active() -> bool:
    return _trace.active is not None


def begin_trace(param_map, ctx):
    prev = _trace.active
    _trace.active = {"params": param_map, "aux": [], "ctx": ctx}
    return prev


def end_trace(prev):
    state = _trace.active
    _trace.active = prev
    return state


def record_aux_update(param: "Parameter", value) -> None:
    """Aux-state write: collected during trace, applied by buffer swap in
    eager mode (on the value's context)."""
    if _trace.active is not None:
        _trace.active["aux"].append((param, value))
    else:
        ctx = value.context
        target = param._data.get(ctx) if param._data else None
        if target is None:
            param._check_initialized(ctx)
        target._set_data(value._data)


def _shape_known(shape) -> bool:
    return shape is not None and all(s > 0 for s in shape)


class Parameter:
    def __init__(self, name: str, grad_req: str = "write", shape=None,
                 dtype="float32", lr_mult: float = 1.0, wd_mult: float = 1.0,
                 init=None, allow_deferred_init: bool = False,
                 differentiable: bool = True, stype: str = "default",
                 grad_stype: str = "default"):
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = dtype
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self.stype = stype
        self.grad_stype = grad_stype
        self._grad_req = grad_req if differentiable else "null"
        self._data: Optional[OrderedDict] = None  # ctx -> NDArray
        self._grad: Optional[OrderedDict] = None
        self._deferred = None  # (init, ctx_list) awaiting shape
        self._trainer = None

    # ------------------------------------------------------------------
    @property
    def grad_req(self) -> str:
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req: str):
        if req not in ("write", "add", "null"):
            raise MXNetError(f"invalid grad_req {req!r}")
        if self._grad_req == req:
            return
        self._grad_req = req
        if req == "null":
            self._grad = None
        elif self._data is not None:
            self._init_grad()

    def __repr__(self):
        return f"Parameter {self.name} (shape={self.shape}, dtype={self.dtype})"

    # ------------------------------------------------------------------
    # initialization
    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit: bool = False) -> None:
        """Allocate + fill per-context arrays (reference: _init_impl ~L300)."""
        if self._data is not None and not force_reinit:
            return
        if ctx is None:
            ctx = [current_context()]
        if isinstance(ctx, Context):
            ctx = [ctx]
        eff_init = init or self.init or default_init
        if not _shape_known(self.shape):
            if self.allow_deferred_init:
                self._deferred = (eff_init, list(ctx))
                return
            raise MXNetError(
                f"cannot initialize {self.name}: shape {self.shape} unknown; "
                "set allow_deferred_init=True or specify the full shape")
        self._init_impl(eff_init, ctx)

    def _init_impl(self, eff_init, ctx_list) -> None:
        import jax

        from ..ndarray import NDArray

        initializer = (eff_init if isinstance(eff_init, (init_mod.Initializer,
                                                         init_mod.Mixed))
                       else init_mod.create(eff_init))
        host = initializer.init_array(self.name, self.shape, self.dtype)
        self._data = OrderedDict()
        for ctx in ctx_list:
            self._data[ctx] = NDArray(jax.device_put(host, ctx.jax_device),
                                      ctx=ctx)
        self._deferred = None
        if self._grad_req != "null":
            self._init_grad()

    def _init_grad(self) -> None:
        import jax.numpy as jnp

        from ..ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray
        from .. import autograd

        self._grad = OrderedDict()
        for ctx, data in self._data.items():
            if self.grad_stype == "row_sparse":
                # sparse grad buffer (reference: grad_stype='row_sparse'
                # on sparse-grad Embedding weights); autograd writes
                # (indices, values) into it without densifying
                g = RowSparseNDArray(
                    jnp.zeros((0,) + tuple(data.shape[1:]), data._data.dtype),
                    {"indices": jnp.zeros((0,), jnp.int32)},
                    tuple(data.shape), ctx=ctx)
            else:
                g = NDArray(jnp.zeros_like(data._data), ctx=ctx)
            self._grad[ctx] = g
            data._grad = g
            data._grad_req = self._grad_req
            autograd.register_leaf(data)

    def release_grad(self) -> None:
        """Free the gradient buffers (as large as the parameter itself).  A
        compiled step that owns the training state never writes them;
        ``_init_grad`` brings them back (``DataParallelStep.sync_to_block``
        does, and so does the first ``grad()``)."""
        if self._grad is None:
            return
        for data in self._data.values():
            data._grad = None
        self._grad = None

    def _finish_deferred_init(self) -> None:
        if self._deferred is None:
            return
        if not _shape_known(self.shape):
            raise DeferredInitializationError(
                f"parameter {self.name} shape still unknown")
        eff_init, ctx_list = self._deferred
        self._init_impl(eff_init, ctx_list)

    def _set_shape_if_deferred(self, shape) -> None:
        """Adopt an inferred shape, honoring any user-fixed dims."""
        if self.shape is None:
            self.shape = tuple(shape)
            return
        merged = []
        for have, got in zip(self.shape, shape):
            if have > 0 and got > 0 and have != got:
                raise MXNetError(
                    f"inferred shape {shape} incompatible with declared "
                    f"{self.shape} for parameter {self.name}")
            merged.append(have if have > 0 else got)
        self.shape = tuple(merged)

    # ------------------------------------------------------------------
    # access
    # ------------------------------------------------------------------
    def _check_initialized(self, ctx=None):
        if self._data is None:
            if self._deferred is not None:
                raise DeferredInitializationError(
                    f"parameter {self.name} deferred (shape unknown yet)")
            raise MXNetError(
                f"parameter {self.name} has not been initialized; call "
                ".initialize() first")
        if ctx is not None and ctx not in self._data:
            raise MXNetError(
                f"parameter {self.name} not initialized on {ctx}; it lives on "
                f"{list(self._data)}")

    def data(self, ctx: Optional[Context] = None):
        if _trace.active is not None:
            sub = _trace.active["params"].get(self)
            if sub is not None:
                return sub
        if ctx is None:
            self._check_initialized()
            ctx = next(iter(self._data))
        else:
            self._check_initialized(ctx)
        return self._data[ctx]

    def list_data(self) -> List:
        self._check_initialized()
        return list(self._data.values())

    def grad(self, ctx: Optional[Context] = None):
        grads = self._live_grad()
        return grads[next(iter(grads)) if ctx is None else ctx]

    def _live_grad(self) -> OrderedDict:
        """ctx -> gradient buffer; buffers a compiled step released
        (``release_grad``) come back here."""
        self._check_initialized()
        if self._grad_req == "null":
            raise MXNetError(f"parameter {self.name} has grad_req='null'")
        if self._grad is None:
            self._init_grad()
        return self._grad

    def list_grad(self) -> List:
        return list(self._live_grad().values())

    def list_ctx(self) -> List[Context]:
        self._check_initialized()
        return list(self._data.keys())

    def set_data(self, data) -> None:
        """Overwrite the parameter value on every context."""
        import jax

        from ..ndarray import NDArray

        self.shape = tuple(data.shape)
        if self._data is None:
            # loading into a not-yet-initialized parameter acts as its
            # initialization (reference: Parameter._load_init)
            if self._deferred is not None:
                _, ctx_list = self._deferred
            else:
                ctx_list = [current_context()]
            host = data.asnumpy() if isinstance(data, NDArray) else np.asarray(data)
            self._data = OrderedDict()
            for ctx in ctx_list:
                self._data[ctx] = NDArray(
                    jax.device_put(host.astype(dtype_np(self.dtype)),
                                   ctx.jax_device), ctx=ctx)
            self._deferred = None
            if self._grad_req != "null":
                self._init_grad()
            return
        src = data.asnumpy() if isinstance(data, NDArray) else np.asarray(data)
        for ctx, nd in self._data.items():
            nd._set_data(jax.device_put(src.astype(np.dtype(nd._data.dtype)),
                                        ctx.jax_device))

    def _reduce(self):
        """One host-complete copy of the value (reference Parameter._reduce:
        device-0 copy for dense params)."""
        self._check_initialized()
        return next(iter(self._data.values()))

    def _load_init(self, value, ctx=None, cast_dtype=False) -> None:
        shape = getattr(value, "shape", None)
        if _shape_known(self.shape) and tuple(self.shape) != tuple(shape):
            raise MXNetError(
                f"parameter {self.name} shape {self.shape} != loaded "
                f"{tuple(shape)}")
        if ctx is not None and self._data is None:
            # loading initializes on the requested ctx, not current_context()
            ctx_list = list(ctx) if isinstance(ctx, (list, tuple)) else [ctx]
            init = self._deferred[0] if self._deferred else None
            self._deferred = (init, ctx_list)
        self.set_data(value)
        if ctx is not None and self._data is not None:
            ctx_list = list(ctx) if isinstance(ctx, (list, tuple)) else [ctx]
            if list(self._data.keys()) != ctx_list:
                self.reset_ctx(ctx_list)

    def zero_grad(self) -> None:
        if self._grad is None:
            return
        import jax.numpy as jnp

        from ..ndarray.sparse import RowSparseNDArray

        for g in self._grad.values():
            if isinstance(g, RowSparseNDArray):
                g.zero()
            else:
                g._set_data(jnp.zeros_like(g._data))

    def reset_ctx(self, ctx) -> None:
        if isinstance(ctx, Context):
            ctx = [ctx]
        self._check_initialized()
        host = next(iter(self._data.values())).asnumpy()
        import jax

        from ..ndarray import NDArray

        self._data = OrderedDict(
            (c, NDArray(jax.device_put(host, c.jax_device), ctx=c)) for c in ctx
        )
        if self._grad_req != "null":
            self._init_grad()

    def cast(self, dtype) -> None:
        self.dtype = dtype
        if self._data is None:
            return
        import jax

        for nd in self._data.values():
            nd._set_data(nd._data.astype(dtype_np(dtype)))
        if self._grad:
            for g in self._grad.values():
                g._set_data(g._data.astype(dtype_np(dtype)))

    def var(self):
        """A symbol variable carrying this parameter's name (used when a
        HybridBlock is traced into a Symbol graph for export).  Cached so
        repeated calls (weight sharing within one trace) return the SAME
        graph node — otherwise list_arguments would show duplicates."""
        from .. import symbol as _sym

        cached = getattr(self, "_var_sym", None)
        if cached is None:
            cached = _sym.var(self.name, shape=self.shape, dtype=self.dtype)
            self._var_sym = cached
        return cached


class Constant(Parameter):
    """Non-learnable constant parameter (reference: gluon/parameter.py Constant)."""

    def __init__(self, name, value):
        from ..ndarray import NDArray

        if isinstance(value, NDArray):
            value = value.asnumpy()
        value = np.asarray(value)
        self.value = value

        class _CInit(init_mod.Initializer):
            def _init_weight(self, _name, arr):
                arr[:] = value

        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, init=_CInit())


class ParameterDict:
    """Prefix-scoped parameter collection (reference ~L500)."""

    def __init__(self, prefix: str = "", shared: Optional["ParameterDict"] = None):
        self._prefix = prefix
        self._params: "OrderedDict[str, Parameter]" = OrderedDict()
        self._shared = shared

    @property
    def prefix(self) -> str:
        return self._prefix

    def __repr__(self):
        items = "\n".join(f"  {v}" for v in self._params.values())
        return f"ParameterDict '{self._prefix}' (\n{items}\n)"

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def __getitem__(self, key) -> Parameter:
        return self._params[key]

    def __contains__(self, key) -> bool:
        return key in self._params

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def get(self, name: str, **kwargs) -> Parameter:
        """Get or create `prefix+name` (reference: ParameterDict.get)."""
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            param = Parameter(full, **kwargs)
            self._params[full] = param
        else:
            for k, v in kwargs.items():
                if k == "shape" and v is not None:
                    if param.shape is None:
                        param.shape = tuple(v)
                elif k == "init" and v is not None and param.init is None:
                    param.init = v
        return param

    def get_constant(self, name: str, value=None) -> Constant:
        full = self._prefix + name
        param = self._get_impl(full)
        if param is None:
            param = Constant(full, value)
            self._params[full] = param
        return param

    def _get_impl(self, full_name):
        if full_name in self._params:
            return self._params[full_name]
        if self._shared is not None and full_name in self._shared:
            self._params[full_name] = self._shared[full_name]
            return self._params[full_name]
        return None

    def update(self, other: "ParameterDict") -> None:
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError(f"duplicate parameter name {k}")
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False) -> None:
        default = init if init is not None else init_mod.Uniform(0.07)
        for param in self.values():
            param.initialize(None, ctx, default_init=default,
                             force_reinit=force_reinit)

    def zero_grad(self) -> None:
        for param in self.values():
            param.zero_grad()

    def reset_ctx(self, ctx) -> None:
        for param in self.values():
            param.reset_ctx(ctx)

    def setattr(self, name, value) -> None:
        for param in self.values():
            setattr(param, name, value)

    def save(self, filename: str, strip_prefix: str = "") -> None:
        from .. import ndarray as nd

        arg_dict = {}
        for param in self.values():
            block = param.list_data()
            weight = block[0]
            name = param.name
            if strip_prefix and name.startswith(strip_prefix):
                name = name[len(strip_prefix):]
            arg_dict[name] = weight
        nd.save(filename, arg_dict)

    def load(self, filename: str, ctx=None, allow_missing: bool = False,
             ignore_extra: bool = False, restore_prefix: str = "",
             loaded=None) -> None:
        from .. import ndarray as nd

        if loaded is None:
            loaded = nd.load(filename)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        if not allow_missing:
            for name in self.keys():
                if name not in loaded:
                    raise MXNetError(f"parameter {name} missing in {filename}")
        for name, value in loaded.items():
            if name not in self._params:
                if ignore_extra:
                    continue
                raise MXNetError(f"parameter {name} in file not in model")
            self._params[name].set_data(value)
