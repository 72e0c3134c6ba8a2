"""Fused optimizer apply: ONE jitted XLA call updates every dense parameter.

The per-param ``Updater`` dispatches one jitted update kernel per parameter
per step — for ResNet-50 that is ~160 tiny XLA dispatches of pure host-side
overhead (the kernels themselves are microseconds).  This module collapses
the whole optimizer tail into a single executable per (optimizer class,
hyperparam signature): weights/grads/states flatten into pytrees and the
entire update runs as one ``jax.jit`` call with donated weight+state
buffers, the fusion argument of TVM (arXiv:1802.04799) and Tensor
Processing Primitives (arXiv:2104.05755) applied to the optimizer step.

Design rules keeping parity with the per-param path exact:

  * the fused kernels ARE the registered per-param ops
    (``ops/optimizer_ops.py``) — same formulas, traced once over all
    params instead of jitted once per param, so fp32 results are
    bit-identical;
  * per-step scalars (lr after schedule/mults, wd, rescale_grad, Adam's
    bias-corrected lr) enter as TRACED arguments — a scheduler changing
    lr every step never retraces; structural hypers (momentum on/off,
    clip_gradient, centered) are static and key the executable cache;
  * state layout is the per-param ``Updater``'s own ``states`` dict
    (this class subclasses it), so save/load_states, the sparse
    fallback, and the ``MX_FUSED_UPDATE=0`` kill switch all see one
    state representation;
  * anything the fused path cannot express — row_sparse grads, unknown
    optimizer classes, mismatched weight/grad devices, exotic state
    shapes — falls back to the per-param update for JUST those params.

Multi-precision (bf16/fp16 weight + fp32 master in the state) fuses too:
the master updates in fp32 and the low-precision weight is one cast, as
in the ``mp_*`` reference ops.

``MX_FUSED_UPDATE=0`` disables the whole path (``get_updater`` then
returns the plain per-param ``Updater``).
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional

import numpy as np

from .. import engine
from .. import memwatch
from .. import telemetry
from .optimizer import Optimizer, Updater

__all__ = ["FusedUpdater", "fused_enabled"]


def fused_enabled() -> bool:
    """MX_FUSED_UPDATE kill switch (default: on)."""
    return os.environ.get("MX_FUSED_UPDATE", "1").lower() not in (
        "0", "false", "off")


# ---------------------------------------------------------------------------
# per-optimizer fused specs
#
# A spec answers three questions for its optimizer class:
#   static(opt)            -> hashable structural hypers (executable key)
#   kind(opt, w, state)    -> per-param update variant, or None (fall back)
#   scalars(opt, index)    -> per-step traced scalars for this param
#   apply(static, kind, w, g, s, sc, rescale) -> (new_w, new_state)
# `apply` runs INSIDE the jit trace; it must only branch on static/kind.
# ---------------------------------------------------------------------------
_SPECS: Dict[str, type] = {}


def _register_spec(cls):
    _SPECS[cls.opt_name] = cls
    return cls


_ND_CLASSES = None  # (NDArray, BaseSparseNDArray), resolved on first use —
# lazy like the rest of the optimizer package (circular-import order), but
# cached because kind() probes run per param per step


def _nd_classes():
    global _ND_CLASSES
    if _ND_CLASSES is None:
        from ..ndarray import NDArray
        from ..ndarray.sparse import BaseSparseNDArray

        _ND_CLASSES = (NDArray, BaseSparseNDArray)
    return _ND_CLASSES


def _is_nd(x) -> bool:
    dense, sparse = _nd_classes()
    return isinstance(x, dense) and not isinstance(x, sparse)


def _clip(opt) -> float:
    return float(opt.clip_gradient) if opt.clip_gradient is not None else -1.0


@_register_spec
class _SGDSpec:
    opt_name = "SGD"

    @staticmethod
    def static(opt):
        return (float(opt.momentum), _clip(opt))

    @staticmethod
    def kind(opt, weight, state):
        if state is None:
            return "plain"
        if _is_nd(state):
            return "mom"
        if (isinstance(state, tuple) and len(state) == 2
                and _is_nd(state[0]) and state[0].shape == weight.shape):
            if state[1] is None:
                return "mp"
            if _is_nd(state[1]):
                return "mp_mom"
        return None

    @staticmethod
    def scalars(opt, index):
        return (opt._get_lr(index), opt._get_wd(index))

    @staticmethod
    def apply(static, kind, w, g, s, sc, rescale):
        from ..ops import optimizer_ops as oo

        momentum, clip = static
        lr, wd = sc
        kw = dict(lr=lr, wd=wd, rescale_grad=rescale, clip_gradient=clip)
        if kind == "plain":
            return oo.sgd_update(w, g, **kw), None
        if kind == "mom":
            return oo.sgd_mom_update(w, g, s, momentum=momentum, **kw)
        if kind == "mp":
            nw, n32 = oo.mp_sgd_update(w, g, s[0], **kw)
            return nw, (n32, None)
        nw, nm, n32 = oo.mp_sgd_mom_update(w, g, s[1], s[0],
                                           momentum=momentum, **kw)
        return nw, (n32, nm)


@_register_spec
class _AdamSpec:
    opt_name = "Adam"

    @staticmethod
    def static(opt):
        return (float(opt.beta1), float(opt.beta2), float(opt.epsilon),
                _clip(opt))

    @staticmethod
    def kind(opt, weight, state):
        if not (isinstance(state, tuple) and len(state) == 2):
            return None
        mp_shape = getattr(state[0], "shape", None) == weight.shape
        if opt.multi_precision and _is_nd(state[0]) and mp_shape \
                and isinstance(state[1], tuple) and len(state[1]) == 2 \
                and all(_is_nd(x) for x in state[1]):
            return "mp"
        if opt.multi_precision and mp_shape:
            # the generic base-class mp path would engage (and, for fp32
            # weights, misread (mean, var) as (master, state)) — keep that
            # exact per-param behavior instead of guessing
            return None
        if all(_is_nd(x) for x in state):
            return "plain"
        return None

    @staticmethod
    def scalars(opt, index):
        import math

        t = opt._index_update_count[index]
        # bias correction folded into lr, exactly as Adam.update does
        lr = opt._get_lr(index) * math.sqrt(1.0 - opt.beta2 ** t) \
            / (1.0 - opt.beta1 ** t)
        return (lr, opt._get_wd(index))

    @staticmethod
    def apply(static, kind, w, g, s, sc, rescale):
        from ..ops import optimizer_ops as oo

        beta1, beta2, eps, clip = static
        lr, wd = sc
        kw = dict(lr=lr, beta1=beta1, beta2=beta2, epsilon=eps, wd=wd,
                  rescale_grad=rescale, clip_gradient=clip)
        if kind == "plain":
            mean, var = s
            nw, nmean, nvar = oo.adam_update(w, g, mean, var, **kw)
            return nw, (nmean, nvar)
        master, (mean, var) = s
        n32, nmean, nvar = oo.adam_update(master, g, mean, var, **kw)
        return n32.astype(w.dtype), (n32, (nmean, nvar))


@_register_spec
class _RMSPropSpec:
    opt_name = "RMSProp"

    @staticmethod
    def static(opt):
        cw = float(opt.clip_weights) if opt.clip_weights is not None else -1.0
        return (float(opt.gamma1), float(opt.gamma2), float(opt.epsilon),
                _clip(opt), cw)

    @staticmethod
    def kind(opt, weight, state):
        if _is_nd(state):
            return "plain"
        if isinstance(state, tuple) and len(state) == 3 \
                and all(_is_nd(x) for x in state):
            return "centered"
        if (opt.multi_precision and isinstance(state, tuple)
                and len(state) == 2 and _is_nd(state[0])
                and state[0].shape == weight.shape):
            if _is_nd(state[1]):
                return "mp_plain"
            if isinstance(state[1], tuple) and len(state[1]) == 3 \
                    and all(_is_nd(x) for x in state[1]):
                return "mp_centered"
        return None

    @staticmethod
    def scalars(opt, index):
        return (opt._get_lr(index), opt._get_wd(index))

    @staticmethod
    def apply(static, kind, w, g, s, sc, rescale):
        from ..ops import optimizer_ops as oo

        gamma1, gamma2, eps, clip, cw = static
        lr, wd = sc
        kw = dict(lr=lr, wd=wd, rescale_grad=rescale, clip_gradient=clip,
                  epsilon=eps, clip_weights=cw)
        if kind == "plain":
            nw, nn = oo.rmsprop_update(w, g, s, gamma1=gamma1, **kw)
            return nw, nn
        if kind == "centered":
            n, g_buf, delta = s
            nw, nn, ng, nd_ = oo.rmspropalex_update(
                w, g, n, g_buf, delta, gamma1=gamma1, gamma2=gamma2, **kw)
            return nw, (nn, ng, nd_)
        master, inner = s
        if kind == "mp_plain":
            n32, nn = oo.rmsprop_update(master, g, inner, gamma1=gamma1, **kw)
            return n32.astype(w.dtype), (n32, nn)
        n, g_buf, delta = inner
        n32, nn, ng, nd_ = oo.rmspropalex_update(
            master, g, n, g_buf, delta, gamma1=gamma1, gamma2=gamma2, **kw)
        return n32.astype(w.dtype), (n32, (nn, ng, nd_))


# ---------------------------------------------------------------------------
# state pytree <-> NDArray structure
# ---------------------------------------------------------------------------
def _state_arrays(s):
    if s is None:
        return None
    if isinstance(s, tuple):
        return tuple(_state_arrays(x) for x in s)
    return s._data


def _write_state(s, new):
    if s is None:
        return
    if isinstance(s, tuple):
        for x, nx in zip(s, new):
            _write_state(x, nx)
        return
    s._set_data(new)


def _flat_state_arrays(updater):
    """memwatch provider: every optimizer-state buffer this updater owns
    (momenta, Adam moments, fp32 masters), flattened out of the per-param
    state tuples — the "optimizer" slice of the live-array census."""
    out = []

    def walk(s):
        if s is None:
            return
        if isinstance(s, tuple):
            for x in s:
                walk(x)
            return
        data = getattr(s, "_data", None)
        if data is not None:
            out.append(data)

    for s in updater.states.values():
        walk(s)
    return out


class FusedUpdater(Updater):
    """Per-param-compatible updater with a fused ``apply([...])`` fast path.

    ``__call__`` is the inherited per-param update (kvstore per-key pushes,
    sparse grads).  ``apply(entries)`` — entries being ``(index, grad,
    weight)`` triples — partitions the batch into fused-eligible params
    (dense, known optimizer, recognized state layout) and per-param
    fallbacks, then updates every fused param in ONE jitted call per
    device.  ``last_info`` records what the most recent ``apply`` did.
    """

    def __init__(self, optimizer: Optimizer):
        super().__init__(optimizer)
        self._fn_cache: Dict[Any, Any] = {}
        self.last_info: Optional[Dict[str, int]] = None
        # live-array census: the states dict is the "optimizer" category
        memwatch.register("optimizer", self, _flat_state_arrays)

    # -- fused executable cache -------------------------------------------
    def _jitted(self, spec, static, kinds, donate):
        key = (spec.opt_name, static, kinds, donate)
        fn = self._fn_cache.get(key)
        if fn is None:
            import jax

            apply_one = spec.apply

            def fused_fn(ws, gs, ss, scalars, rescale):
                # scalars is ONE stacked (n_params, k) array — python-float
                # leaves would force jax's slow dispatch path (a host->device
                # convert per scalar per step); unstacking happens at trace
                # time, so the executable sees plain f32 scalars
                new_ws: List = []
                new_ss: List = []
                for i, (kind, w, g, s) in enumerate(zip(kinds, ws, gs, ss)):
                    nw, ns = apply_one(static, kind, w, g, s,
                                       tuple(scalars[i]), rescale)
                    new_ws.append(nw)
                    new_ss.append(ns)
                return tuple(new_ws), tuple(new_ss)

            # mxlint: disable=retrace-hazard — cached in _fn_cache per
            # (optimizer, static hypers, kinds, donate); built once per key
            fn = jax.jit(fused_fn,
                         donate_argnums=(0, 2) if donate else ())
            self._fn_cache[key] = fn
        return fn

    # -- batch apply -------------------------------------------------------
    def apply(self, entries, donate: bool = False) -> Dict[str, int]:
        """Update a batch of ``(index, grad, weight)`` triples.

        Fused-eligible params update in one jitted call per distinct
        device; the rest take the per-param path.  ``donate=True`` donates
        the weight/state buffers to XLA on non-CPU backends (the caller
        asserts nothing else reads the old buffers — true for Trainer-owned
        parameters, NOT for kvstore-stored values aliased by pulls).
        Returns (and stores in ``last_info``) the dispatch accounting.
        """
        with telemetry.span("fused_apply", n_params=len(entries)):
            return self._apply_impl(entries, donate)

    def _apply_impl(self, entries, donate: bool) -> Dict[str, int]:
        _dense, sparse_cls = _nd_classes()
        opt = self.optimizer
        spec = _SPECS.get(type(opt).__name__)
        fused: Dict[Any, List] = {}  # ctx -> [(index, g, w, state, kind)]
        fallback: List = []
        for index, grad, weight in entries:
            state = self._ensure_state(index, weight)
            kind = None
            if (spec is not None
                    and not isinstance(grad, sparse_cls)
                    and not isinstance(weight, sparse_cls)
                    and grad.context == weight.context):
                kind = spec.kind(opt, weight, state)
            if kind is None:
                fallback.append((index, grad, weight))
            else:
                fused.setdefault(weight.context, []).append(
                    (index, grad, weight, state, kind))
        info = {"n_params": len(entries), "n_fused": 0, "n_fallback": 0,
                "n_jitted_calls": 0, "nbytes": 0}
        for ctx, group in fused.items():
            info["nbytes"] += self._apply_group(spec, group, ctx, donate)
            info["n_jitted_calls"] += 1
            info["n_fused"] += len(group)
        for index, grad, weight in fallback:
            opt.update_multi_precision(index, weight, grad,
                                       self.states[index])
            info["n_fallback"] += 1
        self.last_info = info
        return info

    def _apply_group(self, spec, group, ctx, donate) -> int:
        opt = self.optimizer
        for index, _g, _w, _s, _k in group:
            opt._update_count(index)
        kinds = tuple(kind for *_x, kind in group)
        static = spec.static(opt)
        donate = bool(donate) and ctx.jax_device.platform != "cpu"
        # cold = this (optimizer, hypers, kinds, donate) executable is
        # about to be built: the first call below pays trace + XLA
        # compile and is booked as ONE compile event (never re-emitted)
        cold = (spec.opt_name, static, kinds, donate) not in self._fn_cache
        fn = self._jitted(spec, static, kinds, donate)
        ws = tuple(w._data for _i, _g, w, _s, _k in group)
        gs = tuple(g._data for _i, g, _w, _s, _k in group)
        ss = tuple(_state_arrays(s) for _i, _g, _w, s, _k in group)
        scalars = np.asarray([spec.scalars(opt, index)
                              for index, _g, _w, _s, _k in group],
                             dtype=np.float32)
        rescale = np.float32(opt.rescale_grad)
        t0 = time.perf_counter() if cold else 0.0
        new_ws, new_ss = fn(ws, gs, ss, scalars, rescale)
        if cold:
            memwatch.note_compile(
                f"FusedUpdater:{spec.opt_name}",
                ("FusedUpdater", spec.opt_name, static, kinds, donate,
                 tuple((tuple(w.shape), str(w.dtype)) for w in ws)),
                wall_s=time.perf_counter() - t0, site="fused",
                jitted=fn,
                args=memwatch.shape_structs((ws, gs, ss, scalars,
                                             rescale)),
                n_params=len(group))
        if engine.is_naive():
            import jax

            # mxlint: disable=hot-sync — MXNET_ENGINE_TYPE=NaiveEngine
            # CONTRACT: synchronous per-op dispatch, sync is the feature
            jax.block_until_ready(new_ws)
        nbytes = 0
        for (index, _g, w, s, _k), nw, ns in zip(group, new_ws, new_ss):
            nbytes += nw.nbytes
            w._set_data(nw)
            _write_state(s, ns)
        return nbytes
