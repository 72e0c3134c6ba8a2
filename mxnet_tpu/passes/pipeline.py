"""Ordered, composable, fingerprinted graph passes (docs/PRECISION.md
§Pass pipeline).

PR 15 proved the single dispatch point (``ops/registry._invoke_impl``)
can rewrite the whole traced graph — but AMP and int8 quant were each a
one-off module global: they could not be ordered, composed, or
fingerprinted together.  This module makes graph rewriting first-class,
the Relay pass-manager model (arXiv:1810.00952) shrunk to this repo's
trace-time reality:

  * a :class:`GraphPass` is a named, individually-toggleable rewrite
    whose effect is a trace-time scope (``scope()``) plus a structural
    ``signature()``;
  * a :class:`PassPipeline` is an ORDERED list of passes with ONE shared
    ``signature()`` that joins ``_fingerprint_parts`` — any pass
    config, toggle, or ORDER change produces a different fingerprint on
    the compile events and checkpoint layouts that carry it;
  * a disabled pass is bitwise absent: it contributes nothing to the
    signature and nothing to the trace (``wrap_apply``/``scope`` skip
    it), so pipeline-with-pass-disabled traces a byte-identical program
    to the pre-pipeline path.

Pass classes register by name (:func:`register_pass_type`); an unknown
name raises naming the registered set.  The pipeline serializes to JSON
(name + config per pass, order preserved) and rides checkpoint layouts
next to the Plan.
"""
from __future__ import annotations

import contextlib
from typing import Dict, List, Optional, Tuple

from ..base import MXNetError

__all__ = ["GraphPass", "PassPipeline", "register_pass_type",
           "available_passes", "resolve_pass_type", "apply_env_toggles"]

_PASS_TYPES: Dict[str, type] = {}


def register_pass_type(cls):
    """Class decorator: register ``cls`` under its ``name`` attribute so
    ``PassPipeline.from_json`` / MX_PASSES can resolve it."""
    name = getattr(cls, "name", None)
    if not name:
        raise MXNetError("register_pass_type: pass class needs a non-empty "
                         "'name' attribute")
    if name in _PASS_TYPES and _PASS_TYPES[name] is not cls:
        raise MXNetError(f"graph pass {name!r} registered twice")
    _PASS_TYPES[name] = cls
    return cls


def available_passes() -> List[str]:
    return sorted(_PASS_TYPES)


def resolve_pass_type(name: str) -> type:
    try:
        return _PASS_TYPES[name]
    except KeyError:
        raise MXNetError(
            f"unknown graph pass {name!r}: registered passes are "
            f"{available_passes()}") from None


class GraphPass:
    """One named graph rewrite.  Subclasses set ``name`` (the registry
    key) and override ``signature``/``scope`` (and optionally
    ``wrap_apply``, ``metadata``, ``config_json``/``from_config``)."""

    name: str = ""

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)

    # -- identity ------------------------------------------------------
    def signature(self) -> Tuple:
        """Structural identity of this pass's CONFIG (hashable, restart-
        stable — the fingerprint contract).  The pipeline prefixes the
        pass name, so configs need not repeat it."""
        return ()

    # -- trace-time effect ---------------------------------------------
    def scope(self):
        """Context manager activating the pass's trace-time effect
        (dispatch hooks / precision scopes).  Default: no effect."""
        return contextlib.nullcontext()

    def wrap_apply(self, apply_fn):
        """Wrap a block-apply ``fn(params, key, *inputs)`` so its trace
        runs under this pass.  Default: enter ``scope()`` around the
        call — passes with boundary behavior (AMP's f32 widen) override."""
        scope = self.scope

        def passed_apply(params, key, *inputs):
            with scope():
                return apply_fn(params, key, *inputs)

        return passed_apply

    # -- seams ---------------------------------------------------------
    def metadata(self) -> dict:
        """Declarative facts downstream passes may consult (e.g. the AMP
        pass publishes its backward-graph cast decisions here so a future
        quantized-grads pass has a home).  Never affects the traced
        program or the fingerprint."""
        return {}

    # -- serialization -------------------------------------------------
    def config_json(self) -> dict:
        return {}

    @classmethod
    def from_config(cls, rec: dict) -> "GraphPass":
        return cls()

    def __repr__(self):
        state = "on" if self.enabled else "off"
        return f"<GraphPass {self.name} {state}>"


class PassPipeline:
    """An ordered list of :class:`GraphPass` objects with one shared
    fingerprint.  Construct with passes in APPLICATION order: pass i's
    rewrite sees the graph produced under passes 0..i-1's scopes."""

    def __init__(self, passes=()):
        self.passes: List[GraphPass] = list(passes)
        seen = set()
        for p in self.passes:
            if not isinstance(p, GraphPass):
                raise MXNetError(
                    f"PassPipeline: {p!r} is not a GraphPass")
            if p.name in seen:
                raise MXNetError(
                    f"PassPipeline: duplicate pass {p.name!r} — a pipeline "
                    "holds each named pass at most once")
            seen.add(p.name)

    # -- access / toggling ---------------------------------------------
    def enabled(self) -> List[GraphPass]:
        return [p for p in self.passes if p.enabled]

    def names(self) -> List[str]:
        return [p.name for p in self.passes]

    def get(self, name: str) -> GraphPass:
        for p in self.passes:
            if p.name == name:
                return p
        raise MXNetError(
            f"PassPipeline: no pass named {name!r} in this pipeline "
            f"(has {self.names()}); registered passes are "
            f"{available_passes()}")

    def set_enabled(self, name: str, enabled: bool) -> "PassPipeline":
        self.get(name).enabled = bool(enabled)
        return self

    # -- identity -------------------------------------------------------
    def signature(self) -> Tuple:
        """ONE shared structural identity: (name, config) of every
        ENABLED pass, in order.  Joins the executable fingerprints
        (``DataParallelStep._fingerprint_parts`` hyper_sig, the serving
        engine fingerprint, the ``plan`` telemetry event) — order,
        toggle and config changes all split the fingerprint; a disabled
        pass is absent exactly as the pre-pipeline path was."""
        return ("passes",) + tuple(
            (p.name,) + tuple(p.signature()) for p in self.enabled())

    def fingerprint(self) -> str:
        from .. import memwatch

        return memwatch.fingerprint(self.signature())

    def metadata(self) -> dict:
        return {p.name: p.metadata() for p in self.passes}

    # -- trace-time application ----------------------------------------
    @contextlib.contextmanager
    def scope(self):
        """Enter every enabled pass's scope, pipeline order outermost-
        first.  With nothing enabled this is a no-op (the bitwise-off
        guarantee)."""
        with contextlib.ExitStack() as stack:
            for p in self.enabled():
                stack.enter_context(p.scope())
            yield

    def wrap_apply(self, apply_fn):
        """Wrap a block apply under every enabled pass.  Identity (the
        SAME function object) when nothing is enabled — the off path is
        byte-for-byte the pre-pipeline program."""
        live = self.enabled()
        for p in reversed(live):
            apply_fn = p.wrap_apply(apply_fn)
        return apply_fn

    # -- serialization -------------------------------------------------
    def to_json(self) -> list:
        return [{"pass": p.name, "enabled": bool(p.enabled),
                 "config": p.config_json()} for p in self.passes]

    @classmethod
    def from_json(cls, recs) -> "PassPipeline":
        passes = []
        for rec in recs or ():
            pcls = resolve_pass_type(rec["pass"])
            p = pcls.from_config(rec.get("config") or {})
            p.enabled = bool(rec.get("enabled", True))
            passes.append(p)
        return cls(passes)

    def __repr__(self):
        inner = ", ".join(
            p.name + ("" if p.enabled else "(off)") for p in self.passes)
        return f"<PassPipeline [{inner}]>"


def apply_env_toggles(pipeline: PassPipeline,
                      environ=None) -> PassPipeline:
    """MX_PASSES: comma-separated pass toggles applied to a constructed
    pipeline.  ``-name`` force-disables the named pass (a no-op when the
    pipeline doesn't carry it); a bare ``name`` asserts the pass is
    registered (reserved for future force-enable semantics — enabling
    needs pass-specific config, which env strings don't carry).  Any
    token naming an UNREGISTERED pass raises naming the registered set —
    a typoed knob must fail loudly, not silently serve the wrong
    program."""
    import os

    environ = environ if environ is not None else os.environ
    raw = (environ.get("MX_PASSES") or "").strip()
    if not raw:
        return pipeline
    for tok in raw.split(","):
        tok = tok.strip()
        if not tok:
            continue
        disable = tok.startswith("-")
        name = tok[1:] if disable else tok
        resolve_pass_type(name)  # unknown -> loud MXNetError
        if disable:
            for p in pipeline.passes:
                if p.name == name:
                    p.enabled = False
    return pipeline
