"""Calibrated int8 serving: rewrite Dense/Conv layers inside the serving
engine's traced prefill/decode graphs onto the ``ops/quantization.py``
int8 primitives.

``contrib/quantization.py`` already owns post-training calibration (the
naive/entropy ``_Calibrator`` over a ``_StreamingHist``) and eager
``QuantizedDense``/``QuantizedConv2D`` twins — but those re-dispatch
eagerly per layer per call, which is exactly the per-op overhead the
serving engine exists to remove.  This module produces a
:class:`QuantizedAdapter`: a wrapper around any
:class:`~mxnet_tpu.serving.engine.ServingAdapter` whose ``decode``/
``prefill`` run the SAME traced graphs as the wrapped adapter, except
every selected Dense/Conv layer lowers to int8 matmul/conv with int32
accumulation (MXU ``preferred_element_type=int32``) — so the engine
still books exactly ONE decode executable, now carrying the quantized
program (the *Tensor Processing Primitives* argument, arXiv:2104.05755,
applied as a TVM-style graph rewrite, arXiv:1802.04799).

Mechanics: the adapter pre-quantizes each selected layer's weight to an
int8 device buffer (params-bytes is where int8 serving pays off) and
activates :func:`~mxnet_tpu.precision.runtime.quant_scope` around the
wrapped adapter's traced bodies; ``gluon.nn.Dense``/``Conv2D`` consult
the scope in ``hybrid_forward`` and route through the int8 twin.
Activation ranges come from calibration (``calibrate``), observed via
eager forward-pre hooks exactly as ``contrib.quantization.quantize_net``
does.

The quantization signature (calib mode + per-layer thresholds) joins the
adapter ``signature()`` and therefore the engine's executable
fingerprint: compile events under different ``MX_QUANTIZE``/
``MX_QUANT_CALIB`` settings carry different names.  Int8
buffers register under the ``quantized`` memwatch census category.

The int4 path (:class:`Int4WeightAdapter`) lives next to int8: weight-
ONLY quantization — Dense/Conv weights packed 2 per byte with group-wise
f16 scales (``MX_QUANT_GROUP``), dequantized IN-TRACE by
``_contrib_dequantize_int4`` inside the engine's compiled decode/prefill
bodies.  No activation quantization, hence no calibration: decode is
weight-bandwidth bound, and ~0.14x weight bytes is the win.

Both adapters express their rewrite as a registered graph pass
(``passes/builtin``: ``quant_int8`` / ``quant_int4``) exposed via
``.passes`` — the serving engine builds its pipeline from that, and the
pass signature is what joins the engine's fingerprint.

Env surface: ``MX_QUANTIZE`` (``int8`` to enable, ``0``/unset off) with
``MX_QUANT_CALIB`` (``naive``/``entropy``, default naive) drives
:func:`maybe_quantize_adapter`; ``MX_SERVE_INT4`` (``1``/``int4`` on)
with ``MX_QUANT_GROUP`` (group size, default 32, even) drives
:func:`maybe_int4_adapter`.
"""
from __future__ import annotations

import hashlib
import os
from typing import Callable, Dict, Iterable, List, Optional, Tuple

import numpy as np

from ..base import MXNetError


def _calib_tools():
    """contrib.quantization's calibrators, resolved lazily: this module
    sits on the package's import spine (precision/__init__ loads before
    ndarray finishes importing), and contrib pulls in the ONNX subsystem
    at package level."""
    from ..contrib import quantization as cq

    return cq

__all__ = ["QuantizedAdapter", "quantize_adapter", "maybe_quantize_adapter",
           "Int4WeightAdapter", "int4_adapter", "maybe_int4_adapter",
           "collect_quantizable", "calibrate"]


def collect_quantizable(block, exclude: Iterable[str] = ()) -> List[Tuple]:
    """[(path, layer)] for every Dense/Conv2D reachable from ``block``
    (depth-first over ``_children``, any container shape — unlike the
    sequential-only ``quantize_net`` walker, the serving rewrite never
    replays children, so composite blocks are safe)."""
    from ..gluon import nn as gnn

    exclude = set(exclude or ())
    out: List[Tuple] = []

    def walk(blk, path):
        for key, child in blk._children.items():
            p = f"{path}.{key}" if path else str(key)
            if isinstance(child, gnn.Conv2D):
                # ops/quantization.quantized_conv is NC-first; a
                # channel-last conv stays f32, conservatively
                layout = child._kwargs.get("layout") or "NCHW"
                if layout == "NCHW" and p not in exclude \
                        and child.name not in exclude:
                    out.append((p, child))
            elif isinstance(child, gnn.Dense):
                if p not in exclude and child.name not in exclude:
                    out.append((p, child))
            else:
                walk(child, p)

    walk(block, "")
    return out


def calibrate(layers: List[Tuple], calib_data, calib_fn: Callable,
              calib_mode: str = "naive",
              num_calib_batches: Optional[int] = None,
              root=None) -> Dict[str, float]:
    """Observe per-layer input activations over ``calib_data`` ->
    {path: threshold}.  ``calib_fn(batch)`` runs one representative
    eager forward (e.g. a greedy ``translate`` over a prompt batch);
    forward-pre hooks on the target layers feed the calibrator —
    identical mechanics to ``quantize_net``'s eager calibration pass,
    including the hybridization handling: pass ``root`` (the block
    ``calib_fn`` forwards through) so ``hybridize()``d blocks are
    deactivated for the pass — forward-pre hooks never fire through a
    CachedOp fast path, and a hooked-but-unobserved layer would raise
    at ``threshold()`` below."""
    from .. import autograd

    if calib_mode not in ("naive", "entropy"):
        raise MXNetError(f"unknown calib_mode {calib_mode!r} "
                         "(naive/entropy)")
    cq = _calib_tools()
    calib = cq._Calibrator(calib_mode)
    hooks = []
    for path, layer in layers:
        hook = (lambda pp: lambda blk, args: calib.observe(
            pp, args[0].asnumpy()))(path)
        layer.register_forward_pre_hook(hook)
        hooks.append((layer, hook))
    hybridized = cq._active_blocks(root, []) if root is not None else []
    for b in hybridized:
        b._active = False
    try:
        with autograd.pause():
            for i, batch in enumerate(calib_data):
                calib_fn(batch)
                if num_calib_batches and i + 1 >= num_calib_batches:
                    break
    finally:
        for layer, hook in hooks:
            layer._forward_pre_hooks.remove(hook)
        for b in hybridized:
            b._active = True
    thresholds = {}
    for path, _layer in layers:
        t = calib.threshold(path)
        cq.check_calibrated_threshold(path, calib_mode,
                                      calib.minmax[path], t)
        thresholds[path] = t
    return thresholds


class _TracedTwin:
    """Traced int8 twin of one ``gluon.nn.Dense``/``Conv2D``: wraps the
    eager contrib twin (``QuantizedDense``/``QuantizedConv2D`` — the ONE
    copy of the calibrated quantize -> int8 kernel -> dequantize ->
    activation lowering lives in their F-generic ``_forward``) with the
    facts the serving rewrite needs: the layer path, the signature
    thresholds, byte accounting, and the traced-call contract
    ``twin(F, x, bias)`` where ``bias`` is the layer's own traced
    parameter (the impl's snapshot bias — zeros for bias-less layers —
    is the fallback, a device constant of the traced graph like the
    int8 weight, which is the params-bytes win)."""

    def __init__(self, impl, path: str, act_thresh: Optional[float]):
        self._impl = impl
        self.path = path
        self.act_thresh = act_thresh
        self._w_thresh = impl._w_thresh
        self.orig_nbytes = impl.orig_nbytes
        self.nbytes = impl.nbytes

    def arrays(self):
        i = self._impl
        return [i._qweight._data, i._w_min._data, i._w_max._data]

    def __call__(self, F, x, bias):
        return self._impl._forward(
            F, x, bias if bias is not None else self._impl._bias)


class _Int4Twin:
    """Traced int4 twin of one Dense/Conv2D: wraps the weight-only
    contrib impl (``Int4Dense``/``Int4Conv2D`` — the one copy of the
    dequantize-in-trace lowering) with the layer path, a content digest
    of the packed buffers (the restart-stable signature component — no
    thresholds exist on a weight-only path), and byte accounting."""

    def __init__(self, impl, path: str):
        self._impl = impl
        self.path = path
        h = hashlib.sha256()
        h.update(impl._packed.asnumpy().tobytes())
        h.update(impl._scales.asnumpy().tobytes())
        self.digest = h.hexdigest()[:16]
        self.orig_nbytes = impl.orig_nbytes
        self.nbytes = impl.nbytes

    def arrays(self):
        i = self._impl
        return [i._packed._data, i._scales._data]

    def __call__(self, F, x, bias):
        return self._impl._forward(
            F, x, bias if bias is not None else self._impl._bias)


def _quantized_arrays(adapter):
    """memwatch provider: the quantized weight buffers + scale/range
    constants the adapter holds resident (the `quantized` census
    slice — int8 and int4 adapters both land here)."""
    out = []
    for entry in adapter._entries.values():
        out.extend(entry.arrays())
    return out


class _RewriteAdapterBase:
    """Shared shell of the quantized serving adapters: mirror the
    cached-decode interface facts, register the memwatch census, and
    delegate the traced bodies under the adapter's graph pass scope
    (``self._pass`` — a ``passes/builtin`` quant pass whose scope is
    the ``runtime.quant_scope`` mapping activation).  Subclasses build
    ``self._inner``, ``self._entries``/``self._by_path`` and
    ``self._pass``, then call ``_init_common``."""

    def _init_common(self, inner):
        from .. import memwatch

        self.uses_pages = inner.uses_pages
        self.num_layers = inner.num_layers
        self.num_heads = inner.num_heads
        self.head_dim = inner.head_dim
        self.prefill_names = inner.prefill_names
        # the engine builds its pass pipeline from this
        # (passes.pipeline_for_serving reads adapter.passes)
        self.passes = (self._pass,)
        memwatch.register("quantized", self, _quantized_arrays)

    @staticmethod
    def _resolve_model(inner, who: str):
        model = getattr(inner, "model", None)
        if model is None:
            raise MXNetError(
                f"{who}: the wrapped adapter exposes no .model to "
                "quantize (FullPrefixAdapter-style logits functions own "
                "no layer tree — quantize the underlying block and wrap "
                "that)")
        return model

    # -- identity ------------------------------------------------------
    @property
    def model(self):
        return self._inner.model

    def quant_signature(self) -> Tuple:
        """Structural identity of the quantization config — the pass's
        signature.  Different MX_QUANTIZE/MX_SERVE_INT4/MX_QUANT_*
        settings (or requantized weights) produce a different
        signature."""
        return self._pass.signature()

    def signature(self):
        return tuple(self._inner.signature()) + self.quant_signature()

    # -- params accounting (the bench's params-bytes story) ------------
    def quantized_param_bytes(self) -> int:
        """Bytes of the weights as the quantized graph holds them:
        packed/int8 for the rewritten layers' weights, original dtype
        for everything else (biases, norms, embeddings, excluded
        layers).  This is the PROGRAM's weight footprint
        (docs/PRECISION.md §Params-bytes accounting), not process
        residency — while the fp32 source net is alive the process
        holds both it and the quantized twins."""
        rewritten = {id(layer.weight)
                     for _path, layer in collect_quantizable(self.model)
                     if id(layer) in self._entries}
        total = sum(e.nbytes for e in self._entries.values())
        for p in self.model.collect_params().values():
            if id(p) not in rewritten:
                total += int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
        return total

    def fp32_param_bytes(self) -> int:
        return sum(int(np.prod(p.shape)) * np.dtype(p.dtype).itemsize
                   for p in self.model.collect_params().values())

    def quantized_weight_bytes(self) -> int:
        """Bytes of JUST the rewritten layers' weights as the quantized
        graph holds them (packed nibbles + scales for int4; int8 + range
        scalars' weight part for int8).  The per-layer compression
        acceptance ratio — whole-model ``quantized_param_bytes`` is
        diluted by f32 embeddings/norms that no weight rewrite touches."""
        return sum(e.nbytes for e in self._entries.values())

    def fp32_weight_bytes(self) -> int:
        """Original bytes of just the rewritten layers' weights."""
        return sum(e.orig_nbytes for e in self._entries.values())

    # -- delegated interface -------------------------------------------
    def extra_state(self, slots, ctx, dtype):
        return self._inner.extra_state(slots, ctx, dtype)

    def prefill_src(self, request):
        return self._inner.prefill_src(request)

    def prefill(self, F, src):
        with self._pass.scope():
            return self._inner.prefill(F, src)

    def install(self, state, slot, request):
        return self._inner.install(state, slot, request)

    def validate(self, request):
        return self._inner.validate(request)

    def max_positions(self):
        return self._inner.max_positions()

    def warmup(self, ctx):
        # eager f32 warmup: shape inference only — the quantized graph
        # appears at trace time, under the scope in decode/prefill
        return self._inner.warmup(ctx)

    def decode(self, F, tok, pos, table, keep, pages, rows, lengths,
               extra, pools):
        with self._pass.scope():
            return self._inner.decode(F, tok, pos, table, keep, pages,
                                      rows, lengths, extra, pools)

    def decode_logits(self, F, tok, pos, table, keep, pages, rows,
                      lengths, extra, pools):
        with self._pass.scope():
            return self._inner.decode_logits(F, tok, pos, table, keep,
                                             pages, rows, lengths, extra,
                                             pools)

    def advance_extra(self, F, extra, nxt, pos):
        with self._pass.scope():
            return self._inner.advance_extra(F, extra, nxt, pos)


class QuantizedAdapter(_RewriteAdapterBase):
    """Int8 twin of any :class:`~mxnet_tpu.serving.engine.ServingAdapter`.

    Same cached-decode interface; ``decode``/``prefill`` run the wrapped
    adapter's traced bodies under the ``quant_int8`` pass's scope
    (:func:`runtime.quant_scope`), so the selected Dense/Conv layers
    lower onto the int8 primitives inside the engine's ONE compiled
    executable.  Construct via :func:`quantize_adapter` (calibrated) —
    this constructor takes pre-computed thresholds."""

    precision = "int8"

    def __init__(self, inner, thresholds: Dict[str, Optional[float]],
                 calib_mode: str = "naive",
                 exclude: Iterable[str] = ()):
        from ..gluon import nn as gnn
        from ..passes.builtin import QuantizeInt8Pass

        cq = _calib_tools()
        model = self._resolve_model(inner, "QuantizedAdapter")
        self._inner = inner
        self._calib_mode = calib_mode
        self._entries: Dict[int, object] = {}
        self._by_path: Dict[str, object] = {}
        for path, layer in collect_quantizable(model, exclude):
            if path not in thresholds:
                raise MXNetError(
                    f"QuantizedAdapter: no calibration threshold for "
                    f"layer {path!r} (calibrate observed a different "
                    f"layer set?)")
            impl_cls = (cq.QuantizedConv2D if isinstance(layer, gnn.Conv2D)
                        else cq.QuantizedDense)
            twin = _TracedTwin(impl_cls(layer, thresholds[path]),
                               path, thresholds[path])
            self._entries[id(layer)] = twin
            self._by_path[path] = twin
        if not self._entries:
            raise MXNetError(
                "QuantizedAdapter: no quantizable Dense/Conv2D layers "
                "found in the wrapped adapter's model")
        per_layer = tuple(sorted(
            (path, round(e._w_thresh, 8),
             round(e.act_thresh, 8) if e.act_thresh is not None else None)
            for path, e in self._by_path.items()))
        self._pass = QuantizeInt8Pass(self._entries, calib_mode, per_layer)
        self._init_common(inner)


class Int4WeightAdapter(_RewriteAdapterBase):
    """Weight-only int4 twin of a ServingAdapter: every selected
    Dense/Conv weight is packed 2-per-byte with group-wise f16 scales
    and dequantized IN-TRACE (``_contrib_dequantize_int4``) inside the
    engine's compiled decode/prefill bodies — ~0.14x weight bytes at the
    default group of 32, no calibration (activations stay f32).
    Construct via :func:`int4_adapter` / :func:`maybe_int4_adapter`."""

    precision = "int4"

    def __init__(self, inner, group_size: int = 32,
                 exclude: Iterable[str] = ()):
        from ..gluon import nn as gnn
        from ..passes.builtin import QuantizeInt4Pass

        cq = _calib_tools()
        model = self._resolve_model(inner, "Int4WeightAdapter")
        self._inner = inner
        self._group_size = int(group_size)
        self._entries: Dict[int, object] = {}
        self._by_path: Dict[str, object] = {}
        for path, layer in collect_quantizable(model, exclude):
            impl = (cq.Int4Conv2D(layer, self._group_size)
                    if isinstance(layer, gnn.Conv2D)
                    else cq.Int4Dense(layer, self._group_size))
            twin = _Int4Twin(impl, path)
            self._entries[id(layer)] = twin
            self._by_path[path] = twin
        if not self._entries:
            raise MXNetError(
                "Int4WeightAdapter: no quantizable Dense/Conv2D layers "
                "found in the wrapped adapter's model")
        per_layer = tuple(sorted(
            (path, e.digest) for path, e in self._by_path.items()))
        self._pass = QuantizeInt4Pass(self._entries, self._group_size,
                                      per_layer)
        self._init_common(inner)


def quantize_adapter(adapter, calib_data, calib_fn: Callable,
                     calib_mode: str = "naive",
                     exclude: Iterable[str] = (),
                     num_calib_batches: Optional[int] = None
                     ) -> QuantizedAdapter:
    """Calibrate + wrap: the one-call driver producing an int8 serving
    adapter.  ``calib_fn(batch)`` runs one representative eager forward
    per calibration batch (a greedy ``translate`` over prompts is the
    natural choice for seq2seq serving)."""
    model = getattr(adapter, "model", None)
    if model is None:
        raise MXNetError("quantize_adapter: adapter exposes no .model")
    layers = collect_quantizable(model, exclude)
    if not layers:
        raise MXNetError("quantize_adapter: no quantizable Dense/Conv2D "
                         "layers in the adapter's model")
    thresholds = calibrate(layers, calib_data, calib_fn,
                           calib_mode=calib_mode,
                           num_calib_batches=num_calib_batches,
                           root=model)
    return QuantizedAdapter(adapter, thresholds, calib_mode=calib_mode,
                            exclude=exclude)


def maybe_quantize_adapter(adapter, calib_data=None, calib_fn=None,
                           exclude: Iterable[str] = ()):
    """The env-driven gate: ``MX_QUANTIZE=int8`` (or ``1``) quantizes
    ``adapter`` with the ``MX_QUANT_CALIB`` mode (default naive); unset/
    ``0`` returns the adapter untouched.  Calibration data is required
    when quantization is on — serving an uncalibrated int8 engine by
    accident must fail loudly, not degrade silently."""
    raw = (os.environ.get("MX_QUANTIZE") or "").strip().lower()
    if raw in ("", "0", "false", "off"):
        return adapter
    if raw not in ("1", "int8", "true", "on"):
        raise MXNetError(f"MX_QUANTIZE={raw!r}: expected int8/1 or 0/off")
    mode = (os.environ.get("MX_QUANT_CALIB") or "naive").strip().lower()
    if calib_data is None or calib_fn is None:
        raise MXNetError(
            "MX_QUANTIZE=int8 needs calibration data: pass calib_data + "
            "calib_fn to maybe_quantize_adapter (post-training int8 "
            "without calibrated ranges would quantize on the fly per "
            "step — run quantize_adapter explicitly if that is intended)")
    return quantize_adapter(adapter, calib_data, calib_fn, calib_mode=mode,
                            exclude=exclude)


def int4_adapter(adapter, group_size: int = 32,
                 exclude: Iterable[str] = ()) -> Int4WeightAdapter:
    """Wrap ``adapter`` for weight-only int4 serving.  No calibration
    step — packing is a pure function of the weights (group-wise
    symmetric, ``contrib.quantization._quantize_weight_int4_np``)."""
    return Int4WeightAdapter(adapter, group_size=group_size,
                             exclude=exclude)


def maybe_int4_adapter(adapter, exclude: Iterable[str] = ()):
    """The env-driven gate: ``MX_SERVE_INT4=1`` (or ``int4``) wraps
    ``adapter`` for weight-only int4 serving with the ``MX_QUANT_GROUP``
    group size (default 32); unset/``0`` returns the adapter untouched.
    Composing with ``MX_QUANTIZE=int8`` is rejected — the two rewrites
    claim the same Dense/Conv layers."""
    raw = (os.environ.get("MX_SERVE_INT4") or "").strip().lower()
    if raw in ("", "0", "false", "off"):
        return adapter
    if raw not in ("1", "int4", "true", "on"):
        raise MXNetError(f"MX_SERVE_INT4={raw!r}: expected int4/1 or 0/off")
    if (os.environ.get("MX_QUANTIZE") or "").strip().lower() not in \
            ("", "0", "false", "off"):
        raise MXNetError(
            "MX_SERVE_INT4 and MX_QUANTIZE are both set: the int4 and "
            "int8 rewrites claim the same Dense/Conv layers — pick one")
    graw = (os.environ.get("MX_QUANT_GROUP") or "32").strip()
    try:
        group = int(graw)
    except ValueError:
        raise MXNetError(f"MX_QUANT_GROUP={graw!r}: expected an even int")
    return int4_adapter(adapter, group_size=group, exclude=exclude)
