"""Continuous-batching inference engine: ONE compiled decode step shared
by ragged in-flight requests (docs/SERVING.md).

The hot loop is a single jitted ``decode_step`` over ``S`` fixed decode
*slots*: every input is shape-stable — per-slot positions, page tables
and validity masks are device VALUES, never shapes — so mixed-length
requests arriving mid-flight reuse one executable with zero per-length
retraces (asserted via memwatch compile events in tests/test_serving.py).
Prefill (encode for seq2seq, prompt ingestion for decoder-only) runs as a
second compiled executable over a fixed padded shape, or folds into the
decode step entirely (``FullPrefixAdapter``).

Dispatch is a lazy pipeline reusing the PR 4 ``InflightRing`` semantics:
``_dispatch_step`` chains device state -> device state and admits one
:class:`~mxnet_tpu.parallel.async_loss.AsyncResult` token handle per step
without ever blocking; the host reads tokens back in bursts of
``MX_SERVE_STREAM_EVERY`` steps (stream cadence — never per token), does
scheduler bookkeeping (EOS -> free the slot's KV pages immediately, admit
waiting requests mid-flight), and dispatches the next burst.

Any model servable here implements :class:`ServingAdapter` — the
"cached-decode interface".  Seeds: :class:`TransformerAdapter`
(models/transformer.py, paged KV decode refactored from its dense cache)
and :class:`FullPrefixAdapter` (any fixed-shape logits function — e.g.
an ONNX-imported decoder-only SymbolBlock — served O(L^2) but still
one-executable).
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

import numpy as np

from .. import memwatch
from .. import telemetry
from ..base import MXNetError, env_int
from ..parallel.async_loss import AsyncResult, InflightRing
from .paged_cache import PagedKVCache, PagedStepCache, page_coords, pages_for
from .scheduler import (ContinuousBatchingScheduler, PrefixCache, Request,
                        prefix_key)

__all__ = ["ServingAdapter", "TransformerAdapter", "FullPrefixAdapter",
           "ServingEngine"]


def _serve_fused() -> bool:
    """MX_SERVE_FLASH: 'auto' (default) and 0 take the XLA gather path (the
    bitwise-parity path) on every platform; 1 forces the Pallas paged
    kernel.  The kernel runs interpreted off-TPU (the tests), but Mosaic
    rejects its head-batched dot and it maps the whole pool as one VMEM
    block, so on a TPU forcing it fails at compile time and 'auto' does
    not select it until ROADMAP A4 rewrites it page-blocked."""
    return os.environ.get("MX_SERVE_FLASH", "auto").lower() in (
        "1", "true", "on")


# ---------------------------------------------------------------------------
# traced sampling math (runs inside the ONE compiled decode/verify step)
# ---------------------------------------------------------------------------
def _filter_logits(logits, temp, topk, topp):
    """Temperature/top-k/top-p filtered logits, per slot (jnp arrays,
    trace-time).  logits (S, V); temp/topp (S,) f32; topk (S,) int32
    (0 = off).  Returns (S, V) logits with masked-out entries at -inf —
    gumbel-argmax over the result samples the truncated, temperature-
    scaled distribution.  Rows with temp == 0 produce garbage here (the
    1e-6 floor) and are discarded by the caller's ``where`` against the
    greedy branch."""
    import jax
    import jax.numpy as jnp

    V = logits.shape[-1]
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    order = jnp.argsort(-scaled, axis=-1)
    sdesc = jnp.take_along_axis(scaled, order, axis=-1)
    kk = jnp.clip(jnp.where(topk > 0, topk, V), 1, V).astype(jnp.int32)
    kth = jnp.take_along_axis(sdesc, (kk - 1)[:, None], axis=1)
    filt = jnp.where(scaled < kth, -jnp.inf, scaled)
    # nucleus: drop tokens outside the smallest set whose cumulative
    # (descending) probability reaches top_p; the head token always
    # survives (cum - p_i == 0 < top_p).  top_p >= 1 is a hard off
    # switch — float cumsum can touch 1.0 early and must not truncate.
    fdesc = jnp.take_along_axis(filt, order, axis=-1)
    pdesc = jax.nn.softmax(fdesc, axis=-1)
    cum = jnp.cumsum(pdesc, axis=-1)
    drop_desc = ((cum - pdesc) >= topp[:, None]) & (topp < 1.0)[:, None]
    inv = jnp.argsort(order, axis=-1)
    drop = jnp.take_along_axis(drop_desc, inv, axis=-1)
    return jnp.where(drop, -jnp.inf, filt)


def _split_keys(keys, n):
    """Advance every slot's RNG key one step: (S, 2) uint32 keys ->
    (new_keys (S, 2), subs (S, n, 2)).  Per-slot independent streams —
    a request's randomness is a function of its own seed only, never of
    slot assignment or batch composition."""
    import jax

    out = jax.vmap(lambda k: jax.random.split(k, n + 1))(keys)
    return out[:, 0], out[:, 1:]


def _gumbel_rows(subs, V):
    """(S, 2) subkeys -> (S, V) float32 gumbel noise (one row per slot;
    argmax(logits + gumbel) samples softmax(logits))."""
    import jax
    import jax.numpy as jnp

    return jax.vmap(lambda k: jax.random.gumbel(k, (V,), jnp.float32))(subs)


def _uniform_rows(subs):
    """(S, 2) subkeys -> (S,) float32 U[0,1) — the accept coin flips."""
    import jax
    import jax.numpy as jnp

    return jax.vmap(lambda k: jax.random.uniform(k, (), jnp.float32))(subs)


# ---------------------------------------------------------------------------
# the cached-decode interface
# ---------------------------------------------------------------------------
class ServingAdapter:
    """What a model must expose to be served.

    Attributes: ``num_layers``/``num_heads``/``head_dim`` size the paged
    KV pools (ignored when ``uses_pages`` is False).  All ``F``-taking
    methods run BOTH eagerly and inside the engine's jit trace — NDArray
    ops only, shapes static, values free."""

    uses_pages = True
    num_layers = 0
    num_heads = 1
    head_dim = 1

    def extra_state(self, slots: int, ctx, dtype: str):
        """Adapter-owned device state with a leading slot dim (e.g. the
        encoder memory per slot).  OrderedDict name -> NDArray."""
        return OrderedDict()

    #: extra-state keys the prefill executable produces, in output
    #: order
    prefill_names = ()

    def prefill_src(self, request: Request):
        """Padded (1, Ts) int32 numpy prefill input for the separate
        prefill executable, or None when prefill folds into decode."""
        return None

    def prefill(self, F, src):
        """Traced prefill: (1, Ts) tokens -> dict of extra-state rows
        (each (1, ...)) to install into the request's slot."""
        return {}

    def install(self, state, slot: int, request: Request) -> None:
        """Eager per-slot state init at admission (after core defaults
        tok=bos, pos=0 and any prefill rows are in place)."""

    def validate(self, request: Request) -> None:
        """Reject a request THIS adapter cannot serve, at submit time
        (raise MXNetError).  Anything that would silently truncate or
        corrupt later must fail loudly here."""

    def max_positions(self):
        """The largest decode position the model can represent (e.g. its
        positional-embedding table length), or None for unbounded.  The
        engine refuses a ``max_len`` beyond it at construction — the
        gather-based position lookup would silently CLAMP out-of-table
        positions instead of failing."""
        return None

    def signature(self):
        """Extra structural identity for the executable's fingerprint
        (the name compile telemetry events carry): what changes the
        traced decode program without changing shapes, e.g. the
        fused-attention decision."""
        return ()

    def warmup(self, ctx) -> None:
        """One tiny eager forward so deferred-init parameters take their
        shapes before the engine traces (gluon Dense layers infer shapes
        on first call)."""

    def decode_logits(self, F, tok, pos, table, keep, pages, rows,
                      lengths, extra, pools):
        """Traced decode of ONE position for every slot, stopping at the
        LOGITS: returns ((S, V) logits, new_extra dict, new_pools list)
        with the KV write applied but NO token selected.  The engine's
        sampling and speculative-verify bodies build on this — greedy
        argmax, temperature sampling and draft acceptance are all
        different selections over the same logits."""
        raise NotImplementedError(
            f"{type(self).__name__} implements neither decode_logits "
            "nor decode — sampling and speculative serving need "
            "decode_logits")

    def advance_extra(self, F, extra, nxt, pos):
        """Apply the CHOSEN token to adapter extra state (traced).  Most
        adapters keep step-invariant extra state (e.g. the encoder
        memory) and inherit this identity; an adapter whose extra state
        records emitted tokens (FullPrefixAdapter's prompt buffer)
        overrides it.  Speculative verify skips this hook — it requires
        the identity behaviour (checked at engine construction)."""
        return extra

    def decode(self, F, tok, pos, table, keep, pages, rows, lengths,
               extra, pools):
        """Traced GREEDY decode of ONE position for every slot.  Returns
        (next_tok (S,) int32, new_extra dict, new_pools list).  The
        default composes :meth:`decode_logits` with the argmax-over-
        log-softmax selection ``translate`` applies at beam_size=1 (the
        bitwise greedy contract) and :meth:`advance_extra`."""
        logits, new_extra, new_pools = self.decode_logits(
            F, tok, pos, table, keep, pages, rows, lengths, extra, pools)
        # argmax over log-softmax, the exact selection translate's beam
        # update applies with beam_size=1 (token-for-token parity)
        nxt = F.cast(F.argmax(logits.log_softmax(axis=-1), axis=-1),
                     "int32")
        new_extra = self.advance_extra(F, new_extra, nxt, pos)
        return nxt, new_extra, new_pools


class TransformerAdapter(ServingAdapter):
    """models/transformer.py seq2seq decode on the paged KV cache.

    Prefill = the encoder over the source padded to ``src_max_len``
    (one compiled prefill regardless of source length); decode = the
    same ``Transformer._decode_step`` the standalone ``translate`` runs,
    greedy (log-softmax argmax — matches ``translate(beam_size=1)``
    token-for-token)."""

    prefill_names = ("mem", "src_keep")

    def __init__(self, model, src_max_len: int, fused: Optional[bool] = None):
        self.model = model
        self.src_max = int(src_max_len)
        sa = model.decoder.layers[0].self_attn
        self.num_layers = len(model.decoder.layers)
        self.num_heads = sa._num_heads
        self.head_dim = sa._head_dim
        self._fused = fused

    def _resolved_fused(self) -> bool:
        """The fused decision, resolved ONCE and pinned — the traced
        program and the fingerprint must agree on it."""
        if self._fused is None:
            self._fused = _serve_fused()
        return self._fused

    def max_positions(self):
        return self.model.pos._max_length

    def signature(self):
        return ("fused", self._resolved_fused())

    def extra_state(self, slots, ctx, dtype):
        from ..ndarray import zeros as nd_zeros

        units = self.model._units
        return OrderedDict(
            mem=nd_zeros((slots, self.src_max, units), ctx=ctx,
                         dtype=dtype),
            src_keep=nd_zeros((slots, self.src_max), ctx=ctx, dtype=dtype))

    def validate(self, request):
        if request.tokens.shape[0] > self.src_max:
            raise MXNetError(
                f"request {request.id} source length "
                f"{request.tokens.shape[0]} > adapter src_max_len "
                f"{self.src_max}")

    def prefill_src(self, request):
        toks = request.tokens
        self.validate(request)
        row = np.full((1, self.src_max), self.model._pad_id, np.int32)
        row[0, :toks.shape[0]] = toks
        return row

    def prefill(self, F, src):
        mem, src_keep = self.model._encode_h(F, src)
        return {"mem": mem, "src_keep": src_keep}

    def warmup(self, ctx):
        from ..ndarray import array as nd_array

        src = np.full((1, self.src_max), self.model._pad_id, np.int32)
        src[0, 0] = 1
        tgt = np.ones((1, 1), np.int32)
        self.model(nd_array(src, ctx=ctx, dtype="int32"),
                   nd_array(tgt, ctx=ctx, dtype="int32"))

    def decode_logits(self, F, tok, pos, table, keep, pages, rows,
                      lengths, extra, pools):
        fused = self._resolved_fused()
        caches = [PagedStepCache(pools[2 * i], pools[2 * i + 1], table,
                                 pages, rows, keep,
                                 lengths=lengths, fused=fused)
                  for i in range(self.num_layers)]
        logits = self.model._decode_step(F, tok, pos, extra["mem"],
                                         extra["src_keep"], caches)
        new_pools = []
        for c in caches:
            new_pools.extend((c.k_pool, c.v_pool))
        return logits, extra, new_pools


class FullPrefixAdapter(ServingAdapter):
    """Serve ANY fixed-shape decoder-only logits function — prefill
    chunked into the decode step (the prompt sits in the slot's token
    buffer; the first decode computes it along with everything else).

    ``logits_fn(F, buf) -> (S, L, V)`` over the (S, L) int32 token
    buffer; e.g. a causal HybridBlock forward or an ONNX-imported
    decoder.  O(L^2) per generated token (the universal fallback — no KV
    cache assumptions), but still shape-stable: ONE executable for every
    request length."""

    uses_pages = False

    def __init__(self, logits_fn, max_len: int, pad_id: int = 0):
        self._fn = logits_fn
        self.max_len = int(max_len)
        self.pad_id = int(pad_id)

    def extra_state(self, slots, ctx, dtype):
        from ..ndarray import zeros as nd_zeros

        return OrderedDict(
            buf=nd_zeros((slots, self.max_len), ctx=ctx, dtype="int32"))

    def validate(self, request):
        need = request.tokens.shape[0] + request.max_new_tokens
        if need > self.max_len:
            raise MXNetError(
                f"request {request.id} needs {need} buffer positions "
                f"(prompt {request.tokens.shape[0]} + max_new "
                f"{request.max_new_tokens}) > adapter max_len "
                f"{self.max_len} — the fixed prefix buffer would "
                "silently truncate")

    def install(self, state, slot, request):
        row = np.full((self.max_len,), self.pad_id, np.int32)
        n = request.tokens.shape[0]
        row[:n] = request.tokens
        state["buf"][slot] = row
        state["pos"][slot] = max(0, n - 1)

    def decode_logits(self, F, tok, pos, table, keep, pages, rows,
                      lengths, extra, pools):
        from ..ndarray import NDArray
        import jax.numpy as jnp

        buf = extra["buf"]
        logits = self._fn(F, buf)                      # (S, L, V)
        step = jnp.take_along_axis(
            logits._data, pos._data[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]                              # (S, V)
        return NDArray(step, ctx=buf.context), extra, []

    def advance_extra(self, F, extra, nxt, pos):
        from ..ndarray import NDArray
        import jax.numpy as jnp

        buf = extra["buf"]
        S, L = buf.shape
        wpos = jnp.minimum(pos._data + 1, L - 1)
        new_buf = NDArray(
            buf._data.at[jnp.arange(S), wpos].set(nxt._data),
            ctx=buf.context)
        return {"buf": new_buf}


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
class _Active:
    """Host bookkeeping of one occupied slot."""

    __slots__ = ("req", "pos", "done", "seq")

    def __init__(self, req: Request, seq: int):
        self.req = req
        self.pos = 0      # mirrors the slot's DEVICE position counter
        self.done = False
        self.seq = seq    # admission order (preemption evicts youngest)


class ServingEngine:
    """Fixed-slot continuous-batching engine over one compiled decode
    step (module docstring has the architecture; docs/SERVING.md the
    knobs)."""

    def __init__(self, adapter: ServingAdapter, slots: Optional[int] = None,
                 page_size: Optional[int] = None,
                 pool_pages: Optional[int] = None, max_len: int = 64,
                 stream_every: Optional[int] = None,
                 queue_bound: Optional[int] = None, ctx=None,
                 dtype: str = "float32",
                 sampling: Optional[bool] = None,
                 spec_k: Optional[int] = None, draft=None,
                 prefix_cache: Optional[bool] = None,
                 prefix_entries: Optional[int] = None):
        from ..context import current_context
        from ..ndarray import zeros as nd_zeros

        self._adapter = adapter
        # ---- front-door features, all default-OFF (parity-pinned):
        # sampling adds per-slot temp/topk/topp/rng device state and a
        # sampled decode body; spec_k > 0 switches the run loop to
        # draft-propose + one ("verify", K) dispatch per boundary;
        # prefix_cache turns on COW page sharing + prefill-row reuse.
        self._sampling = (env_int("MX_SERVE_SAMPLING", 0) != 0
                          if sampling is None else bool(sampling))
        self._spec_k = max(0, spec_k if spec_k is not None
                           else env_int("MX_SERVE_SPEC_K", 0))
        if self._spec_k and not adapter.uses_pages:
            raise MXNetError(
                "speculative decoding (spec_k > 0) needs a paged-KV "
                "adapter — the verify step teacher-forces K positions "
                "through the paged cache")
        self._draft = draft
        if self._spec_k and self._draft is None:
            from .speculative import NGramDraft

            self._draft = NGramDraft()
        prefix_on = (env_int("MX_SERVE_PREFIX_CACHE", 0) != 0
                     if prefix_cache is None else bool(prefix_cache))
        if prefix_on and not adapter.uses_pages:
            raise MXNetError(
                "the prefix cache shares paged KV pages — it needs a "
                "paged-KV adapter (uses_pages)")
        self._prefix = PrefixCache(
            prefix_entries if prefix_entries is not None
            else env_int("MX_SERVE_PREFIX_ENTRIES", 64)) \
            if prefix_on else None
        self._prefix_chunk = max(1, env_int("MX_SERVE_PREFIX_CHUNK", 8))
        # precision label of the compiled decode program (fp32, or int8
        # for a precision.QuantizedAdapter) — rides on the mx_serve_*
        # telemetry so dashboards can attribute latency/throughput to
        # the dtype program serving them (docs/PRECISION.md)
        self._precision = str(getattr(adapter, "precision", "fp32"))
        # the serving pass pipeline (passes/builtin.pipeline_for_serving):
        # adapter-contributed quant passes + fused-kernel substitution.
        # Every traced body runs under its scope (_traced), and its ONE
        # signature joins _fingerprint_parts.
        from ..passes.builtin import pipeline_for_serving

        self._pipeline = pipeline_for_serving(adapter)
        self._ctx = ctx if ctx is not None else current_context()
        self._S = slots if slots is not None else env_int("MX_SERVE_SLOTS", 8)
        self._ps = page_size if page_size is not None \
            else env_int("MX_SERVE_PAGE_SIZE", 16)
        self._max_len = int(max_len)
        self._stream_every = max(1, stream_every if stream_every is not None
                                 else env_int("MX_SERVE_STREAM_EVERY", 4))
        self._dtype = dtype
        cap = adapter.max_positions()
        if cap is not None and self._max_len > cap:
            raise MXNetError(
                f"engine max_len {self._max_len} > the model's "
                f"max_positions {cap} (positional table) — out-of-table "
                "positions would silently clamp; lower max_len or build "
                "the model with a larger max_length")
        if adapter.uses_pages:
            n_pages = pool_pages if pool_pages is not None \
                else env_int("MX_SERVE_POOL_PAGES", 0)
            if not n_pages:  # auto: every slot can reach max_len
                n_pages = self._S * pages_for(self._max_len, self._ps) + 1
            self._cache = PagedKVCache(
                adapter.num_layers, n_pages, self._ps, adapter.num_heads,
                adapter.head_dim, ctx=self._ctx, dtype=dtype)
            # table wide enough that positions overrun by a full burst
            # (a request finishing mid-burst keeps decoding until the
            # stream boundary) land on zero -> trash page, never clamp
            # into a live page; a speculative verify overruns by up to
            # K+1 positions per boundary, whichever is larger
            overrun = max(self._stream_every, self._spec_k + 1)
            self._P = pages_for(self._max_len + overrun, self._ps)
        else:
            self._cache = None
            self._P = 1
        self._sched = ContinuousBatchingScheduler(queue_bound)
        self._ring = InflightRing("ServingEngine")
        self._slots: List[Optional[_Active]] = [None] * self._S
        self._arrivals: List = []  # (arrive_at_step, request), sorted
        self._step_n = 0
        self._admit_seq = 0

        # device state: core (tok/pos/table) + adapter extra + pools;
        # everything the compiled step threads state -> state
        state = OrderedDict(
            tok=nd_zeros((self._S, 1), ctx=self._ctx, dtype="int32"),
            pos=nd_zeros((self._S,), ctx=self._ctx, dtype="int32"),
            table=nd_zeros((self._S, self._P), ctx=self._ctx,
                           dtype="int32"))
        # per-slot sampling state rides the compiled step ONLY when
        # sampling is on: a greedy engine's state (and therefore its
        # traced program and fingerprint) is unchanged — the
        # parity-pinned default
        self._samp_names: List[str] = []
        if self._sampling:
            state["temp"] = nd_zeros((self._S,), ctx=self._ctx,
                                     dtype="float32")
            state["topk"] = nd_zeros((self._S,), ctx=self._ctx,
                                     dtype="int32")
            state["topp"] = nd_zeros((self._S,), ctx=self._ctx,
                                     dtype="float32")
            state["rng"] = nd_zeros((self._S, 2), ctx=self._ctx,
                                    dtype="uint32")
            self._samp_names = ["temp", "topk", "topp", "rng"]
        extra = adapter.extra_state(self._S, self._ctx, dtype)
        self._extra_names = list(extra)
        state.update(extra)
        self._pool_names: List[str] = []
        if self._cache is not None:
            for i, (kp, vp) in enumerate(self._cache.pools):
                state[f"kpool{i}"] = kp
                state[f"vpool{i}"] = vp
                self._pool_names += [f"kpool{i}", f"vpool{i}"]
        self._state = state
        self._names = list(state)

        self._param_items = None
        self._run = None
        self._vrun = None   # ("verify", K) speculative executable
        self._irun = None   # ("ingest", K) prefix teacher-forcing
        self._last_nprop = None
        self._spec_proposed = 0  # lifetime draft tokens proposed
        self._spec_accepted = 0  # lifetime draft tokens accepted
        self._prefill_run = None
        self._prefill_names: List[str] = []
        self._pending_compile: Dict = {}
        # zero-downtime weight hot-swap (docs/SERVING.md §Weight
        # hot-swap): verified new weights wait in _staging until the run
        # loop flips them in at a stream boundary
        self._staging: Dict[str, np.ndarray] = {}
        self._swap_pending: Optional[dict] = None
        self._swap_lock = threading.Lock()
        self._running = False
        self._weight_generation = 0
        # live-array census category for the watchdog: the paged pools +
        # slot state are the serving engine's resident footprint
        memwatch.register("serving", self,
                          lambda eng: [a._data for a in
                                       eng._state.values()])
        # the swap staging buffer is its own census category: the
        # transient 2x-weights window shows up attributed (and the leak
        # detector never mistakes it for growth) — it must read empty
        # again after the flip
        memwatch.register("staging", self,
                          lambda eng: list(eng._staging.values()))

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, request: Request) -> Request:
        plen = int(request.prefix.size)
        if plen + request.max_new_tokens > self._max_len:
            raise MXNetError(
                f"request {request.id} prefix {plen} + max_new_tokens "
                f"{request.max_new_tokens} > engine max_len "
                f"{self._max_len}")
        if plen and not self._adapter.uses_pages:
            raise MXNetError(
                f"request {request.id} carries a decoder prefix but the "
                "adapter has no paged KV cache to teacher-force it into "
                "— fold the prefix into the prompt instead")
        if request.temperature > 0 and not self._sampling:
            raise MXNetError(
                f"request {request.id} asks for temperature "
                f"{request.temperature} but this engine was built "
                "greedy-only — construct ServingEngine(sampling=True) "
                "or set MX_SERVE_SAMPLING=1")
        self._adapter.validate(request)
        return self._sched.submit(request)

    def serve(self, requests, arrival_steps=None) -> Dict[str, np.ndarray]:
        """Decode ``requests`` to completion; returns {id: tokens}.

        ``arrival_steps`` (optional, aligned with ``requests``) delays
        request i until the engine's global decode-step counter reaches
        that value — mid-flight joins, the continuous-batching test
        surface.  Requests with arrival 0/None submit immediately."""
        requests = list(requests)
        if arrival_steps is None:
            arrival_steps = [0] * len(requests)
        base = self._step_n
        for req, at in zip(requests, arrival_steps):
            if at:
                self._arrivals.append((base + int(at), req))
            else:
                self.submit(req)
        self._arrivals.sort(key=lambda p: p[0])
        self.run()
        return {r.id: r.stream.asarray() for r in requests}

    def swap_weights(self, ckpt_dir: str,
                     step: Optional[int] = None) -> int:
        """Zero-downtime weight hot-swap: load a checkpoint's params into
        a STAGING buffer off the decode path, verify them, and flip the
        served param pytree at the next stream boundary — in-flight
        requests finish against a consistent weight set, the paged KV
        pool and page tables are untouched, and because ``_params()`` is
        re-read live each dispatch the compiled decode executable is
        reused as-is (same fingerprint = zero recompile).

        Verification before anything is published: the checkpoint's
        SHA-256 digests (``load_checkpoint_state`` rejects torn/corrupt
        steps), full param coverage, and the decode fingerprint
        recomputed over the staged arrays — a mismatched fingerprint
        (different shapes/dtypes, i.e. a different/quantized model) is a
        LOUD rejection and the engine keeps serving the old weights.

        Thread-safe against a concurrent :meth:`run`: the flip itself
        only ever happens on the run-loop thread (between decode bursts)
        or synchronously here when the engine is idle.  Returns the
        checkpoint step swapped in; telemetry records a ``weight_swap``
        event (staged bytes, verify/flip ms, generation) surfaced in
        ``/statusz`` and ``mx_serve_weight_generation``."""
        from .. import checkpoint as ckpt_mod

        t0 = time.perf_counter()
        self._ensure_compiled()
        state = ckpt_mod.load_checkpoint_state(ckpt_dir, step=step)
        if state is None:
            raise MXNetError(
                f"swap_weights: no valid checkpoint in {ckpt_dir!r} — "
                "keeping the current weights")
        snap = state["params"]
        model = getattr(self._adapter, "model", None)
        by_param = {}
        if model is not None and hasattr(model,
                                         "_collect_params_with_prefix"):
            by_param = {id(p): s for s, p in
                        model._collect_params_with_prefix().items()}
        staging: Dict[str, np.ndarray] = {}
        try:
            for name, p in self._param_items:
                sname = by_param.get(id(p), name)
                if sname not in snap:
                    raise MXNetError(
                        f"swap_weights: checkpoint step {state['step']} "
                        f"is missing parameter {sname!r} — rejected, "
                        "keeping the current weights")
                v = snap[sname]
                staging[name] = (v.asnumpy() if hasattr(v, "asnumpy")
                                 else np.asarray(v))
            # the fingerprint gate: the decode executable's structural
            # identity recomputed over the STAGED arrays must equal the
            # serving one — same structure means the compiled step
            # keeps working unchanged
            variant = ("decode", self._ps, self._S)
            sarrs = [a._data for a in self._state.values()]
            cur = memwatch.fingerprint(self._fingerprint_parts(
                variant, list(self._params()) + sarrs))
            new = memwatch.fingerprint(self._fingerprint_parts(
                variant, [staging[n] for n, _ in self._param_items]
                + sarrs))
            if new != cur:
                raise MXNetError(
                    f"swap_weights: checkpoint step {state['step']} has "
                    "a different decode fingerprint (param shapes/dtypes "
                    "or adapter structure changed) — rejected, keeping "
                    "the current weights")
        except MXNetError as e:
            staging.clear()
            telemetry.record("weight_swap", executor="ServingEngine",
                             rejected=True, reason=str(e),
                             generation=self._weight_generation)
            raise
        verify_ms = (time.perf_counter() - t0) * 1e3
        with self._swap_lock:
            self._staging = staging
            self._swap_pending = {
                "step": int(state["step"]),
                "staged_bytes": int(sum(a.nbytes
                                        for a in staging.values())),
                "verify_ms": verify_ms,
            }
        if not self._running:
            # idle engine: no stream boundary will come around — flip now
            self._apply_pending_swap()
        return int(state["step"])

    def _apply_pending_swap(self) -> None:
        """Flip staged weights into the served params (stream-boundary
        only: the run loop between bursts, or swap_weights on an idle
        engine).  ``_params()`` reads ``p.data()`` live each dispatch, so
        set_data IS the flip — the compiled executable never changes."""
        with self._swap_lock:
            pending, staging = self._swap_pending, self._staging
            self._swap_pending = None
            if pending is None:
                return
        t0 = time.perf_counter()
        for name, p in self._param_items:
            p.set_data(staging[name])
        self._weight_generation += 1
        # swap-aware prefix-cache invalidation: every cached prefix was
        # stamped with the generation it was computed under; at the flip
        # all older entries drop (and release their pages) BEFORE the
        # next admission can fork them — a post-swap request can never
        # decode against old-weight KV pages (tests/test_serving_swap).
        if self._prefix is not None:
            dropped = self._prefix.invalidate_stale(self._weight_generation)
            for e in dropped:
                self._release_prefix_entry(e)
            if dropped:
                telemetry.record(
                    "serve_prefix_invalidate", executor="ServingEngine",
                    dropped=len(dropped),
                    generation=self._weight_generation)
        # drain the staging census: post-flip the transient 2x-weights
        # window is over and memwatch's "staging" category reads empty
        self._staging = {}
        telemetry.record_weight_swap(
            generation=self._weight_generation,
            staged_bytes=pending["staged_bytes"],
            verify_ms=pending["verify_ms"],
            flip_ms=(time.perf_counter() - t0) * 1e3,
            step=pending["step"])

    @property
    def weight_generation(self) -> int:
        """How many hot-swaps have been applied (0 = boot weights)."""
        return self._weight_generation

    def run(self, max_steps: int = 1_000_000) -> None:
        """Drive the engine until queue, arrivals and slots are empty."""
        self._ensure_compiled()
        guard = 0
        spins = 0
        self._running = True
        try:
            while True:
                self._pump_arrivals()
                admitted = self._admit_ready()
                active = sum(1 for m in self._slots if m is not None)
                if not active:
                    if self._arrivals:
                        # idle: fast-forward the step clock to the next
                        # join
                        self._step_n = max(self._step_n,
                                           self._arrivals[0][0])
                        continue
                    if self._sched.depth:
                        # all slots free, none admitted: tolerate ONE
                        # spin — a concurrent submit (the replica
                        # server's handler threads) can land between
                        # _admit_ready and the depth check; a request
                        # that truly cannot fit fails again next pass
                        spins += 1
                        if spins > 1:
                            raise MXNetError(
                                "serving queue non-empty but no request "
                                "admissible (pool/config too small?)")
                        continue
                    break
                spins = 0
                spec = self._spec_k > 0 and self._cache is not None
                want = self._spec_k + 1 if spec else self._stream_every
                burst = self._ensure_pages(want)
                # request ids decoding THIS burst (with their trace
                # context), captured before _consume can evict
                # finished ones
                burst_ids = [(m.req.id, m.req.trace_id, m.req.sampled)
                             for m in self._slots
                             if m is not None and not m.done]
                t_burst0 = time.perf_counter()
                if spec and burst == self._spec_k + 1:
                    # one ragged verify dispatch per boundary: draft
                    # proposes K, the target checks all K (+ bonus) in
                    # ONE compiled step; per-slot accepted counts are
                    # device values
                    self._ensure_verify()
                    handle, counts_dev = self._dispatch_spec()
                    self._book_pending_compile()
                    t_stream0 = time.perf_counter()
                    self._consume_spec(handle, counts_dev)
                    burst = self._spec_k + 1  # guard accounting
                else:
                    # plain path (also the fallback when pool pressure
                    # or a near-budget request shrinks the burst below
                    # the verify window)
                    handles = [self._dispatch_step()
                               for _ in range(burst)]
                    self._book_pending_compile()
                    t_stream0 = time.perf_counter()
                    self._consume(handles)
                t_stream1 = time.perf_counter()
                # per-request trace spans at BURST cadence, never per
                # token (docs/OBSERVABILITY.md §Serving traces): one
                # serve_decode span per in-flight request covering
                # dispatch through token readback, plus one serve_stream
                # span for the readback boundary carrying the occupancy
                # gauges trace_report turns into the slot-occupancy
                # timeline.  record_span is the zero-cost-when-off
                # retroactive form — the dispatch loop above never pays
                # for tracing.
                if telemetry.spans_enabled():
                    for rid, tid, samp in burst_ids:
                        if tid is not None and not samp:
                            continue  # head-based sampling dropped it
                        telemetry.record_span(
                            "serve_decode", t_burst0, t_stream1,
                            request_id=rid, steps=burst,
                            **({"trace_id": tid} if tid else {}))
                    telemetry.record_span("serve_stream", t_stream0,
                                          t_stream1,
                                          active_slots=len(burst_ids),
                                          queue_depth=self._sched.depth)
                telemetry.record_serve_state(queue_depth=self._sched.depth,
                                             active_slots=active,
                                             precision=self._precision)
                if self._swap_pending is not None:
                    # the stream boundary IS the swap point: this burst's
                    # tokens are consumed, nothing is in flight — the
                    # next burst dispatches against the new weights
                    self._apply_pending_swap()
                guard += burst
                if guard > max_steps:
                    raise MXNetError(
                        f"serving run exceeded {max_steps} decode "
                        "steps (runaway request set?)")
        finally:
            self._running = False
        self._ring.drain()

    @property
    def step_count(self) -> int:
        return self._step_n

    # ------------------------------------------------------------------
    # compiled step construction
    # ------------------------------------------------------------------
    def _params(self):
        if self._param_items is None:
            model = getattr(self._adapter, "model", None)
            self._param_items = (list(model.collect_params().items())
                                 if model is not None else [])
        return tuple(p.data(self._ctx)._data for _, p in self._param_items)

    def _traced(self, body):
        """Run ``body`` under the parameter-substitution trace (the
        CachedOp recipe): model code sees traced param values, dropout/BN
        stay in inference mode."""
        from .. import autograd
        from ..gluon.parameter import begin_trace, end_trace

        def fn(param_arrays, *arrays):
            from ..ndarray import NDArray

            param_map = {p: NDArray(a, ctx=self._ctx)
                         for (_, p), a in zip(self._param_items,
                                              param_arrays)}
            nds = [NDArray(a, ctx=self._ctx) for a in arrays]
            prev = begin_trace(param_map, self._ctx)
            prev_rec = autograd.set_recording(False)
            prev_train = autograd.set_training(False)
            try:
                # every serving executable traces under the pass
                # pipeline's scope (quant rewrites, fused-kernel
                # substitution) — one place, all variants
                with self._pipeline.scope():
                    out = body(nds)
            finally:
                end_trace(prev)
                autograd.set_recording(prev_rec)
                autograd.set_training(prev_train)
            return tuple(o._data for o in out)

        return fn

    def _decode_body(self, nds):
        from .. import ndarray as F
        from ..ndarray import NDArray
        import jax.numpy as jnp

        state = dict(zip(self._names, nds))
        tok, pos, table = state["tok"], state["pos"], state["table"]
        lengths = pos + 1  # rows valid incl. the one written this step
        Lmax = self._P * self._ps
        keep = NDArray(
            (jnp.arange(Lmax, dtype=jnp.float32)[None, :]
             < lengths._data.astype(jnp.float32)[:, None])
            .astype(jnp.float32), ctx=self._ctx)
        pages, rows = page_coords(table, pos, self._ps)
        extra = {k: state[k] for k in self._extra_names}
        pools = [state[k] for k in self._pool_names]
        new_state = dict(state)
        if not self._sampling:
            # the original greedy body, op-for-op (the parity-pinned
            # default: same trace, same fingerprint)
            nxt, new_extra, new_pools = self._adapter.decode(
                F, tok, pos, table, keep, pages, rows, lengths, extra,
                pools)
        else:
            logits, new_extra, new_pools = self._adapter.decode_logits(
                F, tok, pos, table, keep, pages, rows, lengths, extra,
                pools)
            nxt, new_state["rng"] = self._select_token(F, state, logits)
            new_extra = self._adapter.advance_extra(F, new_extra, nxt,
                                                    pos)
        new_state["tok"] = nxt.reshape(self._S, 1)
        new_state["pos"] = pos + 1
        new_state.update(new_extra)
        new_state.update(dict(zip(self._pool_names, new_pools)))
        return (nxt,) + tuple(new_state[k] for k in self._names)

    def _select_token(self, F, state, logits):
        """Traced token selection under sampling.  Slots with
        temperature 0 take the EXACT argmax-over-log-softmax op sequence
        the greedy body traces — ``where`` selects per slot, so a greedy
        request in a sampling engine stays bitwise identical to the
        greedy engine (tests/test_serving_sampling).  Sampling slots
        take gumbel-argmax over the temperature/top-k/top-p-filtered
        logits, with per-slot RNG keys advanced as device state."""
        from ..ndarray import NDArray
        import jax.numpy as jnp

        greedy = F.cast(F.argmax(logits.log_softmax(axis=-1), axis=-1),
                        "int32")
        temp = state["temp"]._data
        filt = _filter_logits(logits._data, temp, state["topk"]._data,
                              state["topp"]._data)
        new_keys, subs = _split_keys(state["rng"]._data, 1)
        g = _gumbel_rows(subs[:, 0], filt.shape[-1])
        sampled = jnp.argmax(filt + g, axis=-1).astype(jnp.int32)
        nxt = jnp.where(temp > 0, sampled, greedy._data)
        return (NDArray(nxt, ctx=self._ctx),
                NDArray(new_keys, ctx=self._ctx))

    def _shape_sig(self, arrays):
        return tuple((tuple(np.shape(a)), str(getattr(a, "dtype", "?")))
                     for a in arrays)

    def _fingerprint_parts(self, variant, arg_arrays):
        """Restart-stable structural name of one executable for
        ``memwatch.fingerprint`` (shapes/dtypes/config, no object ids):
        compile telemetry events and the ``swap_weights`` gate read it."""
        model = getattr(self._adapter, "model", None)
        return (("ServingEngine",) + tuple(variant)
                + (type(self._adapter).__name__,
                   type(model).__name__ if model is not None else "",
                   tuple(self._adapter.signature()),
                   self._pipeline.signature(),
                   self._S, self._ps, self._P, self._max_len,
                   self._shape_sig(arg_arrays)))

    def _pend_compile(self, jfn, args, variant, site):
        """Note ``jfn``'s compile for booking after its first call
        (``_book_pending_compile``) and hand it back."""
        # fingerprint over params + operands
        flat = list(args[0]) + list(args[1:])
        self._pending_compile[site] = {
            "parts": self._fingerprint_parts(variant, flat), "jitted": jfn,
            "args": memwatch.shape_structs(args)}
        return jfn

    def _ensure_compiled(self):
        if self._run is not None:
            return
        import jax

        self._adapter.warmup(self._ctx)  # deferred-init shapes first
        self._params()  # resolve the param list before tracing
        jfn = jax.jit(self._traced(self._decode_body))
        args = (self._params(),) + tuple(a._data
                                         for a in self._state.values())
        self._run = self._pend_compile(jfn, args,
                                       ("decode", self._ps, self._S),
                                       "serving_decode")

    def _ensure_prefill(self, src_row):
        if self._prefill_run is not None:
            return
        import jax

        adapter = self._adapter
        self._prefill_names = list(adapter.prefill_names)

        def body(nds):
            from .. import ndarray as F

            out = adapter.prefill(F, nds[0])
            return [out[k] for k in adapter.prefill_names]

        jfn = jax.jit(self._traced(body))
        import jax.numpy as jnp

        args = (self._params(), jnp.asarray(src_row))
        self._prefill_run = self._pend_compile(
            jfn, args, ("prefill", src_row.shape[1]), "serving_prefill")

    # ------------------------------------------------------------------
    # teacher-forced multi-position bodies: speculative verify + prefix
    # ingest.  Both unroll K(+1) decode_logits bodies inside ONE jitted
    # step — per-slot proposal counts / ingest lengths are device
    # values, so the SAME executable serves every ragged mix (the
    # ragged-paged-attention property, applied along the position axis).
    #
    # KV safety: body j writes position pos+j BEFORE attending lengths
    # pos+j+1, so rows past a slot's accepted/ingested count hold
    # teacher-forced garbage — but the next dispatch starts at the
    # slot's new pos and REWRITES each such row before it is ever
    # attended (the same invariant the plain decode loop relies on for
    # freshly-granted pages).  Writes beyond a slot's granted pages
    # land on the zero table entry -> trash page.
    # ------------------------------------------------------------------
    def _chain_logits(self, F, state, feed, steps):
        """Unroll ``steps`` decode_logits bodies, teacher-forcing
        ``feed[:, j]`` at position pos+j.  Returns (logits list,
        final extra, final pools) — trace-time only."""
        from ..ndarray import NDArray
        import jax.numpy as jnp

        pos, table = state["pos"], state["table"]
        extra = {k: state[k] for k in self._extra_names}
        pools = [state[k] for k in self._pool_names]
        Lmax = self._P * self._ps
        out = []
        for j in range(steps):
            pos_j = pos + j
            lengths = pos_j + 1
            keep = NDArray(
                (jnp.arange(Lmax, dtype=jnp.float32)[None, :]
                 < lengths._data.astype(jnp.float32)[:, None])
                .astype(jnp.float32), ctx=self._ctx)
            pages, rows = page_coords(table, pos_j, self._ps)
            tok_j = NDArray(feed[:, j:j + 1], ctx=self._ctx)
            logits, extra, pools = self._adapter.decode_logits(
                F, tok_j, pos_j, table, keep, pages, rows, lengths,
                extra, pools)
            out.append(logits)
        return out, extra, pools

    def _verify_body(self, nds):
        """The ("verify", K) executable: teacher-force [tok, d_1..d_K]
        through K+1 decode bodies, accept the longest draft prefix the
        target agrees with (argmax equality under greedy; the standard
        u < p(d) test under sampling), emit a correction/bonus token
        from the first disagreeing position, and advance per-slot state
        by the ACCEPTED count — a device value.  Greedy rows are
        token-for-token the plain decode stream; sampling rows draw
        from exactly the non-speculative output distribution
        (accept/resample, Leviathan et al.)."""
        from .. import ndarray as F
        from ..ndarray import NDArray
        import jax
        import jax.numpy as jnp

        K = self._spec_k
        S = self._S
        n_state = len(self._names)
        state = dict(zip(self._names, nds[:n_state]))
        draft, nprop = nds[n_state], nds[n_state + 1]
        tok, pos = state["tok"], state["pos"]
        d = draft._data                                   # (S, K)
        feed = jnp.concatenate([tok._data, d], axis=1)    # (S, K+1)
        logits_l, extra, pools = self._chain_logits(F, state, feed, K + 1)
        greedy = jnp.stack(
            [F.cast(F.argmax(lg.log_softmax(axis=-1), axis=-1),
                    "int32")._data for lg in logits_l], axis=1)  # (S,K+1)
        kclip = jnp.clip(nprop._data, 0, K)               # (S,)
        jj = jnp.arange(K, dtype=jnp.int32)[None, :]
        if self._sampling:
            temp = state["temp"]._data
            filt = jnp.stack(
                [_filter_logits(lg._data, temp, state["topk"]._data,
                                state["topp"]._data)
                 for lg in logits_l], axis=1)             # (S, K+1, V)
            V = filt.shape[-1]
            new_keys, subs = _split_keys(state["rng"]._data, 2 * K + 1)
            u = jnp.stack([_uniform_rows(subs[:, j])
                           for j in range(K)], axis=1) if K else \
                jnp.zeros((S, 0), jnp.float32)            # (S, K)
            gum = jnp.stack([_gumbel_rows(subs[:, K + j], V)
                             for j in range(K + 1)], axis=1)  # (S,K+1,V)
            probs = jax.nn.softmax(filt, axis=-1)
            pd = jnp.take_along_axis(
                probs[:, :K], d[..., None].astype(jnp.int32),
                axis=-1)[..., 0]                          # (S, K)
            # deterministic draft (q = one point mass): accept w.p. p(d)
            ok = jnp.where(temp[:, None] > 0, u < pd,
                           d == greedy[:, :K])
        else:
            ok = d == greedy[:, :K]
        valid = jj < kclip[:, None]
        accept = jnp.cumprod((ok & valid).astype(jnp.int32), axis=1)
        a = accept.sum(axis=1).astype(jnp.int32)          # (S,)
        tau_g = jnp.take_along_axis(greedy, a[:, None], axis=1)[:, 0]
        if self._sampling:
            sampled = jnp.argmax(filt + gum, axis=-1) \
                .astype(jnp.int32)                        # (S, K+1)
            # resample on rejection: p' ∝ p with the rejected draft
            # token removed (q is a point mass, so max(0, p-q)
            # renormalized is p zeroed at d)
            onehot = jax.nn.one_hot(d, V, dtype=bool)     # (S, K, V)
            resampled = jnp.argmax(
                jnp.where(onehot, -jnp.inf, filt[:, :K]) + gum[:, :K],
                axis=-1).astype(jnp.int32) if K else sampled[:, :0]
            resampled = jnp.concatenate(
                [resampled, sampled[:, K:]], axis=1)      # (S, K+1)
            rejected = a < kclip  # a < proposals => a real disagreement
            tau_s = jnp.where(rejected[:, None], resampled, sampled)
            tau_s = jnp.take_along_axis(tau_s, a[:, None], axis=1)[:, 0]
            tau = jnp.where(state["temp"]._data > 0, tau_s, tau_g) \
                .astype(jnp.int32)
        else:
            tau = tau_g
        dpad = jnp.concatenate([d, jnp.zeros((S, 1), jnp.int32)], axis=1)
        jj1 = jnp.arange(K + 1, dtype=jnp.int32)[None, :]
        tout = jnp.where(jj1 < a[:, None], dpad,
                         jnp.where(jj1 == a[:, None], tau[:, None], 0)
                         ).astype(jnp.int32)              # (S, K+1)
        counts = a + 1
        new_state = dict(state)
        new_state["tok"] = NDArray(tau[:, None], ctx=self._ctx)
        new_state["pos"] = NDArray(pos._data + counts, ctx=self._ctx)
        if self._sampling:
            new_state["rng"] = NDArray(new_keys, ctx=self._ctx)
        new_state.update(extra)
        new_state.update(dict(zip(self._pool_names, pools)))
        return ((NDArray(tout, ctx=self._ctx),
                 NDArray(counts, ctx=self._ctx))
                + tuple(new_state[k] for k in self._names))

    def _ingest_body(self, nds):
        """The ("ingest", K) executable: teacher-force up to K prefix
        tokens per slot into the paged KV cache (per-slot ragged length
        ``n``; n=0 slots are untouched — their garbage writes land on
        rows the decode loop rewrites before attending, or on the trash
        page).  Logits are discarded: ingest exists purely for its KV
        writes."""
        from .. import ndarray as F
        from ..ndarray import NDArray
        import jax.numpy as jnp

        K = self._prefix_chunk
        n_state = len(self._names)
        state = dict(zip(self._names, nds[:n_state]))
        feed, n = nds[n_state], nds[n_state + 1]
        _, extra, pools = self._chain_logits(F, state, feed._data, K)
        new_state = dict(state)
        new_state["pos"] = NDArray(
            state["pos"]._data + jnp.clip(n._data, 0, K), ctx=self._ctx)
        new_state.update(extra)
        new_state.update(dict(zip(self._pool_names, pools)))
        return tuple(new_state[k] for k in self._names)

    def _ensure_verify(self):
        if self._vrun is not None:
            return
        import jax
        import jax.numpy as jnp

        self._ensure_compiled()
        jfn = jax.jit(self._traced(self._verify_body))
        args = (self._params(),) \
            + tuple(a._data for a in self._state.values()) \
            + (jnp.zeros((self._S, self._spec_k), jnp.int32),
               jnp.zeros((self._S,), jnp.int32))
        self._vrun = self._pend_compile(
            jfn, args, ("verify", self._spec_k, self._ps, self._S),
            "serving_verify")

    def _ensure_ingest(self):
        if self._irun is not None:
            return
        import jax
        import jax.numpy as jnp

        self._ensure_compiled()
        jfn = jax.jit(self._traced(self._ingest_body))
        args = (self._params(),) \
            + tuple(a._data for a in self._state.values()) \
            + (jnp.zeros((self._S, self._prefix_chunk), jnp.int32),
               jnp.zeros((self._S,), jnp.int32))
        self._irun = self._pend_compile(
            jfn, args, ("ingest", self._prefix_chunk, self._ps, self._S),
            "serving_ingest")

    def _book_pending_compile(self):
        """Book plain-jit compiles AFTER the dispatching burst (the hot
        body never pays the analysis retrace).  Only entries whose first
        call already happened (wall_s stamped) are booked."""
        done = [s for s, r in self._pending_compile.items()
                if "wall_s" in r]
        for site in done:
            rec = self._pending_compile.pop(site)
            memwatch.note_compile(
                "ServingEngine", rec["parts"], wall_s=rec["wall_s"],
                site=site, jitted=rec["jitted"], args=rec["args"])

    # ------------------------------------------------------------------
    # the hot dispatch body (mxlint HOT_PATH_ENTRIES: no host syncs)
    # ------------------------------------------------------------------
    def _dispatch_step(self):
        """Dispatch ONE compiled decode step: device state chains to
        device state, the per-step token vector rides out as a lazy
        AsyncResult through the bounded ring.  Never blocks on device
        results (make_room bounds the window oldest-first)."""
        self._ring.make_room(self._stream_every, wait_span=False)
        arrays = [a._data for a in self._state.values()]
        t0 = time.perf_counter()
        outs = self._run(self._params(), *arrays)
        if "serving_decode" in self._pending_compile:
            self._pending_compile["serving_decode"].setdefault(
                "wall_s", time.perf_counter() - t0)
        toks = outs[0]
        from ..ndarray import NDArray

        for name, arr in zip(self._names, outs[1:]):
            self._state[name] = NDArray(arr, ctx=self._ctx)
        self._step_n += 1
        handle = AsyncResult(toks, step=self._step_n,
                             executor="ServingEngine", ring=self._ring)
        self._ring.admit(handle)
        return handle

    def _propose(self):
        """Host-side draft proposals for every live slot: (S, K) int32
        token matrix + (S,) proposal counts (ragged — 0 for empty/done
        slots and for requests the draft has nothing for)."""
        from .speculative import traced_propose

        K = self._spec_k
        draft = np.zeros((self._S, K), np.int32)
        nprop = np.zeros((self._S,), np.int32)
        for slot, meta in enumerate(self._slots):
            if meta is None or meta.done:
                continue
            toks = list(traced_propose(self._draft, meta.req,
                                       meta.req.stream.tokens, K))[:K]
            if toks:
                draft[slot, :len(toks)] = toks
                nprop[slot] = len(toks)
        return draft, nprop

    def _dispatch_spec(self):
        """Dispatch ONE compiled verify step (K draft tokens checked +
        one correction/bonus emitted per slot).  Same no-host-sync
        contract as _dispatch_step: the (S, K+1) token matrix rides out
        lazily; the per-slot counts force together with it at the
        stream boundary."""
        import jax.numpy as jnp

        draft, nprop = self._propose()
        self._last_nprop = nprop
        self._ring.make_room(self._stream_every, wait_span=False)
        arrays = [a._data for a in self._state.values()]
        t0 = time.perf_counter()
        outs = self._vrun(self._params(), *arrays, jnp.asarray(draft),
                          jnp.asarray(nprop))
        if "serving_verify" in self._pending_compile:
            self._pending_compile["serving_verify"].setdefault(
                "wall_s", time.perf_counter() - t0)
        tout, counts = outs[0], outs[1]
        from ..ndarray import NDArray

        for name, arr in zip(self._names, outs[2:]):
            self._state[name] = NDArray(arr, ctx=self._ctx)
        self._step_n += 1
        handle = AsyncResult(tout, step=self._step_n,
                             executor="ServingEngine", ring=self._ring)
        self._ring.admit(handle)
        return handle, counts

    def _consume_spec(self, handle, counts_dev):
        """Stream boundary for a verify dispatch: one (S, K+1) token
        matrix + per-slot emitted counts land together.  Row layout per
        slot: the accepted draft tokens, then the correction/bonus
        token, then padding."""
        tout = handle.asnumpy()
        counts = np.asarray(counts_dev)
        proposed = int(self._last_nprop.sum()) \
            if self._last_nprop is not None else 0
        accepted = 0
        for slot, meta in enumerate(self._slots):
            if meta is None:
                continue
            c = int(counts[slot])
            meta.pos += c  # device pos advanced by the accepted count
            if meta.done:
                continue
            req = meta.req
            accepted += max(0, c - 1)
            for i in range(c):
                tok = int(tout[slot, i])
                req.stream.append(tok)
                if req.t_first_token is None:
                    req.t_first_token = time.perf_counter()
                if tok == req.eos_id:
                    meta.done = True
                    req.stream.finish("eos")
                    break
                if len(req.stream) >= req.max_new_tokens:
                    meta.done = True
                    req.stream.finish("length")
                    break
        self._spec_proposed += proposed
        self._spec_accepted += accepted
        # the verify boundary is per-burst, not per-request: name the
        # sampled traces that shared it so serve_report can charge the
        # rejected-draft work back to each request tree
        tids = [m.req.trace_id for m in self._slots
                if m is not None and m.req.trace_id and m.req.sampled]
        telemetry.record_spec_verify(
            proposed=proposed, accepted=accepted,
            **({"trace_ids": tids} if tids else {}))
        for slot, meta in enumerate(self._slots):
            if meta is not None and meta.done:
                self._evict(slot, meta)

    # ------------------------------------------------------------------
    # host-side scheduling (stream boundaries only)
    # ------------------------------------------------------------------
    def _pump_arrivals(self):
        while self._arrivals and self._arrivals[0][0] <= self._step_n:
            _, req = self._arrivals.pop(0)
            self.submit(req)

    def _admit_ready(self) -> int:
        free = [i for i, m in enumerate(self._slots) if m is None]
        if not free or not self._sched.depth:
            return 0
        pages_free = (self._cache.pages_free if self._cache is not None
                      else len(free))
        ready = self._sched.pop_ready(len(free), pages_free, self._ps)
        n = 0
        for i, (slot, req) in enumerate(zip(free, ready)):
            if self._admit(slot, req):
                n += 1
                continue
            # pool too tight for this request's prefix right now: it
            # went back to the queue head inside _admit; park the rest
            # behind it in order (requeue prepends, so walk backwards)
            for r in reversed(ready[i + 1:]):
                self._sched.requeue(r)
            break
        return n

    def _admit(self, slot: int, req: Request) -> bool:
        st = self._state
        if req.generation_at_admit is None:
            # cause attribution: a request admitted under generation G
            # that finishes under G' > G decoded across a weight-swap
            # window (scheduler.Request §Request tracing)
            req.generation_at_admit = self._weight_generation
        # the queue leg of the request-id span tree: queue-start ->
        # admit, recorded retroactively from the scheduler's SLO stamps
        # (t_queue_start, not t_submit: a preempted request's re-queue
        # span must not swallow its first admission's prefill+decode)
        if req.t_queue_start is not None and req.t_admit is not None \
                and telemetry.spans_enabled() \
                and (req.trace_id is None or req.sampled):
            telemetry.record_span(
                "serve_queue", req.t_queue_start, req.t_admit,
                request_id=req.id,
                **({"trace_id": req.trace_id} if req.trace_id else {}))
        if self._cache is not None:
            # pool-pressure attribution: a denied page grant for this
            # slot now names the request (and trace) it starved
            self._cache.annotate(
                slot, request_id=req.id,
                **({"trace_id": req.trace_id} if req.trace_id else {}))
        src = self._adapter.prefill_src(req)
        if src is not None:
            self._prefill_into(slot, req, src)
        st["tok"][slot, 0] = req.bos_id
        st["pos"][slot] = 0
        if self._sampling:
            self._install_sampling(slot, req)
        self._adapter.install(st, slot, req)
        self._admit_seq += 1
        meta = _Active(req, self._admit_seq)
        self._slots[slot] = meta
        if req.prefix.size:
            if not self._install_prefix(slot, meta, req):
                self._rollback_admit(slot, req)
                return False
        return True

    def _prefill_into(self, slot: int, req: Request, src) -> None:
        """Run (or reuse) the prefill executable for one admission.
        With the prefix cache on, identical prefill inputs hit a cached
        device copy of the output rows — the 'prefill once' half of
        prefix reuse (the encoder memory for a repeated source)."""
        st = self._state
        names = list(self._adapter.prefill_names)
        pkey = (prefix_key("prefill", src)
                if self._prefix is not None else None)
        if pkey is not None:
            e = self._prefix.get(pkey, self._weight_generation)
            if e is not None:
                for name in names:
                    st[name][slot] = e["payload"]["rows"][name]
                req.prefill_ms = 0.0
                if req.prefix_hit is None:
                    req.prefix_hit = True
                telemetry.record_serve_prefix(
                    kind="prefill", hit=True, tokens=int(req.tokens.size),
                    request_id=req.id,
                    **({"trace_id": req.trace_id} if req.trace_id else {}))
                return
        self._ensure_prefill(src)
        import jax.numpy as jnp

        t0 = time.perf_counter()
        outs = self._prefill_run(self._params(), jnp.asarray(src))
        t1 = time.perf_counter()
        # prefill_ms is DISPATCH wall (async queueing, like step
        # events — see telemetry.record_step's contract)
        req.prefill_ms = round((t1 - t0) * 1e3, 3)
        if telemetry.spans_enabled() \
                and (req.trace_id is None or req.sampled):
            telemetry.record_span(
                "serve_prefill", t0, t1, request_id=req.id,
                **({"trace_id": req.trace_id} if req.trace_id else {}))
        if "serving_prefill" in self._pending_compile:
            self._pending_compile["serving_prefill"].setdefault(
                "wall_s", time.perf_counter() - t0)
            self._book_pending_compile()
        from ..ndarray import NDArray

        rows = {}
        for name, arr in zip(self._prefill_names, outs):
            row = NDArray(arr, ctx=self._ctx)[0]
            st[name][slot] = row
            rows[name] = row
        if pkey is not None:
            for d in self._prefix.put(pkey, "prefill",
                                      self._weight_generation,
                                      {"rows": rows, "owner": None}):
                self._release_prefix_entry(d)
            req.prefix_hit = False
            telemetry.record_serve_prefix(
                kind="prefill", hit=False, tokens=int(req.tokens.size),
                request_id=req.id,
                **({"trace_id": req.trace_id} if req.trace_id else {}))

    def _install_sampling(self, slot: int, req: Request) -> None:
        """Per-slot sampling state at admission.  The RNG key is a pure
        function of the request's seed — decoding is reproducible across
        restarts, slot assignments and recompute-preemptions (the key
        re-derives identically on re-admission)."""
        import jax

        st = self._state
        st["temp"][slot] = req.temperature
        st["topk"][slot] = req.top_k
        st["topp"][slot] = req.top_p
        if req.seed is None:
            # stamped on the request so a preemption re-derives the
            # same stream (deterministic re-decode, like greedy)
            req.seed = int.from_bytes(os.urandom(4), "little")
        st["rng"][slot] = np.asarray(jax.random.PRNGKey(req.seed))

    # ------------------------------------------------------------------
    # prefix cache: COW page forks + teacher-forced ingest
    # ------------------------------------------------------------------
    def _install_prefix(self, slot: int, meta: _Active,
                        req: Request) -> bool:
        """Put the request's forced decoder prefix into the slot's KV
        pages: fork a cached entry's pages (hit) or teacher-force the
        tokens through the ("ingest", K) executable and register the
        result (miss).  Returns False when the pool cannot hold the
        prefix even after dropping cache entries — the caller rolls the
        admission back."""
        T = int(req.prefix.size)
        key = (prefix_key(req.tokens, req.bos_id, req.prefix)
               if self._prefix is not None else None)
        if key is not None:
            e = self._prefix.get(key, self._weight_generation)
            if e is not None and self._fork_from_entry(slot, e, req):
                meta.pos = T
                if req.prefix_hit is None:
                    req.prefix_hit = True
                telemetry.record_serve_prefix(
                    kind="pages", hit=True, tokens=T, request_id=req.id,
                    **({"trace_id": req.trace_id} if req.trace_id else {}))
                return True
        need = pages_for(T, self._ps) - len(self._cache.owned(slot))
        if not self._alloc_prefix_pages(slot, need):
            return False
        self._state["table"][slot] = self._cache.table_row(slot, self._P)
        self._ingest_prefix(slot, req)
        meta.pos = T
        if key is not None:
            self._register_prefix(slot, key, T)
            req.prefix_hit = False
            telemetry.record_serve_prefix(
                kind="pages", hit=False, tokens=T, request_id=req.id,
                **({"trace_id": req.trace_id} if req.trace_id else {}))
        return True

    def _fork_from_entry(self, slot: int, e: dict, req: Request) -> bool:
        """Copy-on-write fork: adopt the entry's FULL pages (shared,
        refcounted — never written again: the slot's first write lands
        at pos >= prefix_len) and device-copy the partial tail page into
        a private page the slot may keep writing.  Bitwise-identical
        continuation: the forked slot decodes over the exact pool rows
        the cold ingest produced."""
        st = self._state
        T = int(e["payload"]["len"])
        pages = e["payload"]["pages"]
        full, tail = T // self._ps, T % self._ps
        if full:
            self._cache.adopt(slot, pages[:full])
        if tail:
            got = self._cache.alloc(slot, 1)
            if got is None and self._drop_one_prefix_entry():
                got = self._cache.alloc(slot, 1)
            if not got:
                self._cache.free_slot(slot)  # release the adoption
                st["table"][slot] = 0
                return False
            for name in self._pool_names:
                st[name][got[0]] = st[name][pages[full]]
        st["table"][slot] = self._cache.table_row(slot, self._P)
        st["pos"][slot] = T
        st["tok"][slot, 0] = int(req.prefix[-1])
        return True

    def _register_prefix(self, slot: int, key: str, T: int) -> None:
        """After a cold ingest: share the slot's full prefix pages into
        a cache entry and give the entry a private COPY of the partial
        tail page (the donor keeps writing its own tail at pos >= T —
        the entry's copy must stay frozen)."""
        full, tail = T // self._ps, T % self._ps
        self._admit_seq += 1  # unique owner key per registration
        ek = f"prefix:{key[:16]}:{self._admit_seq}"
        slot_pages = self._cache.owned(slot)
        entry_pages = list(slot_pages[:full])
        if full:
            self._cache.adopt(ek, entry_pages)
        if tail:
            got = self._cache.alloc(ek, 1)
            if got is None:
                # no room for the tail copy: don't register a partial
                # entry (a fork would miss the tail rows)
                self._cache.free_slot(ek)
                return
            st = self._state
            for name in self._pool_names:
                st[name][got[0]] = st[name][slot_pages[full]]
            entry_pages.append(got[0])
        for d in self._prefix.put(key, "pages", self._weight_generation,
                                  {"owner": ek, "pages": entry_pages,
                                   "len": T}):
            self._release_prefix_entry(d)

    def _ingest_prefix(self, slot: int, req: Request) -> None:
        """Teacher-force [bos, p_1..p_{T-1}] into the slot's KV pages in
        ("ingest", K)-sized chunks; afterwards the slot sits at pos=T
        with tok=p_T — exactly the state T forced greedy decode steps
        would have produced, so the continuation is bitwise identical
        to decoding the prefix the slow way."""
        import jax.numpy as jnp
        from ..ndarray import NDArray

        self._ensure_ingest()
        T = int(req.prefix.size)
        feed_seq = np.concatenate(
            [[req.bos_id], req.prefix[:-1]]).astype(np.int32)
        Kc = self._prefix_chunk
        t0 = time.perf_counter()
        done = 0
        while done < T:
            n = min(Kc, T - done)
            feed = np.zeros((self._S, Kc), np.int32)
            feed[slot, :n] = feed_seq[done:done + n]
            nvec = np.zeros((self._S,), np.int32)
            nvec[slot] = n
            arrays = [a._data for a in self._state.values()]
            outs = self._irun(self._params(), *arrays,
                              jnp.asarray(feed), jnp.asarray(nvec))
            if "serving_ingest" in self._pending_compile:
                self._pending_compile["serving_ingest"].setdefault(
                    "wall_s", time.perf_counter() - t0)
                self._book_pending_compile()
            for name, arr in zip(self._names, outs):
                self._state[name] = NDArray(arr, ctx=self._ctx)
            done += n
        self._state["tok"][slot, 0] = int(req.prefix[-1])
        if telemetry.spans_enabled() \
                and (req.trace_id is None or req.sampled):
            telemetry.record_span(
                "serve_ingest", t0, time.perf_counter(),
                request_id=req.id, tokens=T,
                **({"trace_id": req.trace_id} if req.trace_id else {}))

    def _alloc_prefix_pages(self, slot: int, n: int) -> bool:
        """Allocate ``n`` pages for a prefix, dropping LRU cache entries
        under pool pressure (evict-before-preempt: cached prefixes are
        recomputable, live requests cost a full re-decode)."""
        if n <= 0:
            return True
        while self._cache.alloc(slot, n) is None:
            if not self._drop_one_prefix_entry():
                return False
        return True

    def _drop_one_prefix_entry(self) -> bool:
        if self._prefix is None:
            return False
        e = self._prefix.pop_lru("pages")
        if e is None:
            return False
        self._release_prefix_entry(e)
        telemetry.record("serve_prefix_evict", executor="ServingEngine",
                         key=e["key"][:12], tokens=e["payload"]["len"])
        return True

    def _release_prefix_entry(self, e: dict) -> None:
        owner = e["payload"].get("owner")
        if owner is not None and self._cache is not None:
            self._cache.free_slot(owner)

    def _rollback_admit(self, slot: int, req: Request) -> None:
        """Undo a partially-completed admission (prefix didn't fit):
        the slot reads empty again and the request parks at the queue
        head, exactly like a preemption before any decode."""
        st = self._state
        if self._cache is not None:
            self._cache.free_slot(slot)
        st["table"][slot] = 0
        st["pos"][slot] = 0
        st["tok"][slot] = 0
        for name in self._extra_names:
            st[name][slot] = 0
        for name in self._samp_names:
            st[name][slot] = 0
        self._slots[slot] = None
        req.t_admit = None
        req.prefill_ms = 0.0
        self._sched.requeue(req)

    def _ensure_pages(self, burst: int) -> int:
        """Grow page tables so every active, unfinished slot can decode
        ``burst`` more positions; shrinks the burst when the pool runs
        dry.  Under real pool pressure (some slot cannot advance even
        one step) the YOUNGEST-admitted request is preempted back to the
        queue head (vLLM-style recompute preemption — greedy decode is
        deterministic, so re-decoding reproduces its tokens) until the
        survivors can advance; a single request that cannot fit at all
        is a configuration error and raises."""
        if self._cache is None:
            return burst
        while True:
            feas = self._grow_tables(burst)
            if feas > 0:
                return feas
            # evict-before-preempt: cached prefixes are cheap to rebuild
            # (one ingest), a live request costs a full re-decode
            if self._drop_one_prefix_entry():
                continue
            cands = [(m.seq, slot, m) for slot, m in enumerate(self._slots)
                     if m is not None and not m.done]
            if len(cands) <= 1:
                raise MXNetError(
                    "paged KV pool cannot hold even one in-flight "
                    "request — raise MX_SERVE_POOL_PAGES (or lower "
                    f"max_len); pool {self._cache.num_pages} pages of "
                    f"{self._ps} tokens")
            _, slot, meta = max(cands)
            self._preempt(slot, meta)

    def _grow_tables(self, burst: int) -> int:
        """One growth pass; returns the feasible burst (0 = some slot is
        starved)."""
        feas = burst
        st = self._state
        for slot, meta in enumerate(self._slots):
            if meta is None or meta.done:
                continue
            rem = meta.req.max_new_tokens - len(meta.req.stream)
            want = min(burst, rem)
            need_pages = pages_for(meta.pos + want, self._ps)
            have = len(self._cache.owned(slot))
            if need_pages > have:
                if self._cache.alloc(slot, need_pages - have) is None:
                    # pool can't cover the whole growth: grab what's left
                    while (self._cache.pages_free
                           and len(self._cache.owned(slot)) < need_pages):
                        self._cache.alloc(slot, 1)
                st["table"][slot] = self._cache.table_row(slot, self._P)
            cap = self._cache.capacity_rows(slot)
            if cap - meta.pos < want:
                feas = min(feas, cap - meta.pos)
        return max(0, feas)

    def _preempt(self, slot: int, meta: _Active):
        """Evict a request mid-decode under pool pressure: pages free
        NOW, the request returns to the queue HEAD and recomputes from
        scratch on re-admission (its stream resets — deterministic
        greedy decode re-emits identical tokens)."""
        st = self._state
        self._cache.free_slot(slot)
        st["table"][slot] = 0
        st["pos"][slot] = 0
        for name in self._extra_names:
            st[name][slot] = 0
        for name in self._samp_names:
            st[name][slot] = 0
        req = meta.req
        req.stream.tokens.clear()
        req.t_admit = None
        req.t_first_token = None  # TTFT re-stamps after re-admission,
        #                           still measured from the ORIGINAL submit
        req.prefill_ms = 0.0
        req.preemptions += 1
        telemetry.record("serve_preempt", request_id=req.id,
                         decoded=meta.pos,
                         **({"trace_id": req.trace_id}
                            if req.trace_id else {}))
        self._sched.requeue(req)
        self._slots[slot] = None

    def _consume(self, handles):
        """Stream boundary: force the burst's token handles (the ONLY
        host readback), append to per-request streams, finish + evict
        completed requests so their pages free immediately."""
        for h in handles:
            toks = h.asnumpy()
            for slot, meta in enumerate(self._slots):
                if meta is None:
                    continue
                meta.pos += 1  # device pos advanced for every slot
                if meta.done:
                    continue
                req = meta.req
                tok = int(toks[slot])
                req.stream.append(tok)
                if req.t_first_token is None:
                    # stream-boundary resolution: the whole burst's tokens
                    # land together, so TTFT is stamped when the FIRST
                    # one becomes host-visible — the user-visible moment
                    req.t_first_token = time.perf_counter()
                if tok == req.eos_id:
                    meta.done = True
                    req.stream.finish("eos")
                elif len(req.stream) >= req.max_new_tokens:
                    meta.done = True
                    req.stream.finish("length")
        for slot, meta in enumerate(self._slots):
            if meta is not None and meta.done:
                self._evict(slot, meta)

    def _evict(self, slot: int, meta: _Active):
        st = self._state
        if self._cache is not None:
            self._cache.free_slot(slot)
        st["table"][slot] = 0
        st["pos"][slot] = 0
        for name in self._extra_names:
            st[name][slot] = 0
        for name in self._samp_names:
            st[name][slot] = 0
        req = meta.req
        now = time.perf_counter()
        decode_ms = max(0.0, (now - req.t_admit) * 1e3
                        - req.prefill_ms) if req.t_admit else 0.0
        # total_ms is the TRUE submit->finish wall: for a preempted
        # request the per-leg fields cover only the last admission, but
        # the SLO latency must include the discarded service period
        total_ms = ((now - req.t_submit) * 1e3
                    if req.t_submit is not None else None)
        # per-request cause attribution from the breadcrumbs stamped as
        # the request moved through the engine, in priority order: a
        # recompute-preemption dominates (it rewinds the whole stream),
        # then a weight-swap window crossing, then a prefix-cache miss
        # (a request with no prefix candidate attributes to "none")
        if req.preemptions:
            cause = "preempt"
        elif (req.generation_at_admit is not None
              and req.generation_at_admit != self._weight_generation):
            cause = "swap"
        elif req.prefix_hit is False:
            cause = "cache_miss"
        else:
            cause = "none"
        telemetry.record_serve_request(
            queue_wait_ms=req.queue_wait_ms, prefill_ms=req.prefill_ms,
            decode_ms=round(decode_ms, 3), tokens=len(req.stream),
            ttft_ms=round(req.ttft_ms, 3),
            total_ms=round(total_ms, 3) if total_ms is not None else None,
            request_id=req.id, reason=req.stream.finish_reason,
            precision=self._precision, cause=cause,
            preemptions=req.preemptions,
            **({"trace_id": req.trace_id, "sampled": req.sampled}
               if req.trace_id else {}))
        self._slots[slot] = None

    # ------------------------------------------------------------------
    # introspection + batched beam serving
    # ------------------------------------------------------------------
    def statusz_snapshot(self) -> dict:
        """Jax-free engine status for the serving front door's /statusz
        (plain attribute reads — safe from the replica's HTTP handler
        threads while the run loop decodes)."""
        snap = {
            "slots": self._S,
            "active_slots": sum(1 for m in self._slots if m is not None),
            "queue_depth": self._sched.depth,
            "queue_bound": self._sched.bound,
            "steps": self._step_n,
            "weight_generation": self._weight_generation,
            "precision": self._precision,
            "sampling": bool(self._sampling),
            "spec_k": self._spec_k,
            "max_len": self._max_len,
        }
        if self._cache is not None:
            snap["pages_free"] = self._cache.pages_free
            snap["pages_total"] = self._cache.num_pages
        if self._prefix is not None:
            snap["prefix_entries"] = len(self._prefix)
            snap["prefix_hits"] = self._prefix.hits
            snap["prefix_misses"] = self._prefix.misses
        if self._spec_k:
            snap["spec_proposed"] = self._spec_proposed
            snap["spec_accepted"] = self._spec_accepted
        return snap

    def serve_beam(self, requests, beam_size: int = 4, alpha: float = 0.6,
                   sync_every: int = 8) -> Dict[str, np.ndarray]:
        """Batched beam serving: decode ``requests`` with the model's
        device-resident beam search (``translate`` — beam bookkeeping
        stays on device, host syncs every ``sync_every`` steps) in ONE
        batch per (bos, eos) group, and return {id: tokens} trimmed the
        same way the greedy engine streams them (bos dropped, cut just
        after eos).  Quality-first counterpart to :meth:`serve`: no
        continuous batching or mid-flight joins, but each request gets a
        beam_size-wide search instead of a single greedy/sampled lane."""
        model = getattr(self._adapter, "model", None)
        if model is None or not hasattr(model, "translate"):
            raise MXNetError(
                "serve_beam needs an adapter exposing .model with "
                "translate() (the seq2seq TransformerAdapter)")
        from ..ndarray import array as nd_array

        requests = list(requests)
        groups: Dict[tuple, List[Request]] = {}
        for req in requests:
            if req.temperature > 0 or req.prefix.size:
                raise MXNetError(
                    f"request {req.id}: beam serving is search, not "
                    "sampling — temperature/prefix don't apply")
            groups.setdefault((req.bos_id, req.eos_id), []).append(req)
        out: Dict[str, np.ndarray] = {}
        for (bos, eos), grp in groups.items():
            t0 = time.perf_counter()
            src_w = max(int(r.tokens.size) for r in grp)
            src = np.zeros((len(grp), src_w), np.int32)
            for i, r in enumerate(grp):
                src[i, :r.tokens.size] = r.tokens
            max_new = max(r.max_new_tokens for r in grp)
            hyp = model.translate(
                nd_array(src, ctx=self._ctx, dtype="int32"), bos_id=bos,
                eos_id=eos, max_len=max_new + 1, beam_size=beam_size,
                alpha=alpha, sync_every=sync_every,
                page_size=self._ps if self._cache is not None else None)
            t1 = time.perf_counter()
            for i, r in enumerate(grp):
                toks = list(hyp[i, 1:])  # row 0 is bos
                if eos in toks:
                    toks = toks[:toks.index(eos) + 1]
                toks = toks[:r.max_new_tokens]
                for t in toks:
                    r.stream.append(t)
                r.stream.finish("eos" if (toks and toks[-1] == eos)
                                else "length")
                out[r.id] = r.stream.asarray()
                telemetry.record_serve_request(
                    queue_wait_ms=0.0, prefill_ms=0.0,
                    decode_ms=round((t1 - t0) * 1e3, 3),
                    tokens=len(toks),
                    ttft_ms=round((t1 - t0) * 1e3, 3),
                    total_ms=round((t1 - t0) * 1e3, 3),
                    request_id=r.id, reason=r.stream.finish_reason,
                    precision=self._precision, beam=beam_size)
        return out
