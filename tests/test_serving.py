"""Inference serving: continuous batching + paged KV-cache decode
(docs/SERVING.md; ISSUE 11 acceptance).

Covers: bitwise paged-vs-dense attend parity, engine-greedy ==
standalone translate(beam_size=1) token-for-token, the one-executable
property on a mixed-length mid-flight trace (exactly one decode + one
prefill compile event), continuous-batching slot/page reuse, scheduler
backpressure, pool exhaustion, serve
telemetry + prometheus gauges, the Pallas ragged paged kernel, and the
FullPrefixAdapter decoder-only path.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import memwatch, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer import (DenseStepCache, Transformer,
                                          _attend_cached, label_smoothed_ce)
from mxnet_tpu.serving import (ContinuousBatchingScheduler, FullPrefixAdapter,
                               PagedKVCache, Request, ServingEngine,
                               TransformerAdapter, gather_pages, page_coords,
                               paged_attend, write_page)

PAD, BOS, EOS = 0, 1, 2


@pytest.fixture
def tele(tmp_path):
    telemetry.reset()
    memwatch.reset()
    telemetry.enable(str(tmp_path))
    yield telemetry
    telemetry.reset()
    memwatch.reset()


def _tiny_model(vocab=16, max_length=48):
    mx.random.seed(0)
    net = Transformer(vocab, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=max_length, dropout=0.0)
    net.initialize(mx.init.Xavier())
    return net


def _reverse_batch(rng, B, L=6, vocab=16):
    src = np.zeros((B, L + 1), np.int32)
    tgt_in = np.zeros((B, L + 2), np.int32)
    tgt_out = np.zeros((B, L + 2), np.int32)
    for b in range(B):
        toks = rng.randint(3, vocab, L)
        src[b, :L] = toks
        rev = toks[::-1]
        tgt_in[b, 0] = BOS
        tgt_in[b, 1:L + 1] = rev
        tgt_out[b, :L] = rev
        tgt_out[b, L] = EOS
    return src, tgt_in, tgt_out


@pytest.fixture(scope="module")
def trained():
    """Tiny transformer memorizing the reverse task + its train batch —
    sharp logits so greedy decode is decision-stable across executables
    (the engine-vs-translate parity surface)."""
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    net = _tiny_model(max_length=20)
    rng = np.random.RandomState(2)
    src, tgt_in, tgt_out = _reverse_batch(rng, 8)
    step = DataParallelStep(
        net, lambda lo, la: label_smoothed_ce(lo, la, smoothing=0.0),
        mesh=local_mesh(devices=[mx.current_context().jax_device]),
        optimizer="adam", optimizer_params={"learning_rate": 5e-3})
    sb = nd.array(src, dtype="int32")
    tb = nd.array(tgt_in, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32))
    for _ in range(48):
        step.step((sb, tb), lb)
    step.sync_to_block()
    return net, src


# ---------------------------------------------------------------------------
# paged cache math
# ---------------------------------------------------------------------------
def test_paged_attend_bitwise_identical_to_dense():
    """ACCEPTANCE: gather-by-page-table attention over scattered pages is
    bitwise identical to the dense-cache _attend_cached for the same
    tokens (same values through the same eager op executables)."""
    rng = np.random.RandomState(0)
    S, H, hd, ps, P = 3, 4, 8, 4, 2
    C, Lmax = H * hd, ps * P
    dense_K = rng.randn(S, Lmax, C).astype(np.float32)
    dense_V = rng.randn(S, Lmax, C).astype(np.float32)
    q = nd.array(rng.randn(S, 1, C).astype(np.float32))
    # ragged validity per slot
    keep_np = np.zeros((S, Lmax), np.float32)
    for s, L in enumerate((5, 8, 1)):
        keep_np[s, :L] = 1.0
    keep = nd.array(keep_np)

    # scatter the dense rows into an arbitrarily-permuted page pool
    table_np = 1 + rng.permutation(S * P).reshape(S, P).astype(np.int32)
    kpool = np.zeros((S * P + 1, ps, H, hd), np.float32)
    vpool = np.zeros_like(kpool)
    for s in range(S):
        for j in range(P):
            rows = dense_K[s, j * ps:(j + 1) * ps].reshape(ps, H, hd)
            kpool[table_np[s, j]] = rows
            vpool[table_np[s, j]] = dense_V[s, j * ps:(j + 1) * ps] \
                .reshape(ps, H, hd)
    table = nd.array(table_np, dtype="int32")
    kp, vp = nd.array(kpool), nd.array(vpool)

    got_K = gather_pages(kp, table).asnumpy()
    assert (got_K == dense_K).all(), "gather must reconstruct exactly"

    ref = _attend_cached(nd, q, nd.array(dense_K), nd.array(dense_V), keep,
                         H, hd).asnumpy()
    out = paged_attend(nd, q, kp, vp, table, keep, H, hd).asnumpy()
    assert (out == ref).all(), "paged attend must be BITWISE dense attend"


def test_write_page_and_coords_roundtrip():
    rng = np.random.RandomState(1)
    S, H, hd, ps, P = 4, 2, 4, 4, 2
    pool = nd.zeros((S * P + 1, ps, H, hd))
    table = nd.array(1 + np.arange(S * P, dtype=np.int32).reshape(S, P),
                     dtype="int32")
    pos = nd.array(np.array([0, 3, 4, 7], np.int32), dtype="int32")
    vals = nd.array(rng.randn(S, H, hd).astype(np.float32))
    pages, rows = page_coords(table, pos, ps)
    pool = write_page(pool, pages, rows, vals)
    dense = gather_pages(pool, table).asnumpy()  # (S, P*ps, C)
    for s, p in enumerate((0, 3, 4, 7)):
        np.testing.assert_array_equal(
            dense[s, p], vals.asnumpy()[s].reshape(-1))
        assert (np.delete(dense[s], p, axis=0) == 0).all()


def test_paged_allocator_alloc_free_exhaustion():
    cache = PagedKVCache(1, 6, 4, 2, 4)  # 5 usable pages (page 0 trash)
    assert cache.pages_free == 5
    got = cache.alloc("a", 3)
    assert len(got) == 3 and 0 not in got
    assert cache.alloc("b", 3) is None, "all-or-nothing"
    assert cache.pages_free == 2
    assert cache.alloc("b", 2) is not None
    assert cache.pages_free == 0
    assert cache.free_slot("a") == 3
    assert cache.pages_free == 3
    row = cache.table_row("b", 4)
    assert row.shape == (4,) and (row[2:] == 0).all()
    with pytest.raises(MXNetError):
        PagedKVCache(1, 1, 4, 2, 4)  # no room for the trash page


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------
def test_engine_greedy_matches_translate(trained):
    """ACCEPTANCE: greedy decode through the engine — mid-flight
    arrivals, shared slots, paged cache — matches standalone
    translate(beam_size=1) token-for-token on a fixed seed."""
    net, src = trained
    eng = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=3,
                        page_size=4, max_len=12, stream_every=4)
    reqs = [Request(src[i], max_new_tokens=9, bos_id=BOS, eos_id=EOS)
            for i in range(6)]
    out = eng.serve(reqs, arrival_steps=[0, 0, 0, 2, 5, 9])
    for i, r in enumerate(reqs):
        ref = net.translate(nd.array(src[i:i + 1], dtype="int32"),
                            bos_id=BOS, eos_id=EOS, max_len=10,
                            beam_size=1)[0, 1:]
        ref = list(ref)
        if EOS in ref:
            ref = ref[:ref.index(EOS) + 1]
        assert list(out[r.id]) == ref, f"request {i} diverged"
        # the memorized task actually decodes the reversal
        assert list(out[r.id][:6]) == list(src[i, :6][::-1])


def test_one_decode_executable_mixed_lengths(tele, tmp_path):
    """ACCEPTANCE: a mixed-length trace (7/19/33, arriving mid-flight)
    books exactly ONE decode compile event (plus one prefill) — no
    per-length retraces."""
    net = _tiny_model()
    eng = ServingEngine(TransformerAdapter(net, src_max_len=6), slots=3,
                        page_size=8, max_len=34, stream_every=4)
    rng = np.random.RandomState(0)
    reqs = [Request(rng.randint(3, 16, 5), max_new_tokens=n,
                    bos_id=BOS, eos_id=EOS)
            for n in (7, 19, 33)]
    eng.serve(reqs, arrival_steps=[0, 3, 11])
    for r in reqs:
        assert len(r.stream) == r.max_new_tokens  # random net: length-cap
    telemetry.flush()
    events = [json.loads(line)
              for line in open(telemetry.event_path(str(tmp_path), 0))]
    compiles = [e for e in events if e["kind"] == "compile"
                and e.get("executor") == "ServingEngine"]
    sites = sorted(e["site"] for e in compiles)
    assert sites == ["serving_decode", "serving_prefill"], sites


def test_continuous_batching_overlaps_and_frees_pages():
    """Slots and pages recycle mid-flight: 6 requests through 2 slots
    finish in far fewer steps than sequential, and every page returns to
    the pool."""
    net = _tiny_model()
    eng = ServingEngine(TransformerAdapter(net, src_max_len=6), slots=2,
                        page_size=4, max_len=12, stream_every=4)
    rng = np.random.RandomState(1)
    lens = [4, 9, 5, 11, 6, 8]
    reqs = [Request(rng.randint(3, 16, 4), max_new_tokens=n, bos_id=BOS,
                    eos_id=EOS) for n in lens]
    out = eng.serve(reqs, arrival_steps=[0, 0, 2, 5, 7, 9])
    assert all(len(out[r.id]) == n for r, n in zip(reqs, lens))
    assert all(r.stream.finished for r in reqs)
    # 2-wide overlap: strictly fewer decode steps than one-at-a-time
    assert eng.step_count < sum(lens), eng.step_count
    assert eng._cache.pages_free == eng._cache.num_pages - 1
    assert all(m is None for m in eng._slots)


def test_scheduler_queue_bound_backpressure():
    sched = ContinuousBatchingScheduler(bound=2)
    sched.submit(Request([3], 4, BOS, EOS))
    sched.submit(Request([3], 4, BOS, EOS))
    with pytest.raises(MXNetError):
        sched.submit(Request([3], 4, BOS, EOS))
    assert sched.depth == 2
    ready = sched.pop_ready(free_slots=2, pages_free=1, page_size=4)
    assert len(ready) == 1, "one free page admits one request"


def test_pool_exhaustion_raises_with_knob_name():
    net = _tiny_model()
    # 2 usable pages x page_size 4 = 8 rows for TWO requests wanting 12
    eng = ServingEngine(TransformerAdapter(net, src_max_len=6), slots=2,
                        page_size=4, pool_pages=3, max_len=12,
                        stream_every=4)
    reqs = [Request(np.array([5, 6, 7], np.int32), max_new_tokens=12,
                    bos_id=BOS, eos_id=EOS) for _ in range(2)]
    with pytest.raises(MXNetError, match="MX_SERVE_POOL_PAGES"):
        eng.serve(reqs)


def test_pool_pressure_preempts_youngest_and_completes(trained):
    """Under pool pressure the youngest request is preempted back to the
    queue head (recompute preemption) instead of crashing the batch: a
    pool that can only hold ~1.5 requests still serves both, tokens
    identical to an unpressured engine (greedy determinism)."""
    net, src = trained
    roomy = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=2,
                          page_size=1, max_len=6, stream_every=1)
    reqs_a = [Request(src[i], max_new_tokens=6, bos_id=BOS, eos_id=-1)
              for i in range(2)]
    want = roomy.serve(reqs_a)

    tight = ServingEngine(TransformerAdapter(net, src_max_len=7), slots=2,
                          page_size=1, pool_pages=10, max_len=6,
                          stream_every=1)
    reqs_b = [Request(src[i], max_new_tokens=6, bos_id=BOS, eos_id=-1)
              for i in range(2)]
    out = tight.serve(reqs_b)
    for a, b in zip(reqs_a, reqs_b):
        np.testing.assert_array_equal(out[b.id], want[a.id])
        assert b.stream.finished
    assert tight._cache.pages_free == tight._cache.num_pages - 1
    # the pool genuinely couldn't hold both: preemption + recompute
    # means strictly more decode steps than the unpressured run
    assert tight.step_count > roomy.step_count, (tight.step_count,
                                                 roomy.step_count)


def test_fullprefix_rejects_buffer_overflow():
    eng = ServingEngine(FullPrefixAdapter(lambda F, buf: None, max_len=8),
                        slots=1, max_len=8, stream_every=2)
    with pytest.raises(MXNetError, match="buffer"):
        eng.submit(Request(np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=5, bos_id=BOS, eos_id=-1))


def test_max_new_tokens_over_capacity_rejected():
    net = _tiny_model()
    eng = ServingEngine(TransformerAdapter(net, src_max_len=6), slots=1,
                        page_size=4, max_len=8, stream_every=2)
    with pytest.raises(MXNetError, match="max_len"):
        eng.submit(Request(np.array([5], np.int32), max_new_tokens=20,
                           bos_id=BOS, eos_id=EOS))


def test_positional_capacity_fails_loudly():
    """Out-of-table decode positions must never silently clamp: the
    engine rejects max_len beyond the model's positional table at
    construction, and standalone translate rejects it at call time."""
    net = _tiny_model(max_length=16)
    with pytest.raises(MXNetError, match="max_positions"):
        ServingEngine(TransformerAdapter(net, src_max_len=6), slots=1,
                      page_size=4, max_len=32)
    with pytest.raises(MXNetError, match="positional table"):
        net.translate(nd.array(np.array([[5, 6]], np.int32),
                               dtype="int32"),
                      bos_id=BOS, eos_id=EOS, max_len=32, beam_size=1)


def test_fused_decision_in_fingerprint():
    """The fused-attention decision changes the traced program without
    changing shapes — it must split the fingerprint that names the
    decode executable's compile events."""
    net = _tiny_model()
    parts = []
    for fused in (False, True):
        eng = ServingEngine(
            TransformerAdapter(net, src_max_len=6, fused=fused),
            slots=1, page_size=4, max_len=8, stream_every=2)
        parts.append(eng._fingerprint_parts(("decode", 4, 1), []))
    assert parts[0] != parts[1]
    assert memwatch.fingerprint(parts[0]) != memwatch.fingerprint(parts[1])


# ---------------------------------------------------------------------------
# satellites: telemetry, fused kernel, generic adapter
# ---------------------------------------------------------------------------
def test_serve_telemetry_rollup_and_prometheus(tele, tmp_path):
    net = _tiny_model()
    eng = ServingEngine(TransformerAdapter(net, src_max_len=6), slots=2,
                        page_size=4, max_len=10, stream_every=4)
    rng = np.random.RandomState(3)
    reqs = [Request(rng.randint(3, 16, 4), max_new_tokens=6, bos_id=BOS,
                    eos_id=EOS) for _ in range(3)]
    eng.serve(reqs)
    s = telemetry.summary()["serving"]
    assert s["requests"] == 3
    assert s["tokens"] == 18
    assert s["p50_latency_ms"] > 0
    assert s["p99_latency_ms"] >= s["p50_latency_ms"]
    # per-request events reach the flight ring (post-mortem tail)
    tail_kinds = [e["kind"] for e in telemetry.flight_tail(256)]
    assert tail_kinds.count("serve_request") == 3
    prom = open(telemetry.export_prometheus()).read()
    assert 'mx_serve_requests_total{rank="0"} 3' in prom
    assert 'mx_serve_tokens_total{rank="0"} 18' in prom
    assert "mx_serve_latency_p99_ms" in prom
    assert "mx_serve_active_slots" in prom
    telemetry.flush()
    events = [json.loads(line)
              for line in open(telemetry.event_path(str(tmp_path), 0))]
    serve_evs = [e for e in events if e["kind"] == "serve_request"]
    assert len(serve_evs) == 3
    for e in serve_evs:
        assert e["tokens"] == 6 and e["reason"] == "length"
        assert "queue_wait_ms" in e and "prefill_ms" in e \
            and "decode_ms" in e


def test_paged_flash_kernel_matches_dense_softmax():
    """Satellite: the Pallas ragged paged kernel (interpret mode on CPU)
    agrees with the dense softmax reference per slot, including an
    inactive (length 0) slot."""
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas.paged_attention import paged_decode_attention

    rng = np.random.RandomState(0)
    S, H, hd, ps, P = 3, 4, 8, 4, 3
    N = 1 + S * P
    q = jnp.asarray(rng.randn(S, H, hd).astype(np.float32))
    kp = jnp.asarray(rng.randn(N, ps, H, hd).astype(np.float32))
    vp = jnp.asarray(rng.randn(N, ps, H, hd).astype(np.float32))
    table = jnp.asarray(1 + np.arange(S * P, dtype=np.int32).reshape(S, P))
    lengths = jnp.asarray(np.array([5, 12, 0], np.int32))
    out = np.asarray(paged_decode_attention(q, kp, vp, table, lengths))
    for s in range(S):
        L = int(lengths[s])
        if L == 0:
            assert (out[s] == 0).all()
            continue
        K = np.asarray(kp)[np.asarray(table)[s]].reshape(P * ps, H, hd)[:L]
        V = np.asarray(vp)[np.asarray(table)[s]].reshape(P * ps, H, hd)[:L]
        sc = np.einsum("hd,lhd->hl", np.asarray(q[s]), K) / np.sqrt(hd)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        ref = np.einsum("hl,lhd->hd", w, V)
        np.testing.assert_allclose(out[s], ref, rtol=1e-5, atol=1e-5)


def test_paged_step_cache_fused_matches_gather():
    """PagedStepCache(fused=True) — the Pallas kernel path — agrees with
    the bitwise gather path for the same write+attend."""
    from mxnet_tpu.serving import PagedStepCache

    class _Attn:  # the two attrs update_and_attend reads
        _num_heads, _head_dim = 4, 8

    rng = np.random.RandomState(5)
    S, H, hd, ps, P = 3, 4, 8, 4, 2
    C, Lmax = H * hd, ps * P
    table = nd.array(1 + np.arange(S * P, dtype=np.int32).reshape(S, P),
                     dtype="int32")
    pos_np = np.array([2, 5, 0], np.int32)
    pos = nd.array(pos_np, dtype="int32")
    lengths = nd.array(pos_np + 1, dtype="int32")
    keep = nd.array((np.arange(Lmax)[None] < (pos_np + 1)[:, None])
                    .astype(np.float32))
    pages, rows = page_coords(table, pos, ps)
    kp = nd.array(rng.randn(S * P + 1, ps, H, hd).astype(np.float32))
    vp = nd.array(rng.randn(S * P + 1, ps, H, hd).astype(np.float32))
    q = nd.array(rng.randn(S, 1, C).astype(np.float32))
    k_t = nd.array(rng.randn(S, 1, C).astype(np.float32))
    v_t = nd.array(rng.randn(S, 1, C).astype(np.float32))

    def attend(fused):
        cache = PagedStepCache(kp, vp, table, pages, rows, keep,
                               lengths=lengths, fused=fused)
        return cache.update_and_attend(nd, _Attn, q, k_t, v_t).asnumpy()

    np.testing.assert_allclose(attend(True), attend(False),
                               rtol=1e-5, atol=1e-5)


def test_fullprefix_adapter_serves_any_decoder(trained):
    """Satellite: the universal cached-decode fallback (prefill chunked
    into the decode step) serves a plain logits function — the ONNX-
    imported-decoder shape — and matches a host greedy loop over the
    same fixed buffer."""
    from mxnet_tpu import autograd

    net, _ = trained
    L = 10

    def lm_logits(F, buf):
        # decoder-only stand-in: the trained seq2seq's decoder over a
        # fixed source — logits (S, L, V) from the full token buffer
        S = buf.shape[0]
        src = F.ones((S, 3), dtype="int32") * 5
        return net._decode_h(F, buf, *net._encode_h(F, src))

    eng = ServingEngine(FullPrefixAdapter(lm_logits, max_len=L,
                                          pad_id=PAD),
                        slots=2, max_len=L, stream_every=2)
    prompts = [np.array([1, 14, 5], np.int32), np.array([1, 8], np.int32)]
    reqs = [Request(p, max_new_tokens=4, bos_id=BOS, eos_id=-1)
            for p in prompts]
    out = eng.serve(reqs)

    for p, r in zip(prompts, reqs):
        buf = np.full((1, L), PAD, np.int32)
        buf[0, :len(p)] = p
        pos = len(p) - 1
        want = []
        with autograd.pause():
            for _ in range(4):
                logits = lm_logits(nd, nd.array(buf, dtype="int32"))
                lp = logits.log_softmax(axis=-1).asnumpy()[0, pos]
                tok = int(lp.argmax())
                want.append(tok)
                pos += 1
                buf[0, pos] = tok
        assert list(out[r.id]) == want


def test_translate_sync_cadence_invariant(trained):
    """The device-side beam loop's early-exit cadence must not change
    outputs: never syncing mid-loop == syncing every step."""
    net, src = trained
    sb = nd.array(src[:2], dtype="int32")
    a = net.translate(sb, bos_id=BOS, eos_id=EOS, max_len=10, beam_size=3,
                      sync_every=1)
    b = net.translate(sb, bos_id=BOS, eos_id=EOS, max_len=10, beam_size=3,
                      sync_every=0)  # 0 = no mid-loop readback at all
    np.testing.assert_array_equal(a, b)
