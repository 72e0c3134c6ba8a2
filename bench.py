"""Single-process measurements on the platform named on the command line.

    python bench.py --platform tpu                     # ResNet-50 v1b, bf16,
                                                       # fused training step
    BENCH_MODEL=bert python bench.py --platform tpu    # BERT-base MLM step
    BENCH_MODEL=serving_throughput python bench.py --platform cpu

Runs ONE measurement (``BENCH_MODEL``, default ``resnet``; the names are the
keys of ``_MODELS`` below) in this process and prints one JSON line
{"metric", "value", "unit", "vs_baseline", "platform", ...}.  It fails when
``--platform`` is not the backend jax has, starts no child process, and
never falls back to another platform.  Baseline: MXNet-CUDA ResNet-50 fp16
on V100 ~1450 img/s/GPU (BASELINE.md).

ROADMAP A1 replaces this file with the benchmark of cells; ``chip_smoke.py``
is the proof that the system starts on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def _iq_mean(xs):
    """Interquartile mean of chunk times — the estimator the overhead
    and precision secondaries (telemetry_overhead, memwatch_overhead,
    amp_step, quantized_serving) share: this box drifts 2x at sub-second
    scale, and the middle half drops both the daemon-stomped chunks and
    the lucky turbo ones that keep fooling min/median estimators here."""
    xs = sorted(xs)
    lo, hi = len(xs) // 4, max(len(xs) // 4 + 1, 3 * len(xs) // 4)
    mid = xs[lo:hi]
    return sum(mid) / len(mid)


def _timed_steps(run_step, steps, trials=3):
    """Warmup (compile) + best-of-`trials` timing of `steps` iterations.

    run_step() must RETURN the step's loss; the loss is materialized on
    the host after each trial — each step's loss depends on the previous
    step's params, so the one host read waits for every dispatched step.
    A stacked superstep loss forces the same way (its full vector lands;
    the last element is read).  Returns best seconds per trial."""
    import numpy as np

    loss = run_step()
    float(np.asarray(loss).ravel()[-1])
    best_dt = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = run_step()
        float(np.asarray(loss).ravel()[-1])
        best_dt = min(best_dt, time.perf_counter() - t0)
    return best_dt


def _common_setup(platform):
    import jax

    import mxnet_tpu as mx

    have = jax.default_backend()
    if have != platform:
        raise SystemExit(f"bench.py: --platform {platform} named, but jax's "
                         f"backend is {have}")
    on_tpu = platform == "tpu"
    mx.random.seed(0)
    ctx = mx.tpu() if on_tpu else mx.cpu()
    mx.context.Context._default_ctx.value = ctx
    return mx, ctx, on_tpu


def bench_bert(platform):
    """Secondary metric (BASELINE): BERT-base MLM pretrain tokens/sec/chip,
    bf16 fused step.  Baseline: GluonNLP fp16 on V100 ~3000 tok/s/GPU."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.models import bert_base
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    batch = int(os.environ.get("BENCH_BATCH", 32 if on_tpu else 2))
    seqlen = int(os.environ.get("BENCH_SEQLEN", 512 if on_tpu else 64))
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 2))

    net = bert_base()
    net.initialize(mx.init.Normal(0.02))
    if on_tpu:
        net.cast("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(logits, labels):
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1))

    step = DataParallelStep(
        net, mlm_loss, mesh=local_mesh(devices=[ctx.jax_device]),
        optimizer="adam", optimizer_params={"learning_rate": 1e-4})
    V = 30522
    tokens = np.random.randint(0, V, (batch, seqlen)).astype(np.int32)
    labels = tokens.astype(np.float32)
    tb = nd.array(tokens, ctx=ctx, dtype="int32")
    lb = nd.array(labels, ctx=ctx)
    best_dt = _timed_steps(lambda: step.step(tb, lb), steps)
    tok_per_sec = batch * seqlen * steps / best_dt
    baseline = 3000.0  # GluonNLP BERT-base fp16 V100 (BASELINE.md)
    print(json.dumps({
        "metric": "bert_base_mlm_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": round(tok_per_sec / baseline, 4),
        "platform": platform,
        "batch": batch, "seqlen": seqlen,
        "telemetry": mx.telemetry.summary(),
    }))


def bench_transformer(platform):
    """Config-4 measurement: Transformer (base by default, BENCH_SIZE=big)
    seq2seq training tokens/sec/chip, label-smoothed CE, fused multi-input
    step.  No published per-GPU reference number survives for the exact
    recipe (BASELINE.json.published is empty), so vs_baseline is reported
    as 0.0 and the raw number is the record."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import nd
    from mxnet_tpu.models.transformer import (label_smoothed_ce,
                                              transformer_base,
                                              transformer_big)
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    batch = int(os.environ.get("BENCH_BATCH", 16 if on_tpu else 2))
    seqlen = int(os.environ.get("BENCH_SEQLEN", 128 if on_tpu else 16))
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 2))
    vocab = int(os.environ.get("BENCH_VOCAB", 32000 if on_tpu else 128))
    big = os.environ.get("BENCH_SIZE", "base") == "big"

    net = (transformer_big if big else transformer_base)(vocab)
    net.initialize(mx.init.Xavier())
    if on_tpu:
        net.cast("bfloat16")
    step = DataParallelStep(
        net, lambda lo, la: label_smoothed_ce(lo, la, smoothing=0.1),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="adam",
        optimizer_params={"learning_rate": 1e-4})
    rng = np.random.RandomState(0)
    src = rng.randint(3, vocab, (batch, seqlen)).astype(np.int32)
    tgt_in = np.concatenate(
        [np.ones((batch, 1), np.int32), src[:, ::-1]], axis=1)
    tgt_out = np.concatenate(
        [src[:, ::-1], np.full((batch, 1), 2, np.int32)], axis=1)
    sb = nd.array(src, ctx=ctx, dtype="int32")
    tb = nd.array(tgt_in, ctx=ctx, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32), ctx=ctx)
    best_dt = _timed_steps(lambda: step.step((sb, tb), lb), steps)
    tok_per_sec = batch * (seqlen + 1) * steps / best_dt
    print(json.dumps({
        "metric": f"transformer_{'big' if big else 'base'}_train_tokens"
                  "_per_sec_per_chip",
        "value": round(tok_per_sec, 2),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "batch": batch, "seqlen": seqlen,
        "telemetry": mx.telemetry.summary(),
    }))


def bench_resnet(platform):
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1b
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    # bs256 is the reference recipe (docs/faq/perf.md) and the r3-verdict
    # lever #1; fits v5e HBM in bf16 with donation.
    batch = int(os.environ.get("BENCH_BATCH", 256 if on_tpu else 8))
    res = int(os.environ.get("BENCH_RES", 224 if on_tpu else 64))
    steps = int(os.environ.get("BENCH_STEPS", 20 if on_tpu else 3))
    layout = os.environ.get("BENCH_LAYOUT", "NHWC" if on_tpu else "NCHW")

    net = resnet50_v1b(layout=layout)
    net.initialize(mx.init.Xavier())
    net.cast("bfloat16" if on_tpu else "float32")

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    step = DataParallelStep(
        net, loss_fn, mesh=local_mesh(devices=[ctx.jax_device]),
        optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4},
        # BENCH_REMAT=1: activation rematerialization — HBM headroom for
        # the bs512 ablation (is bs256 underutilizing the chip?)
        remat=os.environ.get("BENCH_REMAT", "0") == "1")

    shape = (batch, 3, res, res) if layout == "NCHW" else (batch, res, res, 3)
    x = np.random.rand(*shape).astype("float32")
    y = np.random.randint(0, 1000, batch).astype("float32")
    if on_tpu:
        import ml_dtypes

        x = x.astype(ml_dtypes.bfloat16)
    xb, yb = nd.array(x, ctx=ctx, dtype=x.dtype), nd.array(y, ctx=ctx)

    scan_mode = os.environ.get("BENCH_SCAN", "0") == "1"
    if scan_mode:
        # All `steps` iterations inside ONE compiled program (lax.scan):
        # a single dispatch per trial.  The delta vs the per-step-dispatch
        # measurement below IS the host dispatch overhead.  Routed through
        # the SHIPPED superstep mode
        # (DataParallelStep.superstep, docs/PERFORMANCE.md §Superstep) so
        # the bench exercises the production code path, not a hand-rolled
        # scan body; the explicit API bypasses the CPU-mesh gate, which
        # is the point of the ablation.
        def run_scan():
            return step.superstep([(xb, yb)] * steps)

        best_dt = _timed_steps(run_scan, 1)
    else:
        best_dt = _timed_steps(lambda: step.step(xb, yb), steps)
    img_per_sec = batch * steps / best_dt
    baseline = 1450.0  # MXNet-CUDA V100 fp16 (BASELINE.md)
    rec = {
        "metric": "resnet50_v1b_train_images_per_sec_per_chip",
        "value": round(img_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": round(img_per_sec / baseline, 4),
        "platform": platform,
        "batch": batch, "layout": layout,
    }
    if scan_mode:
        rec["mode"] = "scan"
        rec["scan_steps"] = steps
    if os.environ.get("BENCH_REMAT", "0") == "1":
        rec["remat"] = True
    # per-step telemetry rollup (compile vs exec split, retrace counts,
    # transfer bytes) rides along with the headline number — the feature
    # vector a learned cost model trains on
    rec["telemetry"] = mx.telemetry.summary()
    print(json.dumps(rec))


def bench_trainer_overhead(platform):
    """Secondary metric: Trainer.step() dispatch overhead — steps/sec on a
    fixed 50-param toy net with the fused optimizer apply on vs off
    (MX_FUSED_UPDATE).  Gradients are computed once and held fixed; the
    loop times ONLY the step path (allreduce + update dispatch), which is
    exactly where the per-param O(n_params) storm lived."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)

    n_layers = 25  # Dense weight+bias each -> 50 params
    steps = int(os.environ.get("BENCH_OVERHEAD_STEPS", 100))
    trials = int(os.environ.get("BENCH_OVERHEAD_TRIALS", 5))

    def steps_per_sec(fused):
        import jax

        from mxnet_tpu import autograd, gluon, nd
        from mxnet_tpu.gluon import nn

        os.environ["MX_FUSED_UPDATE"] = "1" if fused else "0"
        mx.random.seed(0)
        net = nn.HybridSequential()
        with net.name_scope():
            for _ in range(n_layers):
                net.add(nn.Dense(4))
        net.initialize(mx.init.Xavier(), ctx=ctx)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 1e-3, "momentum": 0.9})
        x = nd.array(np.random.RandomState(0).randn(2, 4).astype(np.float32),
                     ctx=ctx)
        with autograd.record():
            loss = (net(x) ** 2).sum()
        loss.backward()
        params = list(net.collect_params().values())
        for _ in range(3):  # warmup: kvstore/state init + update compiles
            trainer.step(2)
        jax.block_until_ready([p.data()._data for p in params])
        # best-of-`trials` (as _timed_steps): a 2-vCPU box's scheduling
        # noise swings single-trial dispatch timings several-x; the best
        # trial is the uncontended dispatch cost the metric is after
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            for _ in range(steps):
                trainer.step(2)
            jax.block_until_ready([p.data()._data for p in params])
            best = min(best, time.perf_counter() - t0)
        return steps / best

    per_param = steps_per_sec(False)
    fused = steps_per_sec(True)
    print(json.dumps({
        "metric": "trainer_step_overhead",
        "value": round(fused / per_param, 3) if per_param else 0.0,
        "unit": "x_fused_vs_per_param",
        "vs_baseline": 0.0,
        "platform": platform,
        "fused_steps_per_sec": round(fused, 2),
        "per_param_steps_per_sec": round(per_param, 2),
        "n_params": 2 * n_layers,
        "steps": steps,
    }))


def bench_pipeline_overlap(platform):
    """Secondary metric: the async step pipeline win — steps/sec with
    MX_ASYNC_INFLIGHT=2 + DevicePrefetchIter (non-blocking dispatch,
    background device staging, deferred loss readback) vs
    MX_ASYNC_INFLIGHT=0 (every step forced at dispatch, today's old
    behavior), best-of-N trials, on a transfer/prep-heavy toy model where
    host-side batch prep + H2D is comparable to device compute — the
    regime the pipeline exists for.  Values well above 1 are the point
    (docs/PERFORMANCE.md §Async pipeline).  The telemetry block_wait
    rollup per mode rides along as the host-blocking evidence."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    B = int(os.environ.get("BENCH_PIPELINE_BATCH", 256))
    D = int(os.environ.get("BENCH_PIPELINE_DIM", 8192))
    steps = int(os.environ.get("BENCH_PIPELINE_STEPS", 24))
    trials = int(os.environ.get("BENCH_PIPELINE_TRIALS", 3))

    base = np.random.RandomState(0).rand(steps * B, D).astype(np.float32)
    ys = np.random.RandomState(1).randint(0, 10, steps * B).astype(np.float32)

    class AugIter(mx.io.DataIter):
        """Per-batch host 'augmentation' (normalize + nonlinearity):
        genuine numpy work the pipeline can overlap with device compute."""

        def __init__(self):
            super().__init__(batch_size=B)
            self.i = 0

        def reset(self):
            self.i = 0

        def next(self):
            from mxnet_tpu import nd

            if self.i >= steps:
                raise StopIteration
            x = base[self.i * B:(self.i + 1) * B]
            x = np.tanh((x - x.mean(axis=1, keepdims=True))
                        / (x.std(axis=1, keepdims=True) + 1e-6))
            x = (x + np.tanh(1.5 * x - 0.25)).astype(np.float32)
            lab = ys[self.i * B:(self.i + 1) * B]
            self.i += 1
            return mx.io.DataBatch([nd.array(x)], [nd.array(lab)])

    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(64, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-3})

    import tempfile

    tele_dir = tempfile.mkdtemp(prefix="bench_pipeline_tele_")

    def run_mode(inflight, prefetch):
        os.environ["MX_ASYNC_INFLIGHT"] = str(inflight)
        telemetry.reset()
        telemetry.enable(tele_dir)  # block_wait only aggregates when on
        best = float("inf")
        for _ in range(1 + trials):  # first pass warms the compile cache
            it = AugIter()
            it = mx.io.DevicePrefetchIter(it, step) if prefetch else it
            t0 = time.perf_counter()
            loss = None
            for b in it:
                loss = step.step(b.data[0], b.label[0])
                if inflight == 0:
                    float(loss)  # the old per-step host round-trip
            step.drain()
            float(loss)
            best = min(best, time.perf_counter() - t0)
        blocked = sum(row.get("block_wait_ms", 0.0)
                      for row in telemetry.summary()["steps"].values())
        return steps / best, round(blocked, 1)

    sync_sps, sync_block = run_mode(0, prefetch=False)
    async_sps, async_block = run_mode(2, prefetch=True)
    print(json.dumps({
        "metric": "pipeline_overlap",
        "value": round(async_sps / sync_sps, 3) if sync_sps else 0.0,
        "unit": "x_async_vs_sync",
        "vs_baseline": 0.0,
        "platform": platform,
        "async_steps_per_sec": round(async_sps, 2),
        "sync_steps_per_sec": round(sync_sps, 2),
        "sync_block_wait_ms": sync_block,
        "async_block_wait_ms": async_block,
        "batch": B, "dim": D, "steps": steps,
    }))


def bench_serving_throughput(platform):
    """Secondary metric: the continuous-batching win — tokens/sec through
    the serving engine (S slots, paged KV cache, ONE compiled decode
    step shared by ragged in-flight requests) vs sequential per-request
    decode: one ``translate(beam_size=1)`` call per request, the status
    quo this subsystem replaces (ISSUE/ROADMAP item 1).  The slots=1
    engine rides along as ``engine_slots1_tokens_per_sec``, isolating
    the pure batching share of the win from the compiled-single-step
    share.  Mixed-length synthetic request trace with mid-flight
    arrivals; interleaved trials compared by interquartile mean (this
    box drifts 2x at sub-second scale — the telemetry_overhead
    estimator).  Values well above 1 are the point (docs/SERVING.md)."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu.models.transformer import Transformer
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    slots = int(os.environ.get("BENCH_SERVING_SLOTS", 8))
    n_req = int(os.environ.get("BENCH_SERVING_REQUESTS", 16))
    trials = int(os.environ.get("BENCH_SERVING_TRIALS", 4))
    max_len = 40

    mx.random.seed(0)
    net = Transformer(64, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=64, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)

    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 64, 8).astype(np.int32) for _ in range(n_req)]
    # mixed decode lengths (7..33) — the ragged trace continuous
    # batching exists for (eos_id=1: never emitted, length-capped)
    lens = (7 + (np.arange(n_req) * 11) % 27).astype(int)
    arrivals = [0 if i < slots else int(i) for i in range(n_req)]

    def build(n_slots):
        eng = ServingEngine(TransformerAdapter(net, src_max_len=8),
                            slots=n_slots, page_size=8, max_len=max_len,
                            stream_every=4, ctx=ctx)
        # warm the compiled decode + prefill before timing
        eng.serve([Request(prompts[0], 4, bos_id=2, eos_id=1)])
        return eng

    def run_trial(eng, batched):
        reqs = [Request(prompts[i], int(lens[i]), bos_id=2, eos_id=1)
                for i in range(n_req)]
        t0 = time.perf_counter()
        eng.serve(reqs, arrival_steps=arrivals if batched else None)
        wall = time.perf_counter() - t0
        toks = sum(len(r.stream) for r in reqs)
        return toks / wall

    from mxnet_tpu import nd

    src_nds = [nd.array(p.reshape(1, -1), dtype="int32") for p in prompts]

    def run_translate_trial():
        # the status quo: one standalone greedy translate per request
        t0 = time.perf_counter()
        toks = 0
        for i in range(n_req):
            out = net.translate(src_nds[i], bos_id=2, eos_id=1,
                                max_len=int(lens[i]) + 1, beam_size=1)
            toks += out.shape[1] - 1
        return toks / (time.perf_counter() - t0)

    def iq_mean(vals):
        vals = sorted(vals)
        k = max(1, len(vals) // 4)
        core = vals[k:-k] if len(vals) > 2 * k else vals
        return sum(core) / len(core)

    eng_b = build(slots)
    eng_s = build(1)
    run_translate_trial()  # warm translate's eager op cache
    cont, seq, s1 = [], [], []
    for _ in range(trials):  # interleave: box drift hits all modes alike
        cont.append(run_trial(eng_b, batched=True))
        seq.append(run_translate_trial())
        s1.append(run_trial(eng_s, batched=False))
    cont_tps, seq_tps = iq_mean(cont), iq_mean(seq)
    print(json.dumps({
        "metric": "serving_throughput",
        "value": round(cont_tps / seq_tps, 3) if seq_tps else 0.0,
        "unit": "x_continuous_vs_sequential",
        "vs_baseline": 0.0,
        "platform": platform,
        "continuous_tokens_per_sec": round(cont_tps, 2),
        "sequential_tokens_per_sec": round(seq_tps, 2),
        "engine_slots1_tokens_per_sec": round(iq_mean(s1), 2),
        "slots": slots, "requests": n_req,
        "decode_lengths": [int(x) for x in lens],
        "trials": trials,
    }))


def bench_router_throughput(platform):
    """Secondary metric: the serving front door's mixed-traffic win —
    tokens/sec AND p99 TTFT through a multi-replica Router (HTTP, session
    affinity, least-outstanding dispatch) vs ONE engine serving the same
    request trace, at EQUAL OUTPUTS (greedy decode: both runs emit
    token-for-token identical streams, asserted in the record).  The
    router splits queue wait across replicas, so the p99 TTFT drop is
    the headline; the tokens/sec ratio rides along (bounded by how much
    the host overlaps two engines' compiled steps — docs/SERVING.md
    §Front door)."""
    import tempfile
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu.models.transformer import Transformer
    from mxnet_tpu.serving import (ReplicaServer, Request, Router,
                                   ServingEngine, TransformerAdapter)

    n_req = int(os.environ.get("BENCH_ROUTER_REQUESTS", 24))
    n_rep = int(os.environ.get("BENCH_ROUTER_REPLICAS", 2))
    slots = int(os.environ.get("BENCH_ROUTER_SLOTS", 4))
    clients = int(os.environ.get("BENCH_ROUTER_CLIENTS", 8))

    mx.random.seed(0)
    net = Transformer(64, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=64, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 64, 8).tolist() for _ in range(n_req)]
    lens = (7 + (np.arange(n_req) * 11) % 21).astype(int)

    def mk_engine():
        eng = ServingEngine(TransformerAdapter(net, src_max_len=8),
                            slots=slots, page_size=8, max_len=40,
                            stream_every=4, ctx=ctx)
        eng.serve([Request(prompts[0], 4, bos_id=2, eos_id=1)])  # warm
        return eng

    def post(port, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300.0) as r:
            return json.load(r)

    def drive(port):
        bodies = [{"prompt": prompts[i], "max_new_tokens": int(lens[i]),
                   "bos_id": 2, "eos_id": 1, "timeout_s": 300.0}
                  for i in range(n_req)]
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=clients) as ex:
            outs = list(ex.map(lambda b: post(port, b), bodies))
        wall = time.perf_counter() - t0
        toks = sum(len(o["tokens"]) for o in outs)
        ttfts = sorted(o["ttft_ms"] for o in outs)
        p99 = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
        return outs, toks / wall, p99

    # baseline: the SAME trace through one engine behind one replica
    base = ReplicaServer(mk_engine(), bos_id=2, eos_id=1, rank=0,
                         port=0, directory=tempfile.mkdtemp()).start()
    outs_base, tps_base, p99_base = drive(base.port)
    base.stop()

    tmp = tempfile.mkdtemp()
    reps = [ReplicaServer(mk_engine(), bos_id=2, eos_id=1, rank=i,
                          port=0, directory=tmp).start()
            for i in range(n_rep)]
    router = Router(tmp, port=0, health_sec=60.0).start()
    outs_r, tps_router, p99_router = drive(router.port)
    routed_to = sorted({o["routed_to"] for o in outs_r})
    router.stop()
    for r in reps:
        r.stop()

    equal = all(a["tokens"] == b["tokens"]
                for a, b in zip(outs_base, outs_r))
    print(json.dumps({
        "metric": "router_throughput",
        "value": round(tps_router / tps_base, 3) if tps_base else 0.0,
        "unit": "x_router_vs_single_engine",
        "vs_baseline": 0.0,
        "platform": platform,
        "router_tokens_per_sec": round(tps_router, 2),
        "single_tokens_per_sec": round(tps_base, 2),
        "router_p99_ttft_ms": round(p99_router, 2),
        "single_p99_ttft_ms": round(p99_base, 2),
        "p99_ttft_ratio": round(p99_router / p99_base, 3)
        if p99_base else 0.0,
        "equal_outputs": bool(equal),
        "replicas_used": routed_to,
        "replicas": n_rep, "slots_each": slots,
        "requests": n_req, "clients": clients,
    }))


def bench_rqtrace_overhead(platform):
    """Secondary metric: router tokens/sec with fleet-wide request
    tracing ON (``MX_RQTRACE=1``, ``MX_RQTRACE_SAMPLE=1.0`` — every
    request minted, propagated, span-wrapped at router AND replica)
    vs ``MX_RQTRACE=0``, telemetry enabled in BOTH modes so the delta
    isolates the tracing layer: header mint/parse, /tracez bookkeeping,
    the serve_route/serve_dispatch/serve_handle spans and the engine's
    per-request span gating (docs/OBSERVABILITY.md §Request tracing).
    Acceptance bar is <2% overhead (value >= 0.98) — same interleaved
    interquartile-mean estimator as telemetry_overhead (this box drifts
    2x at sub-second scale)."""
    import tempfile
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.transformer import Transformer
    from mxnet_tpu.serving import (ReplicaServer, Request, Router,
                                   ServingEngine, TransformerAdapter)

    n_req = int(os.environ.get("BENCH_RQTRACE_REQUESTS", 16))
    clients = int(os.environ.get("BENCH_RQTRACE_CLIENTS", 4))
    trials = int(os.environ.get("BENCH_RQTRACE_TRIALS", 12))

    mx.random.seed(0)
    net = Transformer(64, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=64, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 64, 8).tolist() for _ in range(n_req)]

    tmp = tempfile.mkdtemp(prefix="bench_rqtrace_")
    telemetry.enable(tmp)
    eng = ServingEngine(TransformerAdapter(net, src_max_len=8),
                        slots=4, page_size=8, max_len=40,
                        stream_every=4, ctx=ctx)
    eng.serve([Request(prompts[0], 4, bos_id=2, eos_id=1)])  # warm
    rep = ReplicaServer(eng, bos_id=2, eos_id=1, rank=0, port=0,
                        directory=tmp).start()
    router = Router(tmp, port=0, health_sec=60.0).start()

    def post(body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{router.port}/generate",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=300.0) as r:
            return json.load(r)

    bodies = [{"prompt": prompts[i], "max_new_tokens": 12,
               "bos_id": 2, "eos_id": 1, "timeout_s": 300.0}
              for i in range(n_req)]

    def one_trial(traced):
        os.environ["MX_RQTRACE"] = "1" if traced else "0"
        os.environ["MX_RQTRACE_SAMPLE"] = "1.0"
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=clients) as ex:
            outs = list(ex.map(post, bodies))
        wall = time.perf_counter() - t0
        toks = sum(len(o["tokens"]) for o in outs)
        return wall, toks, outs

    one_trial(False)
    _, _, outs_warm = one_trial(True)  # warm both paths
    traced_ok = all("trace_id" in o for o in outs_warm)
    offs, ons, toks = [], [], 0
    for _ in range(trials):
        w_off, t_off, _ = one_trial(False)
        offs.append(w_off)
        w_on, t_on, _ = one_trial(True)
        ons.append(w_on)
        assert t_on == t_off, "tracing must not perturb decode"
        toks = t_on
    os.environ.pop("MX_RQTRACE", None)
    os.environ.pop("MX_RQTRACE_SAMPLE", None)
    router.stop()
    rep.stop()

    iq_off, iq_on = _iq_mean(offs), _iq_mean(ons)
    print(json.dumps({
        "metric": "rqtrace_overhead",
        "value": round(iq_off / iq_on, 4),
        "unit": "x_on_vs_off",
        "vs_baseline": 0.0,
        "platform": platform,
        "on_tokens_per_sec": round(toks / iq_on, 2),
        "off_tokens_per_sec": round(toks / iq_off, 2),
        "all_traced": bool(traced_ok),
        "requests": n_req, "clients": clients, "trials": trials,
    }))


def bench_prefix_cache(platform):
    """Secondary metric: the copy-on-write prefix cache — wall clock and
    mean TTFT for N requests sharing one forced decoder prefix, cache ON
    (first request teacher-forces/ingests once, the rest FORK its pages)
    vs OFF (every request re-ingests).  Outputs are asserted bitwise
    equal between the runs — the cache trades nothing for the win
    (docs/SERVING.md §Prefix cache)."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu.models.transformer import Transformer
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    n_req = int(os.environ.get("BENCH_PREFIX_REQUESTS", 12))
    plen = int(os.environ.get("BENCH_PREFIX_TOKENS", 24))
    trials = int(os.environ.get("BENCH_PREFIX_TRIALS", 3))

    mx.random.seed(0)
    net = Transformer(64, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=64, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(0)
    src = rng.randint(3, 64, 8).astype(np.int32)
    prefix = rng.randint(3, 64, plen).astype(np.int32)

    def run(cache_on):
        eng = ServingEngine(TransformerAdapter(net, src_max_len=8),
                            slots=4, page_size=8, max_len=plen + 12,
                            stream_every=4, ctx=ctx,
                            prefix_cache=cache_on)
        # warm every executable (prefill, decode, ingest) off the clock
        eng.serve([Request(src, 2, bos_id=2, eos_id=1,
                           prefix=prefix[:5])])
        walls = []
        streams = None
        hit_rate = 0.0
        for _ in range(trials):
            reqs = [Request(src, 8, bos_id=2, eos_id=1, prefix=prefix)
                    for _ in range(n_req)]
            t0 = time.perf_counter()
            eng.serve(reqs)
            walls.append(time.perf_counter() - t0)
            streams = [list(r.stream) for r in reqs]
        if eng._prefix is not None:
            looked = eng._prefix.hits + eng._prefix.misses
            hit_rate = eng._prefix.hits / looked if looked else 0.0
        return min(walls), streams, hit_rate

    wall_on, streams_on, hit_rate = run(True)
    wall_off, streams_off, _ = run(False)
    print(json.dumps({
        "metric": "prefix_cache",
        "value": round(wall_off / wall_on, 3) if wall_on else 0.0,
        "unit": "x_cached_vs_cold",
        "vs_baseline": 0.0,
        "platform": platform,
        "wall_cached_s": round(wall_on, 4),
        "wall_cold_s": round(wall_off, 4),
        "prefix_hit_rate": round(hit_rate, 4),
        "bitwise_equal": bool(streams_on == streams_off),
        "prefix_tokens": plen, "requests": n_req, "trials": trials,
    }))


def bench_spec_decode(platform):
    """Secondary metric: speculative decoding — tokens/sec with the
    n-gram prompt-lookup draft + ONE ("verify", K) ragged dispatch per
    boundary vs the plain engine, on copy-heavy traffic (repetitive
    continuations — the regime prompt-lookup drafting exists for).
    Greedy output is asserted BITWISE identical; the acceptance rate
    rides in the record (it bounds the achievable speedup: each accepted
    token is a decode dispatch never issued — docs/SERVING.md
    §Speculative decoding)."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu.models.transformer import Transformer
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    n_req = int(os.environ.get("BENCH_SPEC_REQUESTS", 8))
    spec_k = int(os.environ.get("BENCH_SPEC_K", 4))
    max_new = int(os.environ.get("BENCH_SPEC_TOKENS", 24))
    trials = int(os.environ.get("BENCH_SPEC_TRIALS", 3))

    mx.random.seed(0)
    net = Transformer(64, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=64, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, 64, 8).astype(np.int32)
               for _ in range(n_req)]

    def run(k):
        eng = ServingEngine(TransformerAdapter(net, src_max_len=8),
                            slots=4, page_size=8, max_len=40,
                            stream_every=4, ctx=ctx, spec_k=k)
        eng.serve([Request(prompts[0], 4, bos_id=2, eos_id=1)])  # warm
        best = 0.0
        streams = None
        for _ in range(trials):
            reqs = [Request(p, max_new, bos_id=2, eos_id=1)
                    for p in prompts]
            t0 = time.perf_counter()
            eng.serve(reqs)
            wall = time.perf_counter() - t0
            best = max(best, sum(len(r.stream) for r in reqs) / wall)
            streams = [list(r.stream) for r in reqs]
        rate = (eng._spec_accepted / eng._spec_proposed
                if eng._spec_proposed else 0.0)
        return best, streams, rate

    tps_plain, streams_plain, _ = run(0)
    tps_spec, streams_spec, accept_rate = run(spec_k)
    print(json.dumps({
        "metric": "spec_decode",
        "value": round(tps_spec / tps_plain, 3) if tps_plain else 0.0,
        "unit": "x_speculative_vs_plain",
        "vs_baseline": 0.0,
        "platform": platform,
        "speculative_tokens_per_sec": round(tps_spec, 2),
        "plain_tokens_per_sec": round(tps_plain, 2),
        "acceptance_rate": round(accept_rate, 4),
        "greedy_bitwise": bool(streams_plain == streams_spec),
        "spec_k": spec_k, "requests": n_req,
        "max_new_tokens": max_new, "trials": trials,
    }))


def bench_plan_choice(platform):
    """Secondary metric: the auto-sharding planner's chosen plan vs the
    WORST legal plan of the same 2-device mesh, measured steps/sec
    through compile_step_with_plan on a toy Dense net with a
    tp-shardable weight (the signature has no sequence dim, so the
    legal candidates are dp2 and tp2 — and the ranking between them is
    non-trivial: see below).  Interleaved chunks compared by
    interquartile mean — the telemetry_overhead estimator; this box
    drifts 2x at sub-second scale.  The sanity floor is value >= 1.0
    (the chosen plan at least matches the worst candidate); the
    planner's full predicted ranking lands in the record so later eras
    can train on predicted-vs-measured (docs/PERFORMANCE.md §Plan &
    planner)."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    import jax

    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import compile_step_with_plan, local_mesh
    from mxnet_tpu.parallel import planner
    from mxnet_tpu.parallel.sharding import ShardingRules

    B = int(os.environ.get("BENCH_PLAN_BATCH", 128))
    D = int(os.environ.get("BENCH_PLAN_DIM", 2048))
    H = int(os.environ.get("BENCH_PLAN_HIDDEN", 1024))
    steps = int(os.environ.get("BENCH_PLAN_STEPS", 8))
    trials = int(os.environ.get("BENCH_PLAN_TRIALS", 16))

    devices = jax.devices()[:2]
    if len(devices) < 2:
        print(json.dumps({"metric": "plan_choice", "value": 0.0,
                          "error": "needs 2 devices"}))
        return

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(B, D).astype(np.float32))
    y = nd.array(rng.randint(0, 10, B).astype(np.float32))

    rules = ShardingRules([(r".*dense0_weight", (None, "tp")),
                           (r".*dense1_weight", ("tp", None))])

    def build(plan):
        mx.random.seed(0)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(H, activation="relu", in_units=D),
                    nn.Dense(10, in_units=H))
        net.initialize(mx.init.Xavier(), ctx=ctx)
        mesh = plan.build_mesh(devices)
        return compile_step_with_plan(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), plan, mesh=mesh,
            optimizer="sgd", optimizer_params={"learning_rate": 1e-3})

    # hand-derived signature (a Dense feature dim is NOT a sequence —
    # batch_shape is the batch dim only): grads (P ~ D*H*4 bytes) far
    # outweigh activations (B*(H+10)*4), so the analytic model ranks tp
    # (small activation collectives) ABOVE dp (full param-grad
    # allreduce) — the non-obvious layout, and measurably the faster
    # one on this box
    sig = planner.ModelSignature(
        param_shapes={"dense0_weight": (D, H), "dense0_bias": (H,),
                      "dense1_weight": (H, 10), "dense1_bias": (10,)},
        batch_shape=(B,), rules=rules,
        flops_per_step=6.0 * B * (D * H + H * 10),
        act_bytes=4.0 * B * (H + 10))
    ranked = planner.enumerate_plans(sig, 2)
    chosen_c, worst_c = ranked[0], ranked[-1]
    steps_chosen = build(chosen_c.plan)
    steps_worst = build(worst_c.plan)

    def one_chunk(step):
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step.step(x, y)
        step.drain()
        float(loss)
        return time.perf_counter() - t0

    one_chunk(steps_chosen)   # compile warmup
    one_chunk(steps_worst)
    chosen_ts, worst_ts = [], []
    for _ in range(trials):
        chosen_ts.append(one_chunk(steps_chosen))
        worst_ts.append(one_chunk(steps_worst))
    chosen_sps = steps / _iq_mean(chosen_ts)
    worst_sps = steps / _iq_mean(worst_ts)
    print(json.dumps({
        "metric": "plan_choice",
        "value": round(chosen_sps / worst_sps, 3) if worst_sps else 0.0,
        "unit": "x_chosen_vs_worst_legal_steps_per_sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "chosen_strategy": chosen_c.plan.strategy,
        "worst_strategy": worst_c.plan.strategy,
        "chosen_steps_per_sec": round(chosen_sps, 2),
        "worst_steps_per_sec": round(worst_sps, 2),
        "predicted_ranking": [
            {"strategy": c.plan.strategy,
             "mesh": {n: s for n, s in c.plan.mesh_axes if s > 1},
             "predicted_step_s": round(float(c.step_s), 9)}
            for c in ranked],
        "batch": B, "dim": D, "hidden": H, "steps": steps,
        "trials": trials,
    }))


def bench_telemetry_overhead(platform):
    """Secondary metric: steady-state steps/sec with the telemetry
    recorder + span tracing enabled (MX_TELEMETRY_DIR set, spans on — the
    full ~8-events-per-step observability load) vs the recorder fully off,
    best-of-N trials on a toy DataParallelStep net.  The acceptance bar
    is < 2% overhead (value >= 0.98): tracing that perturbs the hot path
    would get turned off in production, defeating its purpose.  The
    per-mode span rollup rides along as evidence the spans actually
    recorded."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    B = int(os.environ.get("BENCH_TELEMETRY_BATCH", 256))
    D = int(os.environ.get("BENCH_TELEMETRY_DIM", 8192))
    steps = int(os.environ.get("BENCH_TELEMETRY_STEPS", 8))
    trials = int(os.environ.get("BENCH_TELEMETRY_TRIALS", 24))

    rng = np.random.RandomState(0)
    from mxnet_tpu import nd

    x = nd.array(rng.rand(B, D).astype(np.float32))
    y = nd.array(rng.randint(0, 10, B).astype(np.float32))

    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-3})

    import tempfile

    tele_dir = tempfile.mkdtemp(prefix="bench_telemetry_")

    def one_trial(enabled):
        telemetry.reset()
        if enabled:
            telemetry.enable(tele_dir)
        t0 = time.perf_counter()
        loss = None
        for _i in range(steps):
            loss = step.step(x, y)
        step.drain()
        float(loss)
        dt = time.perf_counter() - t0
        n_spans = (sum(v["count"]
                       for v in telemetry.summary()["spans"].values())
                   if enabled else 0)
        telemetry.reset()  # leave the recorder detached between trials
        return dt, n_spans

    # This 2-vCPU box drifts by 2x at sub-second scale (thermal/
    # contention + XLA thread scheduling), far above the span layer's
    # real cost — end-to-end trial means measure the machine, not the
    # telemetry.  Instead: many short INTERLEAVED chunks per mode (both
    # modes sample the same machine regimes) compared by INTERQUARTILE
    # MEAN of chunk times — the middle half drops both the
    # daemon-stomped chunks and the lucky turbo ones that keep fooling
    # min/median estimators here.
    one_trial(False)
    one_trial(True)  # warm the compile cache + flusher thread
    offs, ons, n_spans = [], [], 0
    for _ in range(trials):
        dt_off, _ = one_trial(False)
        offs.append(dt_off)
        dt_on, spans = one_trial(True)
        ons.append(dt_on)
        n_spans = max(n_spans, spans)

    iq_off, iq_on = _iq_mean(offs), _iq_mean(ons)
    off_sps = steps / iq_off
    on_sps = steps / iq_on
    print(json.dumps({
        "metric": "telemetry_overhead",
        "value": round(iq_off / iq_on, 4),
        "unit": "x_on_vs_off",
        "vs_baseline": 0.0,
        "platform": platform,
        "on_steps_per_sec": round(on_sps, 2),
        "off_steps_per_sec": round(off_sps, 2),
        "spans_recorded": n_spans,
        "batch": B, "dim": D, "steps": steps,
    }))


def bench_memwatch_overhead(platform):
    """Secondary metric: steady-state steps/sec with the memory watchdog
    ON at its DEFAULT sampling cadence vs ``MX_MEMWATCH=0``, telemetry
    enabled in both modes (the delta is memwatch alone: the per-step cost
    is one counter increment, plus a live-array census + memory_stats
    snapshot every MX_MEMWATCH_EVERY steps).  Acceptance bar is <2%
    overhead (value >= 0.98) — same interleaved interquartile-mean
    estimator as telemetry_overhead (this box drifts 2x at sub-second
    scale; end-to-end trial means measure the machine)."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, memwatch, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    B = int(os.environ.get("BENCH_MEMWATCH_BATCH", 256))
    D = int(os.environ.get("BENCH_MEMWATCH_DIM", 8192))
    steps = int(os.environ.get("BENCH_MEMWATCH_STEPS", 10))
    trials = int(os.environ.get("BENCH_MEMWATCH_TRIALS", 24))

    rng = np.random.RandomState(0)
    from mxnet_tpu import nd

    x = nd.array(rng.rand(B, D).astype(np.float32))
    y = nd.array(rng.randint(0, 10, B).astype(np.float32))

    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-3})

    import tempfile

    tele_dir = tempfile.mkdtemp(prefix="bench_memwatch_")
    telemetry.enable(tele_dir)

    def one_trial(watch):
        os.environ["MX_MEMWATCH"] = "1" if watch else "0"
        memwatch.reset()
        t0 = time.perf_counter()
        loss = None
        for _i in range(steps):
            loss = step.step(x, y)
        step.drain()
        float(loss)
        dt = time.perf_counter() - t0
        n_samples = memwatch.summary()["samples"] if watch else 0
        return dt, n_samples

    one_trial(False)
    one_trial(True)  # warm compile cache + first census
    offs, ons, n_samples = [], [], 0
    for _ in range(trials):
        dt_off, _ = one_trial(False)
        offs.append(dt_off)
        dt_on, samples = one_trial(True)
        ons.append(dt_on)
        n_samples = max(n_samples, samples)
    os.environ.pop("MX_MEMWATCH", None)

    iq_off, iq_on = _iq_mean(offs), _iq_mean(ons)
    print(json.dumps({
        "metric": "memwatch_overhead",
        "value": round(iq_off / iq_on, 4),
        "unit": "x_on_vs_off",
        "vs_baseline": 0.0,
        "platform": platform,
        "on_steps_per_sec": round(steps / iq_on, 2),
        "off_steps_per_sec": round(steps / iq_off, 2),
        "mem_samples_per_trial": n_samples,
        "batch": B, "dim": D, "steps": steps,
    }))


def bench_metrics_scrape_overhead(platform):
    """Secondary metric: steady-state steps/sec with the live metrics
    endpoint serving AND a 1 Hz scraper hammering ``/metrics`` vs the
    endpoint fully off, telemetry enabled in BOTH modes (the delta is
    the endpoint + scrape load alone — /metrics renders from the
    recorder's locked rollups, so the claim under test is that a scrape
    never perturbs the dispatch loop).  Acceptance bar is <2% overhead
    (value >= 0.98) — same interleaved interquartile-mean estimator as
    telemetry_overhead (this box drifts 2x at sub-second scale)."""
    import threading
    import urllib.request

    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, metrics_server, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    B = int(os.environ.get("BENCH_METRICS_BATCH", 256))
    D = int(os.environ.get("BENCH_METRICS_DIM", 8192))
    steps = int(os.environ.get("BENCH_METRICS_STEPS", 8))
    trials = int(os.environ.get("BENCH_METRICS_TRIALS", 24))

    rng = np.random.RandomState(0)
    from mxnet_tpu import nd

    x = nd.array(rng.rand(B, D).astype(np.float32))
    y = nd.array(rng.randint(0, 10, B).astype(np.float32))

    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(128, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
        optimizer_params={"learning_rate": 1e-3})

    import tempfile

    tele_dir = tempfile.mkdtemp(prefix="bench_metrics_")
    telemetry.enable(tele_dir)
    scrapes = [0]
    scrape_errs = []  # a dead/never-scraping scraper must fail the run
    #                   loudly, not report a vacuous ~1.0 overhead

    def one_trial(scrape_on):
        stop = th = None
        if scrape_on:
            assert metrics_server.start(0), "metrics endpoint failed to bind"
            url = f"http://127.0.0.1:{metrics_server.port()}/metrics"
            stop = threading.Event()

            def hammer():
                while not stop.is_set():
                    try:
                        body = urllib.request.urlopen(url, timeout=2).read()
                        if not body.endswith(b"# EOF\n"):
                            scrape_errs.append(f"torn scrape: {body[-50:]!r}")
                            return
                        scrapes[0] += 1
                    except OSError as e:
                        scrape_errs.append(str(e))
                    stop.wait(1.0)  # the 1 Hz production scrape cadence

            th = threading.Thread(target=hammer, daemon=True)
            th.start()
        t0 = time.perf_counter()
        loss = None
        for _i in range(steps):
            loss = step.step(x, y)
        step.drain()
        float(loss)
        dt = time.perf_counter() - t0
        if scrape_on:
            stop.set()
            th.join(timeout=5.0)
            metrics_server.stop()  # endpoint truly OFF in the off chunks
        return dt

    one_trial(False)
    one_trial(True)  # warm the compile cache + the HTTP stack
    offs, ons = [], []
    for _ in range(trials):
        offs.append(one_trial(False))
        ons.append(one_trial(True))
    assert scrapes[0] > 0, \
        f"scraper never completed a scrape — metric is vacuous: {scrape_errs}"
    assert not any("torn" in e for e in scrape_errs), scrape_errs

    iq_off, iq_on = _iq_mean(offs), _iq_mean(ons)
    print(json.dumps({
        "metric": "metrics_scrape_overhead",
        "value": round(iq_off / iq_on, 4),
        "unit": "x_on_vs_off",
        "vs_baseline": 0.0,
        "platform": platform,
        "on_steps_per_sec": round(steps / iq_on, 2),
        "off_steps_per_sec": round(steps / iq_off, 2),
        "scrapes": scrapes[0],
        "batch": B, "dim": D, "steps": steps,
    }))


def bench_cold_start(platform):
    """cold_start: ONE process's time-to-first-step on a toy net sized
    so XLA compile dominates (the regime the AOT executable cache exists
    for).  Run it twice by hand against one MX_EXECUTABLE_CACHE_DIR —
    run 1 compiles + serializes, run 2 deserializes — and compare
    (docs/PERFORMANCE.md).  time_to_first_step spans step
    construction through the first forced loss: exactly what a restarted
    rank pays before training resumes."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, memwatch, nd, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    layers = int(os.environ.get("BENCH_COLDSTART_LAYERS", 10))
    width = int(os.environ.get("BENCH_COLDSTART_WIDTH", 512))
    K = int(os.environ.get("BENCH_COLDSTART_SUPERSTEP", 4))
    # accum_steps statically unrolls the microbatch loop inside the step
    # program: compile cost scales with it while execute stays ~flat —
    # the big-effective-batch production config whose restart recompile
    # is exactly the SLO this cache addresses
    accum = int(os.environ.get("BENCH_COLDSTART_ACCUM", 4))

    import tempfile

    telemetry.enable(tempfile.mkdtemp(prefix="bench_coldstart_tele_"))
    mx.random.seed(0)
    net = nn.HybridSequential()
    with net.name_scope():
        for _ in range(layers):
            net.add(nn.Dense(width, activation="relu"))
        net.add(nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(32, width).astype(np.float32), ctx=ctx)
    y = nd.array(rng.randint(0, 10, 32).astype(np.float32), ctx=ctx)

    t0 = time.perf_counter()
    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="adam",
        optimizer_params={"learning_rate": 1e-3}, accum_steps=accum)
    # superstep executable included: a restarted superstep-mode rank
    # deserializes the scan program too (the heaviest compile on the box)
    loss = (step.superstep([(x, y)] * K) if K > 1 else step.step(x, y))
    float(np.asarray(loss).ravel()[-1])
    ttfs = time.perf_counter() - t0
    step.drain()
    print(json.dumps({
        "metric": "cold_start_child",
        "value": round(ttfs, 3),
        "unit": "seconds_to_first_step",
        "vs_baseline": 0.0,
        "platform": platform,
        "time_to_first_step_s": round(ttfs, 4),
        "cache_hits": memwatch.summary()["compiles"]["cache_hits"],
        "layers": layers, "width": width, "superstep": K,
        "accum_steps": accum,
    }))


def bench_amp_step(platform):
    """Secondary metric: the graph-level AMP pass on-vs-off
    (docs/PRECISION.md) — steady-state step wall of the compiled
    DataParallelStep with the bf16 cast policy + traced dynamic loss
    scaling vs plain f32, interquartile mean over interleaved trials
    (the telemetry_overhead estimator).  A convergence smoke rides
    along: the AMP trajectory must track the fp32 oracle within the
    documented tolerance, or the speed number is meaningless.  On
    XLA:CPU bf16 is emulated, so value ~1.0 is expected there; the MXU
    issue-rate/HBM win is a TPU fact — the record carries the platform
    so eras read it accordingly."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.parallel import DataParallelStep, local_mesh
    from mxnet_tpu.precision import (AmpPolicy, LossScaleConfig,
                                     PrecisionConfig)

    B = int(os.environ.get("BENCH_AMP_BATCH", 256))
    D = int(os.environ.get("BENCH_AMP_DIM", 1024))
    H = int(os.environ.get("BENCH_AMP_HIDDEN", 2048))
    steps = int(os.environ.get("BENCH_AMP_STEPS", 6))
    trials = int(os.environ.get("BENCH_AMP_TRIALS", 8))

    rng = np.random.RandomState(0)
    x = nd.array(rng.rand(B, D).astype(np.float32))
    y = nd.array(rng.randint(0, 10, B).astype(np.float32))
    prec = PrecisionConfig(amp=AmpPolicy(),
                           loss_scale=LossScaleConfig(init_scale=2.0 ** 10,
                                                      growth_interval=1000))

    def build(precision):
        mx.random.seed(0)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(H, activation="relu", in_units=D),
                    nn.Dense(10, in_units=H))
        net.initialize(mx.init.Xavier(), ctx=ctx)
        return DataParallelStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
            optimizer_params={"learning_rate": 1e-2}, precision=precision)

    def trial(step):
        t0 = time.perf_counter()
        loss = None
        for _ in range(steps):
            loss = step.step(x, y)
        step.drain()
        v = float(loss)
        return (time.perf_counter() - t0) / steps, v

    s32, samp = build(None), build(prec)
    trial(s32), trial(samp)  # compile outside the timed trials
    w32, wamp = [], []
    for _ in range(trials):  # interleave: box drift hits both alike
        w32.append(trial(s32)[0])
        wamp.append(trial(samp)[0])

    # convergence smoke on FRESH nets: losses must track fp32
    c32, camp = build(None), build(prec)
    tr32 = [float(c32.step(x, y)) for _ in range(10)]
    tramp = [float(camp.step(x, y)) for _ in range(10)]
    c32.drain(), camp.drain()
    max_dev = max(abs(a - b) for a, b in zip(tr32, tramp))
    loss_tol = float(os.environ.get("BENCH_AMP_LOSS_TOL", 0.05))

    f32_ms, amp_ms = _iq_mean(w32) * 1e3, _iq_mean(wamp) * 1e3
    print(json.dumps({
        "metric": "amp_step",
        "value": round(f32_ms / amp_ms, 3) if amp_ms else 0.0,
        "unit": "x_fp32_vs_amp_step_wall",
        "vs_baseline": 0.0,
        "platform": platform,
        "fp32_step_ms": round(f32_ms, 3),
        "amp_step_ms": round(amp_ms, 3),
        "loss_max_abs_dev": round(max_dev, 5),
        "loss_tol": loss_tol,
        "losses_track_fp32": bool(max_dev <= loss_tol),
        "final_scale": float(np.asarray(camp.scaler_state["scale"])),
        "skipped_steps": int(np.asarray(camp.scaler_state["skipped"])),
        "batch": B, "dim": D, "hidden": H,
        "steps": steps, "trials": trials,
    }))


def bench_quantized_serving(platform):
    """Secondary metric: the calibrated int8 serving engine vs the fp32
    engine (docs/PRECISION.md §Int8 serving) on the reverse-task
    transformer — tokens/sec ratio, params-bytes, and greedy top-1
    agreement (the number that gates whether the int8 program may serve
    at all).  The params-bytes ratio is the quantized PROGRAM's weight
    footprint (docs/PRECISION.md §Params-bytes accounting — the process
    here still holds the fp32 net, so its live memory is fp32+int8);
    it is exact on any host.  The tokens/sec share needs real MXU int8
    to show its full size, so the agreement + bytes are the
    load-bearing CPU facts."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import nd
    from mxnet_tpu.models.transformer import Transformer, label_smoothed_ce
    from mxnet_tpu.parallel import DataParallelStep, local_mesh
    from mxnet_tpu.precision import quantize_adapter
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    n_req = int(os.environ.get("BENCH_QUANT_REQUESTS", 12))
    trials = int(os.environ.get("BENCH_QUANT_TRIALS", 4))
    train_steps = int(os.environ.get("BENCH_QUANT_TRAIN_STEPS", 48))
    BOS, EOS, L = 1, 2, 6

    mx.random.seed(0)
    net = Transformer(16, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=20, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(2)
    src = np.zeros((8, L + 1), np.int32)
    tgt_in = np.zeros((8, L + 2), np.int32)
    tgt_out = np.zeros((8, L + 2), np.int32)
    for b in range(8):
        toks = rng.randint(3, 16, L)
        src[b, :L] = toks
        tgt_in[b, 0] = BOS
        tgt_in[b, 1:L + 1] = toks[::-1]
        tgt_out[b, :L] = toks[::-1]
        tgt_out[b, L] = EOS
    step = DataParallelStep(
        net, lambda lo, la: label_smoothed_ce(lo, la, smoothing=0.0),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="adam",
        optimizer_params={"learning_rate": 5e-3})
    sb = nd.array(src, dtype="int32")
    tb = nd.array(tgt_in, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32))
    for _ in range(train_steps):
        step.step((sb, tb), lb)
    step.sync_to_block()

    def calib_fn(batch):
        net.translate(nd.array(batch, dtype="int32"), bos_id=BOS,
                      eos_id=EOS, max_len=10, beam_size=1)

    qad = quantize_adapter(TransformerAdapter(net, src_max_len=7),
                           [src[i:i + 1] for i in range(8)], calib_fn,
                           calib_mode=os.environ.get("BENCH_QUANT_CALIB",
                                                     "naive"))

    def build(adapter):
        eng = ServingEngine(adapter, slots=4, page_size=4, max_len=12,
                            stream_every=4, ctx=ctx)
        eng.serve([Request(src[0], 4, bos_id=BOS, eos_id=EOS)])  # warm
        return eng

    def run_trial(eng):
        reqs = [Request(src[i % 8], max_new_tokens=9, bos_id=BOS,
                        eos_id=EOS) for i in range(n_req)]
        t0 = time.perf_counter()
        out = eng.serve(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(r.stream) for r in reqs)
        return toks / wall, {r.id: out[r.id] for r in reqs}, reqs

    eng32 = build(TransformerAdapter(net, src_max_len=7))
    engq = build(qad)
    tps32, tpsq = [], []
    last32 = lastq = None
    for _ in range(trials):  # interleaved against box drift
        v, o, r = run_trial(eng32)
        tps32.append(v)
        last32 = (o, r)
        v, o, r = run_trial(engq)
        tpsq.append(v)
        lastq = (o, r)
    agree = total = 0
    for a, b in zip(last32[1], lastq[1]):
        ta, tbq = list(last32[0][a.id]), list(lastq[0][b.id])
        n = min(len(ta), len(tbq))
        agree += sum(1 for i in range(n) if ta[i] == tbq[i])
        total += max(len(ta), len(tbq))
    thresh = float(os.environ.get("BENCH_QUANT_AGREE_THRESHOLD", 0.9))
    print(json.dumps({
        "metric": "quantized_serving",
        "value": round(_iq_mean(tpsq) / _iq_mean(tps32), 3)
                 if _iq_mean(tps32) else 0.0,
        "unit": "x_int8_vs_fp32_tokens_per_sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "int8_tokens_per_sec": round(_iq_mean(tpsq), 2),
        "fp32_tokens_per_sec": round(_iq_mean(tps32), 2),
        "fp32_param_bytes": qad.fp32_param_bytes(),
        "int8_param_bytes": qad.quantized_param_bytes(),
        "param_bytes_ratio": round(
            qad.quantized_param_bytes() / qad.fp32_param_bytes(), 3),
        "top1_agreement": round(agree / total, 4) if total else 0.0,
        "agreement_threshold": thresh,
        "meets_agreement": bool(total and agree / total >= thresh),
        "quantized_layers": len(qad._entries),
        "requests": n_req, "trials": trials,
    }))


def bench_int4_serving(platform):
    """Secondary metric: weight-only int4 serving (docs/PRECISION.md
    §Int4 weight-only serving) vs the fp32 engine on the reverse-task
    transformer.  The load-bearing CPU facts are the weight-bytes ratio
    (packed nibbles + f16 group scales over the REWRITTEN layers —
    0.5625 bytes/weight at group 32, the ≤0.16x acceptance number), the
    whole-model param-bytes ratio (diluted by f32 embeddings/norms),
    and greedy top-1 agreement; tokens/sec rides along but the
    decode-bandwidth win needs real HBM to show its size."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import nd
    from mxnet_tpu.models.transformer import Transformer, label_smoothed_ce
    from mxnet_tpu.parallel import DataParallelStep, local_mesh
    from mxnet_tpu.precision import int4_adapter
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    n_req = int(os.environ.get("BENCH_INT4_REQUESTS", 12))
    trials = int(os.environ.get("BENCH_INT4_TRIALS", 4))
    train_steps = int(os.environ.get("BENCH_INT4_TRAIN_STEPS", 48))
    group = int(os.environ.get("MX_QUANT_GROUP", 32))
    BOS, EOS, L = 1, 2, 6

    mx.random.seed(0)
    net = Transformer(16, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=20, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(2)
    src = np.zeros((8, L + 1), np.int32)
    tgt_in = np.zeros((8, L + 2), np.int32)
    tgt_out = np.zeros((8, L + 2), np.int32)
    for b in range(8):
        toks = rng.randint(3, 16, L)
        src[b, :L] = toks
        tgt_in[b, 0] = BOS
        tgt_in[b, 1:L + 1] = toks[::-1]
        tgt_out[b, :L] = toks[::-1]
        tgt_out[b, L] = EOS
    step = DataParallelStep(
        net, lambda lo, la: label_smoothed_ce(lo, la, smoothing=0.0),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="adam",
        optimizer_params={"learning_rate": 5e-3})
    sb = nd.array(src, dtype="int32")
    tb = nd.array(tgt_in, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32))
    for _ in range(train_steps):
        step.step((sb, tb), lb)
    step.sync_to_block()

    qad = int4_adapter(TransformerAdapter(net, src_max_len=7),
                       group_size=group)

    def build(adapter):
        eng = ServingEngine(adapter, slots=4, page_size=4, max_len=12,
                            stream_every=4, ctx=ctx)
        eng.serve([Request(src[0], 4, bos_id=BOS, eos_id=EOS)])  # warm
        return eng

    def run_trial(eng):
        reqs = [Request(src[i % 8], max_new_tokens=9, bos_id=BOS,
                        eos_id=EOS) for i in range(n_req)]
        t0 = time.perf_counter()
        out = eng.serve(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(r.stream) for r in reqs)
        return toks / wall, {r.id: out[r.id] for r in reqs}, reqs

    eng32 = build(TransformerAdapter(net, src_max_len=7))
    engq = build(qad)
    tps32, tpsq = [], []
    last32 = lastq = None
    for _ in range(trials):  # interleaved against box drift
        v, o, r = run_trial(eng32)
        tps32.append(v)
        last32 = (o, r)
        v, o, r = run_trial(engq)
        tpsq.append(v)
        lastq = (o, r)
    agree = total = 0
    for a, b in zip(last32[1], lastq[1]):
        ta, tbq = list(last32[0][a.id]), list(lastq[0][b.id])
        n = min(len(ta), len(tbq))
        agree += sum(1 for i in range(n) if ta[i] == tbq[i])
        total += max(len(ta), len(tbq))
    thresh = float(os.environ.get("BENCH_INT4_AGREE_THRESHOLD", 0.99))
    print(json.dumps({
        "metric": "int4_serving",
        "value": round(_iq_mean(tpsq) / _iq_mean(tps32), 3)
                 if _iq_mean(tps32) else 0.0,
        "unit": "x_int4_vs_fp32_tokens_per_sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "int4_tokens_per_sec": round(_iq_mean(tpsq), 2),
        "fp32_tokens_per_sec": round(_iq_mean(tps32), 2),
        "group_size": group,
        "fp32_weight_bytes": qad.fp32_weight_bytes(),
        "int4_weight_bytes": qad.quantized_weight_bytes(),
        "weight_bytes_ratio": round(
            qad.quantized_weight_bytes() / qad.fp32_weight_bytes(), 4),
        "param_bytes_ratio": round(
            qad.quantized_param_bytes() / qad.fp32_param_bytes(), 3),
        "top1_agreement": round(agree / total, 4) if total else 0.0,
        "agreement_threshold": thresh,
        "meets_agreement": bool(total and agree / total >= thresh),
        "quantized_layers": len(qad._entries),
        "requests": n_req, "trials": trials,
    }))


def bench_fused_kernel(platform):
    """Secondary metric: the fused_kernels pass (MX_PALLAS_FUSED=1 —
    registered Pallas kernels substituted at the dispatch point, see
    docs/PRECISION.md §Pass pipeline) vs the stock ops on the serving
    engine.  On CPU the kernels run in interpret mode, so the ratio
    measures correctness overhead, not the fusion win (that needs a
    TPU); the load-bearing CPU facts are the BITWISE token agreement
    with the pass off and the fingerprint split."""
    import numpy as np

    mx, ctx, on_tpu = _common_setup(platform)
    from mxnet_tpu import memwatch, nd
    from mxnet_tpu.models.transformer import Transformer, label_smoothed_ce
    from mxnet_tpu.parallel import DataParallelStep, local_mesh
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    n_req = int(os.environ.get("BENCH_FUSED_REQUESTS", 8))
    trials = int(os.environ.get("BENCH_FUSED_TRIALS", 3))
    train_steps = int(os.environ.get("BENCH_FUSED_TRAIN_STEPS", 48))
    BOS, EOS, L = 1, 2, 6

    mx.random.seed(0)
    net = Transformer(16, units=32, hidden_size=64, num_heads=4,
                      num_layers=2, max_length=20, dropout=0.0)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    rng = np.random.RandomState(2)
    src = np.zeros((8, L + 1), np.int32)
    tgt_in = np.zeros((8, L + 2), np.int32)
    tgt_out = np.zeros((8, L + 2), np.int32)
    for b in range(8):
        toks = rng.randint(3, 16, L)
        src[b, :L] = toks
        tgt_in[b, 0] = BOS
        tgt_in[b, 1:L + 1] = toks[::-1]
        tgt_out[b, :L] = toks[::-1]
        tgt_out[b, L] = EOS
    step = DataParallelStep(
        net, lambda lo, la: label_smoothed_ce(lo, la, smoothing=0.0),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="adam",
        optimizer_params={"learning_rate": 5e-3})
    sb = nd.array(src, dtype="int32")
    tb = nd.array(tgt_in, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32))
    for _ in range(train_steps):
        step.step((sb, tb), lb)
    step.sync_to_block()

    def build():
        eng = ServingEngine(TransformerAdapter(net, src_max_len=7),
                            slots=4, page_size=4, max_len=12,
                            stream_every=4, ctx=ctx)
        eng.serve([Request(src[0], 4, bos_id=BOS, eos_id=EOS)])  # warm
        return eng

    def run_trial(eng):
        reqs = [Request(src[i % 8], max_new_tokens=9, bos_id=BOS,
                        eos_id=EOS) for i in range(n_req)]
        t0 = time.perf_counter()
        out = eng.serve(reqs)
        wall = time.perf_counter() - t0
        toks = sum(len(r.stream) for r in reqs)
        return toks / wall, {r.id: out[r.id] for r in reqs}, reqs

    os.environ["MX_PALLAS_FUSED"] = "0"
    stock = build()
    os.environ["MX_PALLAS_FUSED"] = "1"
    fused = build()
    fp = lambda e: memwatch.fingerprint(
        e._fingerprint_parts(("decode", 4, 2), []))
    tps0, tpsf = [], []
    last0 = lastf = None
    for _ in range(trials):  # interleaved against box drift
        v, o, r = run_trial(stock)
        tps0.append(v)
        last0 = (o, r)
        v, o, r = run_trial(fused)
        tpsf.append(v)
        lastf = (o, r)
    agree = total = 0
    for a, b in zip(last0[1], lastf[1]):
        ta, tbf = list(last0[0][a.id]), list(lastf[0][b.id])
        n = min(len(ta), len(tbf))
        agree += sum(1 for i in range(n) if ta[i] == tbf[i])
        total += max(len(ta), len(tbf))
    print(json.dumps({
        "metric": "fused_kernel",
        "value": round(_iq_mean(tpsf) / _iq_mean(tps0), 3)
                 if _iq_mean(tps0) else 0.0,
        "unit": "x_fused_vs_stock_tokens_per_sec",
        "vs_baseline": 0.0,
        "platform": platform,
        "interpret_mode": not on_tpu,
        "fused_tokens_per_sec": round(_iq_mean(tpsf), 2),
        "stock_tokens_per_sec": round(_iq_mean(tps0), 2),
        "token_agreement": round(agree / total, 4) if total else 0.0,
        "bitwise_tokens": bool(total and agree == total),
        "fingerprint_split": fp(stock) != fp(fused),
        "fused_ops": fused._pipeline.get("fused_kernels")._ops,
        "requests": n_req, "trials": trials,
    }))


_MODELS = {
    "resnet": bench_resnet,
    "bert": bench_bert,
    "transformer": bench_transformer,
    "trainer_overhead": bench_trainer_overhead,
    "pipeline_overlap": bench_pipeline_overlap,
    "serving_throughput": bench_serving_throughput,
    "router_throughput": bench_router_throughput,
    "rqtrace_overhead": bench_rqtrace_overhead,
    "prefix_cache": bench_prefix_cache,
    "spec_decode": bench_spec_decode,
    "plan_choice": bench_plan_choice,
    "amp_step": bench_amp_step,
    "quantized_serving": bench_quantized_serving,
    "int4_serving": bench_int4_serving,
    "fused_kernel": bench_fused_kernel,
    "telemetry_overhead": bench_telemetry_overhead,
    "memwatch_overhead": bench_memwatch_overhead,
    "metrics_scrape_overhead": bench_metrics_scrape_overhead,
    "cold_start": bench_cold_start,
}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", required=True, choices=["cpu", "tpu"],
                    help="the backend jax must have; no fallback")
    args = ap.parse_args()
    model = os.environ.get("BENCH_MODEL", "resnet")
    if model not in _MODELS:
        raise SystemExit(f"bench.py: BENCH_MODEL={model!r}; "
                         f"known: {sorted(_MODELS)}")
    _MODELS[model](args.platform)


if __name__ == "__main__":
    main()
