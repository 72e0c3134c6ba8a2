"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        # on a TPU host, from the root of a checkout

Drives the two hot paths once, through the entry points a user calls, at the
published width of models the repo supports, with random weights and
synthetic data made from a seed:

  train     bert_base() (12 x 768/3072, 12 heads, vocabulary 30,522), bf16,
            batch 32 x 512, Adam, through DataParallelStep on mx.tpu()
  serve     transformer_big(32000) behind TransformerAdapter + ServingEngine
            on mx.tpu(): 32 slots, 256 positions per slot, sources <= 128
  train     resnet50_v1b(layout="NHWC"), bf16, batch 256 at 224^2, SGD with
            momentum, through DataParallelStep
  kernels   one line per Pallas kernel the package exports
  4 chips   when the host has four: the BERT step on local_mesh() (dp4) and
            gluon.Trainer steps with KVStore('device')

One process; it never sets a platform; every phase runs unguarded, so any
exception or failed check is a non-zero exit.  It exits non-zero before any
phase when jax's first device is not a TPU.  The times it prints are for the
record: they are not metrics and go into no table.  The last two lines of
stdout are JSON: a summary of the phases that ends with "claim": null, and
then the result, exactly
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
with the device as jax reports it.  Neither is printed unless every phase
passed.
"""
import collections
import gc
import json
import re
import sys
import time

import numpy as np

T0 = time.perf_counter()


def log(msg):
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def check(ok, what):
    """A failed check ends the run: nothing below it may print a result."""
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def _devices(tree):
    import jax

    return {d for a in jax.tree_util.tree_leaves(tree) for d in a.devices()}


def _platforms(tree):
    return {d.platform for d in _devices(tree)}


def _mosaic_calls(*lowered):
    """Kernel name -> Mosaic calls in lowered programs (an interpreted
    pallas_call lowers to plain HLO and leaves none)."""
    return collections.Counter(
        k for low in lowered
        for k in re.findall(r'kernel_name = "(\w+)"', low.as_text()))


# ---------------------------------------------------------------------------
# the jobs: (net, loss_fn, data, label) on ctx, everything from a seed
# ---------------------------------------------------------------------------
def bert_job(ctx, make_net, vocab, batch, seqlen):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    net = make_net()
    net.initialize(mx.init.Normal(0.02), ctx=ctx)
    net.cast("bfloat16")
    ce = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(logits, labels):
        return ce(logits.reshape(-1, logits.shape[-1]), labels.reshape(-1))

    tokens = np.random.RandomState(0).randint(
        0, vocab, (batch, seqlen)).astype(np.int32)
    return (net, mlm_loss, nd.array(tokens, ctx=ctx, dtype="int32"),
            nd.array(tokens.astype(np.float32), ctx=ctx))


def resnet_job(ctx, make_net, classes, batch, res):
    import ml_dtypes

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd

    net = make_net()
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.cast("bfloat16")
    rng = np.random.RandomState(0)
    x = rng.rand(batch, res, res, 3).astype(ml_dtypes.bfloat16)
    y = rng.randint(0, classes, batch).astype(np.float32)
    return (net, gluon.loss.SoftmaxCrossEntropyLoss(),
            nd.array(x, ctx=ctx, dtype=x.dtype), nd.array(y, ctx=ctx))


# ---------------------------------------------------------------------------
# train: one DataParallelStep, a few steps on one repeated batch
# ---------------------------------------------------------------------------
def train_phase(tag, ctx, mesh, job, optimizer, opt_params, steps,
                mosaic_kernels=()):
    """Returns {"first_loss", "kernels"}.  ``mosaic_kernels`` are the
    Pallas kernel names the lowered step must call through Mosaic (a
    one-device mesh only: GSPMD cannot partition a Mosaic call, so over
    several devices the step keeps the stock XLA ops)."""
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu.ops import pallas
    from mxnet_tpu.parallel import DataParallelStep

    platform = ctx.jax_device.platform
    mesh_devs = set(mesh.devices.flat)
    with ctx:
        mx.random.seed(0)
        net, loss_fn, x, y = job(ctx)
        step = DataParallelStep(net, loss_fn, mesh=mesh, optimizer=optimizer,
                                optimizer_params=opt_params)

        # state init apart from the compile: one eager forward at full
        # batch resolves the deferred shapes, then params go to the mesh
        t0 = time.perf_counter()
        (x,), y = step.stage(x, y)
        jax.block_until_ready(step.params)
        init_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        handle = step.step(x, y)
        jax.block_until_ready(step.params)
        compile_s = time.perf_counter() - t0
        check(_platforms(handle._value) == {platform},
              f"{tag}: loss on {_platforms(handle._value)}")
        first = float(handle)

        # the same steps closed two ways; the two must agree (a wait that
        # does not wait shows up as a block_until_ready time near zero)
        t0 = time.perf_counter()
        for _ in range(steps):
            handle = step.step(x, y)
        jax.block_until_ready(step.params)
        bur_s = (time.perf_counter() - t0) / steps
        t0 = time.perf_counter()
        for _ in range(steps):
            handle = step.step(x, y)
        last = float(handle)
        host_s = (time.perf_counter() - t0) / steps
        log(f"{tag}: state init {init_s:.1f}s (eager forward for deferred "
            f"shapes, placement); compile+first step {compile_s:.1f}s; "
            f"per step "
            f"{bur_s * 1e3:.1f} ms closed by block_until_ready, "
            f"{host_s * 1e3:.1f} ms closed by a host read of the loss; "
            f"loss {first:.4f} -> {last:.4f} over {2 * steps + 1} steps")
        check(0.5 <= bur_s / host_s <= 2.0,
              f"{tag}: block_until_ready {bur_s:.4f}s/step and host read "
              f"{host_s:.4f}s/step disagree")
        check(np.isfinite(last) and last < first,
              f"{tag}: loss {first} -> {last}")

        # residency: every parameter and optimizer leaf on the mesh
        check(_platforms((step.params, step.opt_state)) == {platform},
              f"{tag}: state on {_platforms((step.params, step.opt_state))}")
        check(_devices(step.params) == mesh_devs,
              f"{tag}: params on {len(_devices(step.params))} device(s), "
              f"mesh has {len(mesh_devs)}")

        # Mosaic: the lowered step calls the kernels compiled, not
        # interpreted
        kernels = collections.Counter()
        if mosaic_kernels:
            with pallas.compute_on(platform):
                kernels = _mosaic_calls(step._jitted.lower(
                    step.params, step.opt_state, jax.random.PRNGKey(0),
                    np.float32(0.0),
                    (jax.ShapeDtypeStruct(x.shape, x._data.dtype),),
                    jax.ShapeDtypeStruct(y.shape, y._data.dtype)))
            for k in mosaic_kernels:
                check(kernels[k] > 0, f"{tag}: no Mosaic call to {k} in the "
                                      f"lowered step (found {dict(kernels)})")
            log(f"{tag}: {sum(kernels.values())} Mosaic calls in the lowered "
                f"step: {dict(kernels)}")

        # where donation bites: the step donated its parameters on every
        # call above; none of these may meet a deleted array
        p0 = next(iter(net.collect_params().values()))
        check(np.isfinite(p0.data(ctx).asnumpy().astype(np.float32)).all(),
              f"{tag}: the block's own parameter before sync_to_block")
        step.drain()
        step.sync_to_block()
        check(np.isfinite(p0.data(ctx).asnumpy().astype(np.float32)).all(),
              f"{tag}: parameter read through the block")
        state = step.state_dict()
        check(len(state["params"]) == len(step.params)
              and all(np.isfinite(np.asarray(v, np.float32)).all()
                      for v in state["params"].values()),
              f"{tag}: state_dict params")
        check(len(state["opt_state"]) >= len(step.params),
              f"{tag}: state_dict optimizer state")
        again = float(step.step(x, y))
        check(np.isfinite(again),
              f"{tag}: step after sync_to_block/state_dict")
        step.drain()
    return {"first_loss": first, "kernels": kernels}


# ---------------------------------------------------------------------------
# serve: transformer behind the engine, mixed lengths, mid-flight arrivals
# ---------------------------------------------------------------------------
def serve_phase(tag, ctx, make_net, vocab, slots, max_len, src_max,
                n_requests):
    import mxnet_tpu as mx
    from mxnet_tpu.ops import pallas
    from mxnet_tpu.serving import Request, ServingEngine, TransformerAdapter

    platform = ctx.jax_device.platform

    def trace():
        """The same requests every call: mixed source and output lengths,
        the second half arriving mid-flight.  eos_id=-1 is never produced,
        so every stream must run to its requested length."""
        rng = np.random.RandomState(7)
        lengths = [max_len // 32, max_len // 15, max_len // 6,
                   3 * max_len // 8, max_len - 8]
        reqs, arrivals = [], []
        for i in range(n_requests):
            src = rng.randint(3, vocab, int(rng.randint(4, src_max + 1)))
            reqs.append(Request(src, max_new_tokens=lengths[i % len(lengths)],
                                bos_id=1, eos_id=-1, request_id=f"r{i}"))
            arrivals.append(0 if i < n_requests // 2
                            else int(rng.randint(1, 24)))
        return reqs, arrivals

    with ctx:
        mx.random.seed(0)
        net = make_net()
        net.initialize(mx.init.Xavier(), ctx=ctx)
        eng = ServingEngine(TransformerAdapter(net, src_max_len=src_max),
                            slots=slots, page_size=16, max_len=max_len,
                            ctx=ctx)
        runs = []
        for n in (1, 2):
            reqs, arrivals = trace()
            t0 = time.perf_counter()
            out = eng.serve(reqs, arrivals)
            dt = time.perf_counter() - t0
            for r in reqs:
                toks = out[r.id]
                check(len(toks) == r.max_new_tokens,
                      f"{tag}: {r.id} has {len(toks)} tokens, asked "
                      f"{r.max_new_tokens}")
                check(((0 <= toks) & (toks < vocab)).all(),
                      f"{tag}: {r.id} has out-of-vocabulary ids")
            runs.append(out)
            log(f"{tag}: pass {n}: {len(reqs)} requests, "
                f"{sum(len(t) for t in out.values())} tokens, "
                f"{eng.step_count} decode steps so far, {dt:.1f}s"
                + (" (prefill and decode compiles included)" if n == 1
                   else ""))
        check(all(np.array_equal(runs[0][k], runs[1][k]) for k in runs[0]),
              f"{tag}: the same requests served twice gave other tokens")
        # both executables computed on the chip: the decode step's outputs
        # are the engine state; the prefill's are its "mem" rows
        state = {k: v._data for k, v in eng._state.items()}
        check(_devices(state) == {ctx.jax_device},
              f"{tag}: engine state on {_devices(state)}")
        with pallas.compute_on(platform):
            kernels = _mosaic_calls(
                eng._run.lower(eng._params(), *state.values()),
                eng._prefill_run.lower(eng._params(),
                                       np.zeros((1, src_max), np.int32)))
        log(f"{tag}: Mosaic calls in the lowered prefill and decode steps: "
            f"{dict(kernels)}")
    return {"fused_paged_attention": bool(eng._adapter._resolved_fused()),
            "kernels": kernels}


# ---------------------------------------------------------------------------
# kernels: one line each
# ---------------------------------------------------------------------------
def kernels_phase(ctx, on_path, fused_paged_attention, rows, units, heads,
                  seqlen, vocab):
    """One line per Pallas kernel the package exports: where this run's
    lowered steps call it (``on_path``: kernel name -> calls), and, compiled
    alone by Mosaic at the BERT phase's shapes, how far it is from the jnp
    composition it replaces (bf16 data: a few bf16 roundings of the largest
    value)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops import pallas
    from mxnet_tpu.ops.contrib_ops import _dense_attention

    dev = ctx.jax_device
    rng = np.random.RandomState(0)

    def rand(*shape):
        return jax.device_put(jnp.asarray(rng.randn(*shape), jnp.bfloat16),
                              dev)

    def delta(got, want):
        """Largest difference over the largest reference magnitude."""
        got, want = (jnp.asarray(a, jnp.float32) for a in (got, want))
        return float(jnp.max(jnp.abs(got - want))
                     / jnp.maximum(jnp.max(jnp.abs(want)), 1e-6))

    def line(name, kernels, alone):
        calls = {k: on_path[k] for k in kernels if on_path[k]}
        where = (f"compiled by Mosaic on this run's path ({calls})" if calls
                 else "on no path of this run")
        log(f"kernel {name}: {where}; {alone}")

    def ln_ref(x, g, b):
        x = x.astype(jnp.float32)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return ((x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b)

    with pallas.compute_on(dev.platform):
        # flash attention, forward and backward, against dense attention
        q, k, v, w = (rand(rows // seqlen * heads, seqlen, units // heads)
                      for _ in range(4))

        def loss(attend):
            return lambda q, k, v: (attend(q, k, v).astype(jnp.float32)
                                    * w.astype(jnp.float32)).sum()

        def dense(q, k, v):
            return _dense_attention(q, k, v, False,
                                    1.0 / np.sqrt(q.shape[-1]))

        got = jax.jit(jax.value_and_grad(loss(pallas.flash_attention),
                                         argnums=(0, 1, 2)))(q, k, v)
        want = jax.jit(jax.value_and_grad(loss(dense),
                                          argnums=(0, 1, 2)))(q, k, v)
        d_fwd = delta(jax.jit(pallas.flash_attention)(q, k, v),
                      jax.jit(dense)(q, k, v))
        d_bwd = max(delta(a, b) for a, b in zip(got[1], want[1]))
        check(d_fwd < 3e-2 and d_bwd < 3e-2,
              f"flash_attention differs: forward {d_fwd}, backward {d_bwd}")
        line("flash_attention", ("mx_flash_fwd", "mx_flash_dq", "mx_flash_dkv"),
             f"alone at {tuple(q.shape)} bf16, relative delta to dense "
             f"attention {d_fwd:.1e} forward, {d_bwd:.1e} backward (BERT's "
             f"training step takes the dense path while attention-"
             f"probability dropout is on)")

        # the Mamba-2 chunked scan, forward and backward, against its einsum
        # form at the nemotron_h widths (heads of 64, state 128, chunk 128)
        from mxnet_tpu.ops import ssm_ops

        sx, sdt = rand(2, 1024, 16, 64), rand(2, 1024, 16)
        sb, sc, sw = rand(2, 1024, 2, 128), rand(2, 1024, 2, 128), \
            rand(2, 1024, 16, 64)
        heads_f32 = [jax.device_put(jnp.asarray(v, jnp.float32), dev)
                     for v in (np.log(np.arange(1.0, 17.0)), np.ones(16),
                               np.full(16, -2.0))]

        def scan_loss(scan):
            def f(x, dt, b, c, a_log, d, dt_bias):
                y = scan(x, dt, a_log, b, c, d, dt_bias)
                return (y.astype(jnp.float32) * sw.astype(jnp.float32)).sum()
            return jax.jit(jax.value_and_grad(f, argnums=tuple(range(7))))

        scan_args = (sx, sdt, sb, sc, *heads_f32)
        by_kernel = lambda *a: ssm_ops.ssd_scan(*a, chunk=128)
        by_einsums = lambda *a: ssm_ops._ssd_scan(*a, 128)
        as_op = lambda scan: jax.jit(lambda x, dt, b, c, a_log, d, dt_bias:
                                     scan(x, dt, a_log, b, c, d, dt_bias))
        d_fwd = delta(as_op(by_kernel)(*scan_args),
                      as_op(by_einsums)(*scan_args))
        got = scan_loss(by_kernel)(*scan_args)
        want = scan_loss(by_einsums)(*scan_args)
        d_bwd = max(delta(a, b) for a, b in zip(got[1], want[1]))
        # the per-head parameter gradients are sums that cancel: a few
        # bf16 roundings of their terms, not of their value
        check(d_fwd < 3e-2 and d_bwd < 5e-2,
              f"ssd_scan differs: forward {d_fwd}, backward {d_bwd}")
        line("ssd_scan", ("mx_ssd_fwd", "mx_ssd_bwd"),
             f"alone at {tuple(sx.shape)} bf16 over 2 groups of state 128, "
             f"relative delta to the einsum form {d_fwd:.1e} forward, "
             f"{d_bwd:.1e} over its seven gradients")

        # the held experts' chunk loop, forward and backward, at the
        # nemotron_h widths: megablox's grouped products and the combine
        # against ragged_dot and a scatter-add (the form a partitioned step
        # takes)
        from mxnet_tpu.ops import moe_ops

        eu, ew = rand(2048, 2688), rand(2048, 2688)
        up, down = (rand(8, 2688, 1856) * 0.02).astype(jnp.bfloat16), \
            (rand(8, 1856, 2688) * 0.02).astype(jnp.bfloat16)
        experts, gates = jax.jit(lambda u, r: moe_ops.moe_route(
            u, r, jnp.zeros((32,)), top_k=6, scaling=2.5))(eu, rand(32, 2688))

        def experts_loss(u, gates, up, down):
            out = moe_ops.moe_experts(u, experts, gates, up, down)[0]
            return (out.astype(jnp.float32) * ew.astype(jnp.float32)).sum()

        got = jax.jit(jax.value_and_grad(
            experts_loss, argnums=(0, 1, 2, 3)))(eu, gates, up, down)
        with pallas.compute_on(dev.platform, partitioned=True):
            want = jax.jit(jax.value_and_grad(
                experts_loss, argnums=(0, 1, 2, 3)))(eu, gates, up, down)
        d_fwd = delta(got[0], want[0])
        d_bwd = max(delta(a, b) for a, b in zip(got[1], want[1]))
        check(d_fwd < 3e-2 and d_bwd < 3e-2,
              f"moe_experts differs: forward {d_fwd}, backward {d_bwd}")
        line("moe_combine", ("mx_moe_combine",),
             f"alone at {tuple(eu.shape)} bf16, 8 of 32 experts of 1856 held, "
             f"6 choices: relative delta to the scatter-add form "
             f"{d_fwd:.1e} for the sum, {d_bwd:.1e} over its four gradients")

        # the same op at the zaya widths: one choice a token over 16 gated
        # experts all held, 8,192 rows in one chunk of uneven groups, at the
        # tiles ``grouped_tiling`` picks for that shape
        zu, zw = rand(8192, 2048), rand(8192, 2048)
        zmats = tuple((rand(16, 2048, 2048) * 0.02).astype(jnp.bfloat16)
                      for _ in range(3))
        zexperts = jax.random.categorical(
            jax.random.PRNGKey(7), jnp.linspace(1.5, -1.5, 16),
            shape=(8192, 1)).astype(jnp.int32)
        zgates = jax.nn.sigmoid(rand(8192, 1).astype(jnp.float32))

        def gated_loss(u, gates, gate, up, down):
            out = moe_ops.moe_experts(u, zexperts, gates, up, down, gate,
                                      activation="swiglu")[0]
            return (out.astype(jnp.float32) * zw.astype(jnp.float32)).sum()

        gated = lambda: jax.jit(jax.value_and_grad(
            gated_loss, argnums=(0, 1, 2, 3, 4)))(zu, zgates, *zmats)
        got = gated()
        with pallas.compute_on(dev.platform, partitioned=True):
            want = gated()
        d_fwd = delta(got[0], want[0])
        d_bwd = max(delta(a, b) for a, b in zip(got[1], want[1]))
        check(d_fwd < 3e-2 and d_bwd < 3e-2,
              f"gated moe_experts differs: forward {d_fwd}, backward {d_bwd}")
        tiles = moe_ops.grouped_tiling("gmm", 8192, 2048, 2048, 16, 2)
        log(f"kernel moe_experts (gated): alone at {tuple(zu.shape)} bf16, 16 "
            f"experts of 2048 held, 1 choice, gmm tiles {tiles}: relative "
            f"delta to the ragged_dot form {d_fwd:.1e} for the sum, "
            f"{d_bwd:.1e} over its five gradients")

        # a hyper-connected sublayer at the xing4_0 widths: the stream mix's
        # kernels against the jax form (the form a partitioned step takes)
        from mxnet_tpu.ops import hc_ops

        streams, mix_w = rand(1024, 4 * 3584), rand(3584)
        mix_leaves = (jnp.ones((4 * 3584,), jnp.bfloat16),
                      (rand(24, 4 * 3584) * 0.02).astype(jnp.bfloat16),
                      jnp.full((3,), 0.01, jnp.bfloat16), rand(24))

        def mix_loss(x, gain, phi, a, b):
            c = hc_ops.mhc_coefficients(x, gain, phi, a, b)
            out = hc_ops.mhc_post(x, hc_ops.mhc_pre(x, c) * mix_w, c)
            return (out.astype(jnp.float32)
                    * streams.astype(jnp.float32)).sum(), out

        mix = lambda: jax.jit(jax.value_and_grad(mix_loss, has_aux=True))(
            streams, *mix_leaves)
        got = mix()
        with pallas.compute_on(dev.platform, partitioned=True):
            want = mix()
        d_fwd, d_bwd = delta(got[0][1], want[0][1]), delta(got[1], want[1])
        check(d_fwd < 3e-2 and d_bwd < 3e-2,
              f"mhc_mix differs: forward {d_fwd}, backward {d_bwd}")
        line("mhc_mix", ("mx_mhc_coef", "mx_mhc_pre", "mx_mhc_post",
                         "mx_mhc_post_bwd", "mx_mhc_coef_pre_bwd"),
             f"alone at {tuple(streams.shape)} bf16, 4 streams of 3584, 20 "
             f"iterations: relative delta to the jax form {d_fwd:.1e} for "
             f"the streams after the sublayer, {d_bwd:.1e} for gX")

        x, r = rand(rows, units), rand(rows, units)
        g = jnp.ones((units,), jnp.bfloat16)
        b = jnp.zeros((units,), jnp.bfloat16)
        d_ln = delta(jax.jit(pallas.layer_norm)(x, g, b), ln_ref(x, g, b))
        check(d_ln < 3e-2, f"layer_norm differs by {d_ln}")
        line("layer_norm", ("mx_layer_norm_fwd",),
             f"alone at {tuple(x.shape)} bf16, relative delta {d_ln:.1e}")
        d_aln = delta(jax.jit(pallas.add_layer_norm)(x, r, g, b),
                      ln_ref(x.astype(jnp.float32) + r.astype(jnp.float32),
                             g, b))
        check(d_aln < 3e-2, f"add_layer_norm differs by {d_aln}")
        line("add_layer_norm", ("mx_add_layer_norm_fwd",),
             f"alone at {tuple(x.shape)} bf16, relative delta {d_aln:.1e}")

        # exported only; at the repo's own vocabulary, where its row block
        # used to overflow VMEM
        logits = rand(1024, vocab)
        labels = jax.device_put(
            jnp.asarray(rng.randint(0, vocab, 1024), jnp.int32), dev)
        xf = logits.astype(jnp.float32)
        want = (jax.nn.logsumexp(xf, axis=-1)
                - jnp.take_along_axis(xf, labels[:, None], axis=-1)[:, 0])
        d_sce = delta(jax.jit(pallas.softmax_cross_entropy)(logits, labels),
                      want)
        check(d_sce < 1e-3, f"softmax_cross_entropy differs by {d_sce}")
        line("softmax_cross_entropy", ("mx_softmax_xent",),
             f"alone at {tuple(logits.shape)} bf16, relative delta "
             f"{d_sce:.1e} (exported only: no model calls it)")

    check(not fused_paged_attention,
          "the serving engine selected the paged Pallas kernel")
    log("kernel paged_decode_attention: not on the TPU path. Mosaic rejects "
        "its head-batched dot and it maps the whole pool as one VMEM block; "
        "the engine decodes through the XLA gather path (MX_SERVE_FLASH="
        "auto) until ROADMAP A4 rewrites it page-blocked; MX_SERVE_FLASH=1 "
        "forces it and fails at compile time")


# ---------------------------------------------------------------------------
# four chips: dp4 BERT against the one-chip loss, and KVStore('device')
# ---------------------------------------------------------------------------
def kvstore_phase(tag, ctxs):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon import nn

    mx.random.seed(0)
    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation="relu"), nn.Dense(10))
    net.initialize(mx.init.Xavier(), ctx=ctxs)
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9},
                            kvstore="device")
    rng = np.random.RandomState(1)
    xs = [nd.array(rng.randn(8, 64).astype(np.float32), ctx=c) for c in ctxs]
    ys = [nd.array(rng.randint(0, 10, 8).astype(np.float32), ctx=c)
          for c in ctxs]
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    params = list(net.collect_params().values())
    for n in range(2):  # the second step computes on what the first pulled
        with autograd.record():
            losses = [loss_fn(net(x), y) for x, y in zip(xs, ys)]
        if n == 0:
            before = [p.data(ctxs[0]).asnumpy() for p in params]
        autograd.backward(losses)
        trainer.step(8 * len(ctxs))
    for p, b in zip(params, before):
        vals = [p.data(c).asnumpy() for c in ctxs]
        check(all(np.isfinite(v).all() for v in vals), f"{tag}: {p.name}")
        check(all(np.array_equal(vals[0], v) for v in vals[1:]),
              f"{tag}: {p.name} differs between devices after the reduce")
        check(not np.array_equal(vals[0], b), f"{tag}: {p.name} not updated")
        for c in ctxs:
            check(_devices(p.data(c)._data) == {c.jax_device},
                  f"{tag}: {p.name} for {c} on {_devices(p.data(c)._data)}")
    log(f"{tag}: two Trainer steps over {len(ctxs)} contexts: weights "
        "updated and equal on every device")


def main():
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"platform: {dev.platform}\ndevice_kind: {dev.device_kind}\n"
          f"device count: {device['count']}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: jax found no TPU; nothing was run",
              file=sys.stderr)
        return 1

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1b
    from mxnet_tpu.models import bert_base
    from mxnet_tpu.models.transformer import transformer_big
    from mxnet_tpu.parallel import local_mesh

    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    ctx = mx.tpu(0)
    one_chip = local_mesh(devices=[ctx.jax_device])
    phases = {}

    def bert(c):
        return bert_job(c, bert_base, 30522, 32, 512)

    bert_kernels = ("mx_layer_norm_fwd",)
    bert1 = train_phase("train bert_base bf16 32x512 adam", ctx, one_chip,
                        bert, "adam", {"learning_rate": 1e-4}, steps=5,
                        mosaic_kernels=bert_kernels)
    phases["train_bert_base"] = "ok"
    gc.collect()

    served = serve_phase("serve transformer_big 32 slots x 256", ctx,
                         lambda: transformer_big(32000), 32000, slots=32,
                         max_len=256, src_max=128, n_requests=12)
    phases["serve_transformer_big"] = "ok"
    gc.collect()

    train_phase("train resnet50_v1b NHWC bf16 256x224^2 sgd", ctx, one_chip,
                lambda c: resnet_job(c, lambda: resnet50_v1b(layout="NHWC"),
                                     1000, 256, 224),
                "sgd", {"learning_rate": 0.03, "momentum": 0.9, "wd": 1e-4},
                steps=5)
    phases["train_resnet50_v1b"] = "ok"
    gc.collect()

    kernels_phase(ctx, bert1["kernels"] + served["kernels"],
                  served["fused_paged_attention"], rows=32 * 512, units=768,
                  heads=12, seqlen=512, vocab=30522)
    phases["kernels"] = "ok"

    if len(jax.local_devices()) >= 4:
        mesh4 = local_mesh(devices=jax.local_devices()[:4])
        bert4 = train_phase("train bert_base bf16 32x512 adam dp4", ctx,
                            mesh4, bert, "adam", {"learning_rate": 1e-4},
                            steps=2)
        a, b = bert4["first_loss"], bert1["first_loss"]
        # the bound __graft_entry__.dryrun_multichip holds a resharded step
        # to against the same step on another layout
        check(abs(a - b) <= 2e-3 * max(1.0, abs(b)),
              f"dp4 first loss {a!r} vs one chip {b!r}")
        log(f"four chips: dp4 first loss {a:.4f}, one chip {b:.4f}")
        gc.collect()
        kvstore_phase("four chips: KVStore('device')",
                      [mx.tpu(i) for i in range(4)])
        phases["four_chips"] = "ok"
    else:
        log(f"four chips: did not run ({len(jax.local_devices())} local "
            "device)")
        phases["four_chips"] = "not run"

    log("all phases passed")
    print(json.dumps({"phases": phases,
                      "seconds": round(time.perf_counter() - T0, 1),
                      "claim": None}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
