"""Traffic kind ``train_job``: one compiled training step, one repeated
seeded batch staged once on the device, driven through
``DataParallelStep.stage`` + ``.step``.

Set-up builds ONE step object, drives it from the seed through its first
``check_steps`` steps (the window's own call and feed) and hands that same
object to the window.  The readings of those steps (losses, the first
gradient's norms out of Adam's first moment, the parameters' change) are
reduced to scalars on the device before the window; the plain reference
runs after the window has closed, the peak has been read and the program's
state is freed.
"""
import gc
import time

import numpy as np


def _leaf_norms(tree):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
        v.astype(jnp.float32)))) for k, v in t.items()})(tree)


def _delta_norms(now, start):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
        for k in a})(now, start)


def batch_tokens(seed, vocab, batch, seq):
    """The job's one batch: rows that all differ, from the seed."""
    return np.random.default_rng([int(seed), 1]).integers(
        0, vocab, (batch, seq), dtype=np.int32)


class Job:
    """The program side: the compiled step with its state."""

    def __init__(self, cell, seed, ctx, devices):
        import jax

        import mxnet_tpu as mx
        from mxnet_tpu import nd
        from mxnet_tpu.parallel import DataParallelStep, local_mesh

        from .. import loader, weights

        cfg, traffic = cell.config, cell.traffic
        self.cfg, self.ctx, self.seed = cfg, ctx, int(seed)
        self.ref = cell.reference()
        self.spec = self.ref.param_spec(cfg)
        self.batch = traffic["batch_per_chip"] * len(devices)
        self.seq = traffic["seq_len"]
        self.tokens = batch_tokens(seed, cfg["vocab_size"], self.batch,
                                   self.seq)
        prog = cfg["program"]
        opt = dict(cfg["optimizer"])
        self.beta1 = opt["beta1"]
        with ctx:
            # the program's dropout stream starts at PRNGKey(seed)
            mx.random.seed(self.seed & 0x7FFFFFFF)
            net = loader.factory(prog["factory"])(**prog["factory_kwargs"])
            net.initialize(mx.init.Zero(), ctx=ctx)
            net.cast(cfg["dtype"])
            w = weights.make_weights(self.spec, self.seed, cfg["dtype"],
                                     ctx.jax_device)
            weights.install(net, w, ctx)
            del w
            self.prefix = net.prefix
            self.step = DataParallelStep(
                net, loader.factory(prog["loss"])(),
                mesh=local_mesh(devices=list(devices)),
                optimizer=opt.pop("name"), optimizer_params=opt)
            x = nd.array(self.tokens, ctx=ctx, dtype="int32")
            y = nd.array(self.tokens.astype(np.float32), ctx=ctx)
            (self.x,), self.y = self.step.stage(x, y)
            jax.block_until_ready(self.step.params)
        self.net = net

    def _strip(self, tree):
        n = len(self.prefix)
        return {(k[n:] if k.startswith(self.prefix) else k): v
                for k, v in tree.items()}

    def first_steps(self, n):
        """Drive the step through its first ``n`` steps by the window's own
        call and return the program's readings as host numbers."""
        import jax

        from .. import check, weights

        losses, grad, probe = [], None, None
        for i in range(n):
            h = self.step.step(self.x, self.y)
            losses.append(h)
            if i == 0:      # Adam's first moment after one step: (1-b1) g
                m = self._strip(self.step.opt_state[0])
                grad, probe = _leaf_norms(m), check.sketch(m)
        start = weights.make_weights(self.spec, self.seed, self.cfg["dtype"],
                                     self.ctx.jax_device)
        now = self._strip(self.step.params)
        start = {k: jax.device_put(v, now[k].sharding)   # replicated on a mesh
                 for k, v in start.items()}
        delta = _delta_norms(now, start)
        del start
        grad, delta, probe = jax.device_get((grad, delta, probe))
        return {"loss": [float(h) for h in losses],
                "grad_norm": {k: float(v) / (1 - self.beta1)
                              for k, v in grad.items()},
                "grad_sketch": {k: v / (1 - self.beta1)
                                for k, v in probe.items()},
                "delta_norm": {k: float(v) for k, v in delta.items()}}

    def call(self):
        return self.step.step(self.x, self.y)

    def close(self):
        import jax

        jax.block_until_ready(self.step.params)

    def free(self):
        self.step.drain()
        self.step = self.net = self.x = self.y = None
        gc.collect()


def reference_readings(cell, seed, device, quant=None, rows=None):
    """The plain reference (or, with ``quant``/``rows``, the control or a
    planted fault) over the same batch from the same seed."""
    import jax

    from .. import check, weights

    cfg, traffic = cell.config, cell.traffic
    ref = cell.reference()
    batch = traffic["batch_per_chip"] * cell.chips
    tokens = batch_tokens(seed, cfg["vocab_size"], batch, traffic["seq_len"])
    with jax.default_device(device):
        w = weights.make_weights(ref.param_spec(cfg), int(seed), cfg["dtype"],
                                 device)
        return ref.train(cfg, w, tokens, int(seed) & 0x7FFFFFFF,
                         traffic["check_steps"],
                         cell.checks["reference"]["rows_per_block"],
                         quant=quant, rows=rows, probe=check.sketch)


def run(cell, args, rec, clock, devices, ctx):
    """One run of a training cell -> the harness's result parts."""
    from .. import check, tracing

    traffic = cell.traffic
    job = Job(cell, args.seed, ctx, devices)
    got = job.first_steps(traffic["check_steps"])
    job.close()
    items = job.batch * job.seq
    done = [0]
    tracer = tracing.Window(cell, rec, traffic, args.seconds,
                            lambda: done[0]) if args.trace else None

    # ---- the window
    t0 = time.perf_counter()
    setup_s = t0 - clock.t_process
    t_end = t0 + args.seconds
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if tracer is not None:
            tracer.poll(now - t0, job.close)
        with rec.span("step_call"):
            last = job.call()
        done[0] += 1
    with rec.span("close"):
        job.close()
    t1 = time.perf_counter()
    if tracer is not None:
        tracer.close()
    steps, window_s = done[0], t1 - t0
    obs = {"t0": t0, "t1": t1, "window_s": window_s, "steps": steps,
           "items": steps * items, "seq_len": job.seq,
           "chips": len(devices)}
    e2e = {"train_throughput": steps * items / window_s / len(devices),
           "setup_s": setup_s}
    rec.counters["loss_at_close"] = float(last)
    calls = rec.durations("step_call", t0, t1)
    third = max(1, len(calls) // 3)      # a slow stretch shows where it lies
    rec.counters.update(
        step_call_max_ms=1e3 * max(calls), step_call_max_at=calls.index(
            max(calls)),
        **{f"step_call_mean_ms_third{i + 1}": 1e3 * sum(
            calls[i * third:(i + 1) * third or None][:third]) / third
           for i in range(3)})
    peak = max(tracing.memory_peak(d) for d in devices)
    job.free()

    # ---- the comparison, after the window, on freed memory
    t = time.perf_counter()
    ref = reference_readings(cell, args.seed, devices[0])
    rec.counters["reference_s"] = time.perf_counter() - t
    numbers = check.training_numbers(got, ref)
    return {"e2e": e2e, "obs": obs, "attempted": steps, "failed": 0,
            "numbers": numbers, "memory_peak_bytes": peak,
            "trace": tracer.reduced() if tracer is not None else None}
