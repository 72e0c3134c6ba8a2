#!/usr/bin/env python
"""Dump the (optimized, when possible) HLO of the fused ResNet-50 train
step, plus an mx.profiler aggregate table — the committed perf evidence the
r3 verdict asked for (analog of inspecting the reference's cuDNN algo
choices / kernel schedule).

    python tools/dump_hlo.py --platform tpu|cpu [--layout NHWC] [--batch 256]

Artifacts land in docs/artifacts/:
    resnet50_step_{layout}_bs{batch}.hlo.txt   (compiler output)
    resnet50_step_{layout}_bs{batch}.profile.txt (per-op aggregate table)

On the TPU platform this is the real XLA:TPU optimized module (layout
assignment, fusion decisions, MXU conv configs all visible); on CPU it
still shows GSPMD partitioning + fusion structure and proves the recipe.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ART = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "docs", "artifacts")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", default="NHWC", choices=["NHWC", "NCHW"])
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--res", type=int, default=224)
    ap.add_argument("--platform", required=True, choices=["cpu", "tpu"],
                    help="tpu fails when jax shows no accelerator; cpu "
                         "pins the CPU backend")
    ap.add_argument("--profile-steps", type=int, default=0,
                    help="also run N profiled steps for the aggregate table")
    args = ap.parse_args()

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd
    from mxnet_tpu.gluon.model_zoo.vision import resnet50_v1b
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    on_tpu = args.platform == "tpu"
    if not on_tpu:
        mx.context.pin_platform("cpu")
    ctx = mx.tpu() if on_tpu else mx.cpu()
    # artifacts are named after the platform of the device that computed
    platform = ctx.jax_device.platform
    mx.context.Context._default_ctx.value = ctx
    mx.random.seed(0)

    net = resnet50_v1b(layout=args.layout)
    net.initialize(mx.init.Xavier())
    if on_tpu:
        net.cast("bfloat16")
    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
        optimizer_params={"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})

    shape = ((args.batch, 3, args.res, args.res) if args.layout == "NCHW"
             else (args.batch, args.res, args.res, 3))
    x = np.random.rand(*shape).astype(np.float32)
    if on_tpu:
        import ml_dtypes

        x = x.astype(ml_dtypes.bfloat16)
    y = np.random.randint(0, 1000, args.batch).astype(np.float32)
    xb = nd.array(x, ctx=ctx, dtype=x.dtype)
    yb = nd.array(y, ctx=ctx)

    # one step builds + compiles the jitted function
    t0 = time.perf_counter()
    loss = step.step(xb, yb)
    float(np.asarray(loss))
    compile_s = time.perf_counter() - t0

    os.makedirs(ART, exist_ok=True)
    tag = f"resnet50_step_{args.layout.lower()}_bs{args.batch}"

    # re-lower with the step's own argument structure; the persistent
    # compile cache makes the second compile a load
    compiled = step._jitted.lower(
        step.params, step.opt_state, jax.random.PRNGKey(0),
        np.float32(0.1), (xb._data,), yb._data).compile()
    texts = [("optimized", compiled.as_text())]

    hlo_path = os.path.join(ART, tag + f".{platform}.hlo.txt")
    with open(hlo_path, "w") as f:
        f.write(f"# platform={platform} layout={args.layout} "
                f"batch={args.batch} res={args.res} "
                f"first-step(incl compile)={compile_s:.1f}s\n")
        for kind, text in texts:
            f.write(f"\n### {kind}\n{text}\n")
    # quick signal: count layout-change ops (transpose/copy) in the module
    ntrans = sum(t.count("transpose(") for _, t in texts)
    print(f"wrote {hlo_path} ({sum(len(t) for _, t in texts)} bytes, "
          f"{ntrans} transpose sites)")

    if args.profile_steps:
        from mxnet_tpu import profiler

        profiler.set_config(profile_all=True)
        profiler.start()
        for _ in range(args.profile_steps):
            loss = step.step(xb, yb)
        float(np.asarray(loss))
        profiler.stop()
        table = profiler.dumps(reset=True)
        ppath = os.path.join(ART, tag + f".{platform}.profile.txt")
        with open(ppath, "w") as f:
            f.write(table)
        print(f"wrote {ppath}")


if __name__ == "__main__":
    sys.exit(main() or 0)
