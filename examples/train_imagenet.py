"""ImageNet-style classification training (reference:
example/image-classification/train_imagenet.py): ResNet/VGG/MobileNet from
the model zoo over ImageRecordIter (.rec) input, with the fused
data-parallel step as the TPU throughput path.

Run:
  python examples/train_imagenet.py --rec train.rec --model resnet50_v1b
  python examples/train_imagenet.py --synthetic   # no data needed
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon.model_zoo import vision
from mxnet_tpu.parallel import DataParallelStep, local_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rec", default=None, help=".rec file (ImageRecordIter)")
    ap.add_argument("--synthetic", action="store_true")
    ap.add_argument("--model", default="resnet50_v1b")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--device", default="tpu", choices=["tpu", "cpu"],
                    help="tpu (default) fails when jax shows no "
                         "accelerator; cpu pins the CPU backend")
    args = ap.parse_args()
    if args.steps < 1:
        raise SystemExit("--steps must be >= 1")
    if args.device == "cpu":
        mx.context.pin_platform("cpu")
    with (mx.cpu() if args.device == "cpu" else mx.tpu()) as ctx:
        train(args, ctx)


def train(args, ctx):
    shape = tuple(int(s) for s in args.image_shape.split(","))
    mx.random.seed(0)
    net = vision.get_model(args.model)
    net.initialize(mx.init.Xavier())
    if args.dtype == "bfloat16":
        net.cast("bfloat16")

    step = DataParallelStep(
        net, gluon.loss.SoftmaxCrossEntropyLoss(),
        mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
        optimizer_params={"learning_rate": args.lr, "momentum": 0.9,
                          "wd": 1e-4})

    if args.rec:
        it = mx.io.ImageRecordIter(
            path_imgrec=args.rec, batch_size=args.batch_size,
            data_shape=shape, shuffle=True, rand_crop=True,
            rand_mirror=True)
        # device-side prefetch: decode/augment AND the H2D transfer of the
        # next batch run in a background thread while the current fused
        # step computes (the step skips its own transfer)
        it = mx.io.DevicePrefetchIter(it, step)

        def batches():
            while True:
                for b in it:
                    yield b.data[0], b.label[0]
                it.reset()
    else:
        rng = np.random.RandomState(0)
        x = rng.rand(args.batch_size, *shape).astype(np.float32)
        y = rng.randint(0, args.num_classes,
                        args.batch_size).astype(np.float32)
        if args.dtype == "bfloat16":
            import ml_dtypes

            x = x.astype(ml_dtypes.bfloat16)
        xb = nd.array(x, ctx=ctx, dtype=x.dtype)
        yb = nd.array(y, ctx=ctx)

        def batches():
            while True:
                yield xb, yb

    gen = batches()
    t0 = time.perf_counter()
    for i, (data, label) in zip(range(args.steps), gen):
        # step() returns a LAZY AsyncLoss: dispatch never blocks, and the
        # loss is only read back at the logging interval below
        loss = step.step(data, label)
        if i % 10 == 0:
            v = float(loss)
            dt = time.perf_counter() - t0
            seen = (i + 1) * args.batch_size
            print(f"step {i}: loss={v:.4f}  {seen / dt:.1f} img/s")
    step.drain()  # land (and error-check) every in-flight step
    v = float(loss)
    print(f"final loss {v:.4f}")
    assert np.isfinite(v)


if __name__ == "__main__":
    main()
