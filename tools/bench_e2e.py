#!/usr/bin/env python
"""Config-2 end-to-end rehearsal (r4 verdict #7): ONE measured loop of
ImageRecordIter (libmxio C++ decode/augment) -> device feed -> fused
DataParallelStep, reporting train img/s AND the input-stall fraction —
the coupling the reference's ImageRecordIter + executor pipeline provides
(SURVEY §3.6), which per-component benches (bench_io.py, bench.py) can't
see.

    python tools/bench_e2e.py --device cpu       # CPU sanity shapes
    python tools/bench_e2e.py --device tpu --crop 224 --batch-size 256 \
        --model resnet50_v1b --dtype bfloat16    # the real config-2 loop

The step dispatches asynchronously (PjRt), so the host's time splits into
"waiting on the input pipeline" (stall) vs "dispatch + waiting on the
device".  input_stall_pct ~ 0 means the C++ pipeline keeps the chip fed.
Prints one JSON line.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-images", type=int, default=256)
    ap.add_argument("--size", type=int, default=96)
    ap.add_argument("--crop", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--threads", type=int, default=os.cpu_count() or 4)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--num-classes", type=int, default=100)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--device", required=True, choices=["cpu", "tpu"],
                    help="tpu fails when jax shows no accelerator; cpu "
                         "pins the CPU backend")
    args = ap.parse_args()

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, nd, recordio
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import ImageRecordIter
    from mxnet_tpu.io import native as native_mod
    from mxnet_tpu.parallel import DataParallelStep, local_mesh

    if args.device == "cpu":
        mx.context.pin_platform("cpu")
    ctx = mx.cpu() if args.device == "cpu" else mx.tpu()
    mx.context.Context._default_ctx.value = ctx
    mx.random.seed(0)

    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as d:
        rec = os.path.join(d, "bench.rec")
        writer = recordio.MXIndexedRecordIO(os.path.join(d, "bench.idx"),
                                            rec, "w")
        for i in range(args.num_images):
            arr = rng.randint(0, 255, (args.size, args.size, 3), np.uint8)
            header = recordio.IRHeader(0, float(i % args.num_classes), i, 0)
            writer.write_idx(i, recordio.pack_img(header, arr, quality=90))
        writer.close()

        it = ImageRecordIter(
            path_imgrec=rec, data_shape=(3, args.crop, args.crop),
            batch_size=args.batch_size, shuffle=True,
            rand_crop=True, rand_mirror=True, resize=args.size,
            mean_r=123.68, mean_g=116.28, mean_b=103.53,
            std_r=58.395, std_g=57.12, std_b=57.375,
            preprocess_threads=args.threads)

        net = getattr(vision, args.model)(classes=args.num_classes)
        net.initialize(mx.init.Xavier())
        net.cast(args.dtype)
        step = DataParallelStep(
            net, gluon.loss.SoftmaxCrossEntropyLoss(),
            mesh=local_mesh(devices=[ctx.jax_device]), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})

        def feed(batch):
            x = batch.data[0]
            if args.dtype == "bfloat16":
                x = x.astype("bfloat16")
            return step.step(x, batch.label[0])

        # warmup epoch: thread-pool spin-up + the one compile
        loss = None
        for batch in it:
            loss = feed(batch)
        float(np.asarray(loss))

        n, fetch_s, loss = 0, 0.0, None
        t0 = time.perf_counter()
        for _ in range(args.epochs):
            it.reset()
            while True:
                f0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                fetch_s += time.perf_counter() - f0
                loss = feed(batch)
                n += args.batch_size
        final = float(np.asarray(loss))  # drain the async chain
        total = time.perf_counter() - t0
    print(json.dumps({
        "metric": "e2e_recorditer_train_images_per_sec",
        "value": round(n / total, 1), "unit": "images/sec",
        "input_stall_pct": round(100.0 * fetch_s / total, 1),
        "final_loss": round(final, 4),
        "platform": ctx.jax_device.platform,
        "native_io": native_mod.available(),
        "model": args.model, "batch": args.batch_size, "crop": args.crop,
        "threads": args.threads,
    }))


if __name__ == "__main__":
    main()
