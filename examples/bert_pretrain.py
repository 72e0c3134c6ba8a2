"""BERT MLM pretraining over a device mesh (reference: the GluonNLP
bert pretraining scripts the reference docs point at; BASELINE target 2).

Single chip:   python examples/bert_pretrain.py --steps 20
Virtual mesh:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
               python examples/bert_pretrain.py --dp 4 --tp 2 --model small
3D (dp/pp/tp): ... bert_pretrain.py --dp 2 --pp 2 --tp 2 --model small
               (pipeline-parallel stacked encoder, models/bert_pp.py)
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.models import bert_base, bert_small
from mxnet_tpu.models.bert import bert_sharding_rules
from mxnet_tpu.parallel import DataParallelStep, make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="small", choices=["small", "base"])
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline stages (uses the stacked pp encoder)")
    ap.add_argument("--pp-microbatches", type=int, default=2)
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
    with mx.cpu() if args.device == "cpu" else mx.tpu():
        train(args)


def train(args):
    import jax

    mx.random.seed(0)
    n_dev = args.dp * args.tp * args.pp
    devices = jax.devices()[:n_dev]
    if len(devices) < n_dev:
        raise SystemExit(f"need {n_dev} devices, have {len(devices)}")
    mesh = make_mesh(tp=args.tp, pp=args.pp, devices=devices)

    if args.pp > 1:
        # pipeline path: the stacked-parameter encoder (models/bert_pp.py)
        from mxnet_tpu.models import bert_pp_small
        from mxnet_tpu.models.bert_pp import (BERTForMLMPipelined,
                                              bert_pp_sharding_rules)

        net = (BERTForMLMPipelined() if args.model == "base"
               else bert_pp_small())
        rules = bert_pp_sharding_rules()
    else:
        net = bert_base() if args.model == "base" else bert_small()
        rules = bert_sharding_rules()
    if args.model != "base":
        args.seq_len = min(args.seq_len, 64)  # small-config max_length
    net.initialize(mx.init.Normal(0.02))
    if args.dtype == "bfloat16":
        net.cast("bfloat16")
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    def mlm_loss(logits, labels):
        return loss_fn(logits.reshape(-1, logits.shape[-1]),
                       labels.reshape(-1))

    step = DataParallelStep(net, mlm_loss, mesh=mesh, optimizer="adam",
                            optimizer_params={"learning_rate": 1e-4},
                            rules=rules,
                            pp_microbatches=args.pp_microbatches)
    V = 30522 if args.model == "base" else 512
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, V, (args.batch_size, args.seq_len)).astype(
        np.int32)
    labels = tokens.astype(np.float32)
    tb = nd.array(tokens, dtype="int32")
    lb = nd.array(labels)

    t0 = time.perf_counter()
    for i in range(args.steps):
        # lazy AsyncLoss: only the logging interval pays a host readback
        loss = step.step(tb, lb)
        if i % 5 == 0:
            v = float(loss)
            dt = time.perf_counter() - t0
            toks = (i + 1) * args.batch_size * args.seq_len
            print(f"step {i}: loss={v:.4f}  {toks / dt:.0f} tok/s")
    step.drain()
    v = float(loss)
    print(f"final mlm loss {v:.4f} on mesh "
          f"dp{args.dp}xpp{args.pp}xtp{args.tp}")
    assert np.isfinite(v)


if __name__ == "__main__":
    main()
