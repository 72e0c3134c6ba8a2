"""Transformer machine-translation training (BASELINE config 4 skeleton;
reference: GluonNLP scripts/machine_translation train_transformer.py).

Runs the encoder-decoder Transformer with label-smoothed CE through the
fused multi-input DataParallelStep — forward, backward, optimizer and the
tied-embedding softmax compile to ONE XLA program per step.  With no WMT
corpus in the sandbox (zero egress) the default data is a synthetic
copy/reverse corpus; point --src/--tgt at token-id files (one
space-separated sentence per line) for real data.

  python examples/train_wmt.py --model base --steps 30
  python examples/train_wmt.py --model big --dp 8   # pod recipe shape
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models.transformer import (Transformer, label_smoothed_ce,
                                          transformer_base, transformer_big)
from mxnet_tpu.parallel import DataParallelStep, make_mesh

PAD, BOS, EOS = 0, 1, 2


def synthetic_batch(rng, batch, src_len, vocab):
    src = rng.randint(3, vocab, (batch, src_len)).astype(np.int32)
    tgt_in = np.zeros((batch, src_len + 2), np.int32)
    tgt_out = np.zeros((batch, src_len + 2), np.int32)
    rev = src[:, ::-1]
    tgt_in[:, 0] = BOS
    tgt_in[:, 1:src_len + 1] = rev
    tgt_out[:, :src_len] = rev
    tgt_out[:, src_len] = EOS
    return src, tgt_in, tgt_out


def load_parallel_corpus(src_path, tgt_path, max_len, batch):
    """Token-id files (one space-separated sentence per line) -> one
    padded (src, tgt_in, tgt_out) batch of the first `batch` pairs."""
    def read(path):
        rows = []
        with open(path) as f:
            for line in f:
                toks = [int(t) for t in line.split()][:max_len]
                if toks:
                    rows.append(toks)
        return rows

    s_rows, t_rows = read(src_path), read(tgt_path)
    if len(s_rows) != len(t_rows):
        raise SystemExit(f"corpus length mismatch: {len(s_rows)} src vs "
                         f"{len(t_rows)} tgt sentences")
    n = min(batch, len(s_rows))
    Ls = max(len(r) for r in s_rows[:n])
    Lt = max(len(r) for r in t_rows[:n]) + 2
    src = np.full((n, Ls), PAD, np.int32)
    tgt_in = np.full((n, Lt), PAD, np.int32)
    tgt_out = np.full((n, Lt), PAD, np.int32)
    for i in range(n):
        src[i, :len(s_rows[i])] = s_rows[i]
        tgt_in[i, 0] = BOS
        tgt_in[i, 1:len(t_rows[i]) + 1] = t_rows[i]
        tgt_out[i, :len(t_rows[i])] = t_rows[i]
        tgt_out[i, len(t_rows[i])] = EOS
    return src, tgt_in, tgt_out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="base", choices=["base", "big", "tiny"])
    ap.add_argument("--src", default=None, help="source token-id file")
    ap.add_argument("--tgt", default=None, help="target token-id file")
    ap.add_argument("--vocab-size", type=int, default=32000)
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--src-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoothing", type=float, default=0.1)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--sp", type=int, default=1)
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
    n_dev = args.dp * args.sp
    devices = jax.devices()[:n_dev]
    if len(devices) < n_dev:
        raise SystemExit(f"need {n_dev} devices, have {len(devices)}")
    mesh = make_mesh(sp=args.sp, devices=devices)

    if args.model == "tiny":
        net = Transformer(args.vocab_size, units=64, hidden_size=128,
                          num_heads=4, num_layers=2, dropout=0.1)
    elif args.model == "base":
        net = transformer_base(args.vocab_size)
    else:
        net = transformer_big(args.vocab_size)
    net.initialize(mx.init.Xavier())
    if args.dtype == "bfloat16":
        net.cast("bfloat16")

    step = DataParallelStep(
        net,
        lambda logits, labels: label_smoothed_ce(logits, labels,
                                                 smoothing=args.smoothing),
        mesh=mesh, optimizer="adam",
        optimizer_params={"learning_rate": args.lr})

    rng = np.random.RandomState(0)
    if args.src and args.tgt:
        src, tgt_in, tgt_out = load_parallel_corpus(
            args.src, args.tgt, args.src_len, args.batch_size)
    else:
        src, tgt_in, tgt_out = synthetic_batch(rng, args.batch_size,
                                               args.src_len, args.vocab_size)
    sb = nd.array(src, dtype="int32")
    tb = nd.array(tgt_in, dtype="int32")
    lb = nd.array(tgt_out.astype(np.float32))

    tokens_per_step = int((tgt_out != PAD).sum())
    t0 = time.perf_counter()
    for i in range(args.steps):
        # lazy AsyncLoss: forced at step 0 (compile split) and at the end
        loss = step.step((sb, tb), lb)
        if i == 0:
            val = float(loss)
            print(f"step 0: loss={val:.4f} (compile "
                  f"{time.perf_counter() - t0:.1f}s)", flush=True)
            t0 = time.perf_counter()
    step.drain()
    val = float(loss)
    dt = time.perf_counter() - t0
    rate = tokens_per_step * max(args.steps - 1, 1) / dt
    print(f"final loss {val:.4f}  {rate:.0f} tok/s")


if __name__ == "__main__":
    main()
