"""The readings the Keye cell's limits are set from, with its own faults,
and how far the program's selection agrees with the reference's:

    python3 benchmark/tools/keye_readings.py --seeds 1,2,3 [--rehearse 1] \
        [--out file]

For each seed, against the plain reference: the control (the reference in
the nearest precision below the configuration's, put in the program's
place) and the two planted faults of the selection, ``dense`` (every
earlier key: the selection ignored) and ``recent`` (the ``topk`` latest
keys); and ``agreement``: the pairs the program's first-layer selection at
the first step shares with the reference's, over the reference's (bf16 moves
scores near each row's threshold).  The program's own readings are those its
benchmark runs print.  Not part of a benchmark run; limits go into
benchmark/checks/<cell>.json by hand, with the readings into PERF.md.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

CELL = "keye_vl_2_0_30b_a3b.train_1x16k"


def flat(numbers):
    return {k: v[0] for k, v in numbers.items()}


def agreement(cell, seed, device, ctx):
    """(shared pairs, the reference's pairs) of the first layer's selection
    at the first step: the program's blocks and kernels in the stored type,
    the reference's float32 and ``lax.top_k``."""
    import jax
    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.models.keye import DSAIndexer
    from mxnet_tpu.ops import dsa_ops

    from benchmark import weights
    from benchmark.drivers import train_job

    cfg, traffic = cell.config, cell.traffic
    ref = cell.reference()
    sa, d = cfg["sa_config"], cfg["hidden_size"]
    tokens = train_job.batch_tokens(seed, cfg["vocab_size"], 1,
                                    traffic["seq_len"])
    w = weights.make_weights(ref.param_spec(cfg), seed, cfg["dtype"], device)
    keep = ("embed_weight", "layer0_attn_norm_gamma") + tuple(
        k for k in w if k.startswith("layer0_indexer_"))
    w = {k: w[k] for k in keep}
    with ctx:
        norm = nn.RMSNorm(epsilon=cfg["rms_norm_eps"], in_channels=d,
                          prefix="attn_norm_")
        block = DSAIndexer(d, sa["indexer_num_heads"],
                           sa["indexer_head_dim"], cfg["rope_theta"],
                           cfg["indexer_rope_fraction"], cfg["rms_norm_eps"],
                           prefix="indexer_")
        for b in (norm, block):
            b.initialize(mx.init.Zero(), ctx=ctx)
            b.cast(cfg["dtype"])
            for name, p in b.collect_params().items():
                p.set_data(nd.array(w["layer0_" + name], ctx=ctx,
                                    dtype=cfg["dtype"]))
        h = nd.array(w["embed_weight"][tokens], ctx=ctx, dtype=cfg["dtype"])
        qi, ki, wt = (x._data for x in block(norm(h)))
    bits, _bits_t = jax.jit(lambda a, b, c: dsa_ops.select(
        a, b, c, sa["topk"]))(qi, ki, wt)
    del qi, ki, wt
    P = {k[len("layer0_"):]: v.astype(jnp.float32) for k, v in w.items()
         if k.startswith("layer0_")}
    h32 = w["embed_weight"].astype(jnp.float32)[jnp.asarray(tokens)]
    u = ref._rms(h32, P["attn_norm_gamma"], cfg["rms_norm_eps"])
    rq, rk, rw = ref.indexer(P, "indexer_", u, cfg)
    n = traffic["seq_len"]
    blk = 1024 if n % 1024 == 0 else n      # query rows a block

    @jax.jit
    def block_counts(i, bits):
        qpos = i * blk + jnp.arange(blk)
        score = ref.index_scores(
            jax.lax.dynamic_slice_in_dim(rq, i * blk, blk, 1), rk,
            jax.lax.dynamic_slice_in_dim(rw, i * blk, blk, 1))
        want = ref.selection(score, qpos, jnp.arange(n), sa["topk"])
        if blk == n:
            got = dsa_ops.unpack(bits)[:, :n, :n]
        else:
            got = dsa_ops.unpack(jax.lax.dynamic_slice_in_dim(
                bits, i * blk // 32, blk // 32, 1))[:, :, :n]
        return jnp.sum(want & got), jnp.sum(want)

    shared = total = 0
    for i in range(n // blk):
        a, b = block_counts(i, bits)
        shared, total = shared + int(a), total + int(b)
    return shared, total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    import run as bench_run  # benchmark/run.py
    from benchmark import check, loader
    from benchmark.drivers import train_job

    cell = loader.Cell(loader.load_benchmark(), CELL,
                       rehearse=bool(a.rehearse))
    bench_run.configure_jax()
    devices = bench_run.find_devices(cell, a.rehearse)
    if devices is None:
        print("no TPU", file=sys.stderr)
        return 3
    import mxnet_tpu as mx

    dev = devices[0]
    ctx = mx.cpu() if dev.platform == "cpu" else mx.tpu(dev.id)
    from benchmark import weights

    rows = []
    for s in [int(s) for s in a.seeds.split(",") if s]:
        t = time.perf_counter()
        row = {"seed": s}
        shared, total = agreement(cell, s, dev, ctx)
        row["agreement"] = {"shared": shared, "reference": total,
                            "share": shared / total}
        ref = train_job.reference_readings(cell, s, dev)
        ctl = train_job.reference_readings(cell, s, dev,
                                           quant=cell.checks["control"])
        row["control_" + cell.checks["control"]] = flat(
            check.training_numbers(ctl, ref))
        cfg, traffic = cell.config, cell.traffic
        tokens = train_job.batch_tokens(s, cfg["vocab_size"],
                                        traffic["batch_per_chip"],
                                        traffic["seq_len"])
        for fault in ("dense", "recent"):
            w = weights.make_weights(cell.reference().param_spec(cfg), s,
                                     cfg["dtype"], dev)
            got = cell.reference().train(
                cfg, w, tokens, s, traffic["check_steps"],
                cell.checks["reference"]["rows_per_block"],
                probe=check.sketch, fault=fault)
            del w
            row["fault_" + fault] = flat(check.training_numbers(got, ref))
        row["seconds"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
        if a.out:
            os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
            with open(a.out, "w") as f:
                json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    sys.exit(main())
