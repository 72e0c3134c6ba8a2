#!/usr/bin/env python3
"""Time megablox's grouped products alone over candidate tilings, beside what
``mxnet_tpu.ops.moe_ops.grouped_cost`` expects of each.

    python3 tools/grouped_tiles.py --m 8192 --k 2048 --n 2048 --groups 16
    python3 tools/grouped_tiles.py --m 4096 --k 3584 --n 1024 --groups 8 --carry 1
    python3 tools/grouped_tiles.py ... --compile-only 1     # no chip: v5e target

One shape a call: ``gmm`` (rows (m, k) times (groups, k, n), and with the
matrices transposed, as the backward pass runs it) and ``tgmm`` (the matrices'
gradient (groups, k, n); with ``--carry 1`` added into an f32 array, as a
loop's chunk does).  ``--sizes`` gives the groups' rows, or ``--skew`` draws
them (largest over mean about ``skew``; 1 is even).  The candidates are the
cheapest ``--top`` by the estimate, the tiling the repo had before PR 36 and
``--also`` ("256,2048,512;...").  A row: kind, tiling, VMEM by
``grouped_vmem``, row tiles visited times tm over m, estimate, measured
milliseconds a call (the median of ``--repeat`` timed batches; the group
metadata's small XLA ops are in it, about 0.04 ms by the one tiling whose time
inside a step is known), or why the compiler refused.  A measured table also goes to
``chiprun_out/grouped_tiles/<m>x<k>x<n>x<groups>[c].json``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from mxnet_tpu.ops import moe_ops

BEFORE = (512, 1024, 1024)      # every shape's tiling up to PR 35


def draw_sizes(m, groups, skew, seed):
    """``groups`` sizes that sum to ``m``, the largest about ``skew`` times
    the mean, none on a tile's edge but by chance."""
    share = np.full(groups, 1 / groups)
    if skew > 1:    # a draw, stretched towards the wanted largest share
        share = np.random.default_rng(seed).dirichlet(np.ones(groups))
        share = share ** (np.log(skew) / np.log(share.max() * groups))
        share = share / share.sum()
    sizes = np.floor(share * m).astype(np.int64)
    sizes[-1] += m - sizes.sum()
    return sizes.astype(np.int32)


def candidates(kind, args, carry):
    m, k, n, groups = args.m, args.k, args.n, args.groups
    every = moe_ops.grouped_candidates(kind, m, k, n, 2, carry,
                                       args.vmem << 20)
    every.sort(key=lambda t: moe_ops.grouped_cost(kind, t, m, k, n, groups, 2,
                                                  carry))
    picked = every[:args.top]
    before = (BEFORE[0], BEFORE[1], BEFORE[2] // 2) if carry else BEFORE
    for extra in [before] + [tuple(int(x) for x in t.split(","))
                             for t in args.also.split(";") if t]:
        if extra not in picked and m % extra[0] == 0:
            picked.append(extra)
    return picked


def calls(kind, carry, tiling, args):
    """(function of the operands, operands) of one product at ``tiling``."""
    m, k, n, groups = args.m, args.k, args.n, args.groups
    key = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    bf16 = jnp.bfloat16
    lhs = jax.random.normal(key[0], (m, k), bf16)
    if kind in ("gmm", "gmmT"):
        shape = (groups, n, k) if kind == "gmmT" else (groups, k, n)
        rhs = jax.random.normal(key[1], shape, bf16)
        fn = lambda lhs, rhs, sizes: gmm(
            lhs, rhs, sizes, bf16, tiling, transpose_rhs=kind == "gmmT")
        return fn, (lhs, rhs)
    d_out = jax.random.normal(key[1], (m, n), bf16)
    if not carry:
        fn = lambda lhs, d_out, sizes: tgmm(
            lhs.swapaxes(0, 1), d_out, sizes, bf16, tiling)
        return fn, (lhs, d_out)
    acc = jax.random.normal(key[2], (groups, k, n), jnp.float32)
    fn = lambda lhs, d_out, acc, sizes: tgmm(
        lhs.swapaxes(0, 1), d_out, sizes, jnp.float32, tiling,
        existing_out=acc)
    return fn, (lhs, d_out, acc)


@functools.lru_cache(maxsize=None)
def v5e_device():
    """One chip of a v5e host that is described, not attached."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]


def measure(fn, operands, sizes, args, carry=False):
    # a carry is the last operand: given away and taken back call after
    # call, as a loop's is (kept, it would be copied before every call)
    jitted = jax.jit(fn, donate_argnums=(len(operands) - 1,) if carry else ())
    if args.compile_only:
        one_chip = jax.sharding.SingleDeviceSharding(v5e_device())
        jitted.lower(*(
            jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in (*operands, sizes))).compile()
        return None

    def call(operands):
        out = jitted(*operands, sizes)
        return out, (*operands[:-1], out) if carry else operands

    out, operands = call(operands)
    jax.block_until_ready(out)
    times = []
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        for _ in range(args.batch):
            out, operands = call(operands)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - t0) / args.batch)
    return statistics.median(times) * 1e3


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    for name in ("m", "k", "n", "groups"):
        ap.add_argument(f"--{name}", type=int, required=True)
    ap.add_argument("--carry", type=int, default=0)
    ap.add_argument("--sizes", default="")
    ap.add_argument("--skew", type=float, default=3.6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--top", type=int, default=5)
    ap.add_argument("--also", default="")
    ap.add_argument("--kinds", default="gmm,gmmT,tgmm")
    ap.add_argument("--vmem", type=int, default=moe_ops.VMEM_BYTES >> 20,
                    help="MiB a candidate may count")
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--batch", type=int, default=20)
    ap.add_argument("--compile-only", type=int, default=0)
    args = ap.parse_args()

    m, k, n, groups = args.m, args.k, args.n, args.groups
    sizes = (np.asarray([int(s) for s in args.sizes.split(",")], np.int32)
             if args.sizes else draw_sizes(m, groups, args.skew, args.seed))
    assert sizes.shape == (groups,) and sizes.sum() == m, sizes
    print(f"# m {m} k {k} n {n} groups {groups} carry {args.carry} sizes "
          f"{sizes.tolist()} (largest over mean "
          f"{sizes.max() * groups / m:.2f}); least at the MXU's pace "
          f"{2e3 * m * k * n / moe_ops.MXU_FLOPS:.3f} ms")
    rows = []
    print(f"{'kind':6} {'tiling':>18} {'vmem MB':>8} {'rows run':>8} "
          f"{'est ms':>7} {'ms':>7}")
    for kind in args.kinds.split(","):
        carry = bool(args.carry) and kind == "tgmm"
        base = "gmm" if kind.startswith("gmm") else "tgmm"
        chosen = moe_ops.grouped_tiling(base, m, k, n, groups, 2, carry)
        for tiling in candidates(base, args, carry):
            fn, operands = calls(kind, carry, tiling, args)
            row = {"kind": kind, "carry": carry, "tiling": list(tiling),
                   "chosen": tiling == chosen,
                   "vmem_mb": moe_ops.grouped_vmem(base, tiling, 2, carry)
                   / 2 ** 20,
                   "rows_run_over_rows": moe_ops.row_tiles_visited(
                       base, sizes, tiling[0]) * tiling[0] / m,
                   "est_ms": 1e3 * moe_ops.grouped_cost(
                       base, tiling, m, k, n, groups, 2, carry)}
            try:
                row["ms"] = measure(fn, operands, jnp.asarray(sizes), args,
                                    carry)
            except Exception as e:      # the compiler's refusal is a reading
                row["refused"] = f"{type(e).__name__}: {str(e)[:300]}"
            rows.append(row)
            got = ("refused: " + row["refused"][:90] if "refused" in row
                   else "compiled" if row["ms"] is None
                   else f"{row['ms']:7.3f}")
            print(f"{kind + ('+' if carry else ''):6} {str(tiling):>18} "
                  f"{row['vmem_mb']:8.1f} {row['rows_run_over_rows']:8.2f} "
                  f"{row['est_ms']:7.3f} {got}"
                  f"{'  <- chosen' if row['chosen'] else ''}", flush=True)
    if args.compile_only:
        return
    out = os.path.join("chiprun_out", "grouped_tiles")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(
            out, f"{m}x{k}x{n}x{groups}{'c' if args.carry else ''}.json"),
            "w") as f:
        json.dump({"args": vars(args), "sizes": sizes.tolist(),
                   "device": str(jax.devices()[0].device_kind), "rows": rows},
                  f, indent=1)


if __name__ == "__main__":
    main()
