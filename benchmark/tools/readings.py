"""The readings a training cell's limits are set from, many seeds in one
process (set-up is long, so the program's dozen seeds and the control's are
read together):

    python3 benchmark/tools/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--rehearse 1] [--out file]

For each seed: the program's numbers against the plain reference (the lower
reading).  For each control seed also: the control (the reference computed
in the nearest precision below the configuration's, put in the program's
place) and the planted fault "half of the batch left out, the mean taken
over the rest" (the upper readings).  Not part of a benchmark run; limits
go into benchmark/checks/<cell>.json by hand, with the readings into
PERF.md.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def flat(numbers):
    return {k: v[0] for k, v in numbers.items()}


def train_seed(cell, seed, control, devices, ctx, program=True):
    import numpy as np

    from benchmark import check
    from benchmark.drivers import train_job

    row = {"seed": seed}
    if program:
        job = train_job.Job(cell, seed, ctx, devices)
        got = job.first_steps(cell.traffic["check_steps"])
        job.close()
        job.free()
    ref = train_job.reference_readings(cell, seed, devices[0])
    if program:
        nums = check.training_numbers(got, ref)
        row["program"] = flat(nums)
        row["leaves"] = {k: v[1] for k, v in nums.items()
                         if not k.startswith("loss")}
    if control:
        mode = cell.checks["control"]
        ctl = train_job.reference_readings(cell, seed, devices[0],
                                           quant=mode)
        row["control_" + mode] = flat(check.training_numbers(ctl, ref))
        batch = cell.traffic["batch_per_chip"] * cell.chips
        half = train_job.reference_readings(cell, seed, devices[0],
                                            rows=np.arange(batch // 2))
        row["fault_half_batch"] = flat(check.training_numbers(half, ref))
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--program", type=int, default=1,
                    help="0: a training cell's control and fault alone "
                    "(they need no run of the program)")
    ap.add_argument("--rehearse", type=int, default=0)
    ap.add_argument("--out", default="")
    a = ap.parse_args()
    import run as bench_run  # benchmark/run.py
    from benchmark import loader

    cell = loader.Cell(loader.load_benchmark(), a.workload,
                       rehearse=bool(a.rehearse))
    bench_run.configure_jax()
    devices = bench_run.find_devices(cell, a.rehearse)
    if devices is None:
        print("no TPU", file=sys.stderr)
        return 3
    import mxnet_tpu as mx

    ctx = mx.cpu() if devices[0].platform == "cpu" else mx.tpu(devices[0].id)
    control = {int(s) for s in a.control_seeds.split(",") if s}
    rows = []
    for s in [int(s) for s in a.seeds.split(",") if s]:
        t = time.perf_counter()
        row = train_seed(cell, s, s in control, devices, ctx,
                         bool(a.program))
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
