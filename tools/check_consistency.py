"""CPU-vs-TPU consistency oracle over the full op sweep.

Replays every tests/test_op_sweep.py case on ``mx.cpu()`` and on ``mx.tpu()``
in ONE process (a TPU process has both backends) and compares forwards and
tape gradients: the TPU-native analog of the reference's check_consistency
harness (tests/python/gpu/test_operator_gpu.py ~L1300), which re-runs the
whole op surface across device/dtype combos.

    python tools/check_consistency.py [--limit N] [--filter SUBSTR]
                                      [--out chiprun_out/CONSISTENCY.json]

Without a chip ``mx.tpu()`` raises and the exit code is non-zero; nothing is
ever reported as skipped.  Exit 0 needs every case compared, no case beyond
the tolerances below and no error on either side.

Tolerances.  An f32 ``dot``/convolution on the TPU runs at jax's DEFAULT
matmul precision: the MXU multiplies in one bf16 pass (8 mantissa bits per
operand, f32 accumulation), so a contraction agrees with the host's f32
result to about 2^-8 per product, not to f32 round-off.  Everything else
(elementwise, reductions, transcendentals) is f32 on both sides.  A case
therefore passes at ``F32_TOL``, or is listed under ``bf16_pass_only`` when
it needs ``MXU_TOL``, or is a mismatch.  Gradients get ``GRAD_FACTOR`` times
either bound (a gradient through a contraction is another contraction).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(_REPO, "tests"), _REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# (rtol, atol); atol scales with the reference's largest magnitude
F32_TOL = (2e-3, 2e-4)
MXU_TOL = (2e-2, 2e-2)
GRAD_FACTOR = 2.5


def eval_case(sweep, case, ctx):
    """Deterministic forward (+ tape gradient) of one sweep case on ctx.

    Returns (list_of_forward_arrays, list_of_grad_arrays_or_None).  Inputs
    are seeded identically for every context; gradients go through the
    autograd tape (jax.vjp), i.e. the exact path training uses."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, nd

    mx.random.seed(0)
    arrs = sweep._inputs_np(case, np.random.RandomState(11))
    with ctx:
        out = case.fn(*[nd.array(a, ctx=ctx) for a in arrs])
        outs = out if isinstance(out, (list, tuple)) else [out]
        fwd = [np.asarray(o.asnumpy(), dtype=np.float64) for o in outs]
        if not case.grad:
            return fwd, None
        inputs = [nd.array(a, ctx=ctx) for a in arrs]
        for i, x in enumerate(inputs):
            if i not in case.int_inputs:
                x.attach_grad()
        with autograd.record():
            loss = sweep._sum_all(case.fn(*inputs))
        loss.backward()
        grads = [
            (None if i in case.int_inputs or x.grad is None
             else np.asarray(x.grad.asnumpy(), dtype=np.float64))
            for i, x in enumerate(inputs)]
    return fwd, grads


def _worst(got, want, tol, factor=1.0):
    """None when every array pair is within ``tol``; else a message for the
    first pair that is not."""
    rtol, atol = tol[0] * factor, tol[1] * factor
    for k, (a, b) in enumerate(zip(got, want)):
        if a is None or b is None:
            continue
        if a.shape != b.shape:
            return f"[{k}]: shape {a.shape} vs {b.shape}"
        scale = max(1.0, float(np.abs(b).max())) if b.size else 1.0
        if not np.allclose(a, b, rtol=rtol, atol=atol * scale,
                           equal_nan=True):
            return (f"[{k}]: max|Δ|={float(np.abs(a - b).max()):.3g} "
                    f"at rtol={rtol:g} atol={atol * scale:g}")
    return None


def compare(tpu, cpu):
    """('f32' | 'bf16_pass' | 'mismatch', message) for one case's (fwd,
    grads) pairs."""
    msg = None
    for name, tol in (("f32", F32_TOL), ("bf16_pass", MXU_TOL)):
        msg = _worst(tpu[0], cpu[0], tol)
        if msg is not None:
            msg = "fwd" + msg
        elif tpu[1] is not None and cpu[1] is not None:
            msg = _worst(tpu[1], cpu[1], tol, GRAD_FACTOR)
            if msg is not None:
                msg = "grad" + msg
        if msg is None:
            return name, None
    return "mismatch", msg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--limit", type=int, default=0, help="first N cases only")
    ap.add_argument("--filter", default="", help="substring filter on case id")
    ap.add_argument("--out", default=os.path.join(_REPO, "chiprun_out",
                                                  "CONSISTENCY.json"))
    args = ap.parse_args()

    import mxnet_tpu as mx

    tpu, cpu = mx.tpu(), mx.cpu()
    dev = tpu.jax_device  # raises here without a chip
    import test_op_sweep as sweep

    cases = [c for c in sweep.CASES if args.filter in c.id]
    if args.limit:
        cases = cases[:args.limit]

    t0 = time.perf_counter()
    compared, bf16_pass_only, mismatches, errors = 0, [], [], {}
    for n, case in enumerate(cases, 1):
        try:
            got = eval_case(sweep, case, tpu)
            want = eval_case(sweep, case, cpu)
        except Exception as e:  # record and keep sweeping; counted below
            errors[case.id] = f"{type(e).__name__}: {e}"[:400]
            continue
        verdict, msg = compare(got, want)
        compared += 1
        if verdict == "bf16_pass":
            bf16_pass_only.append(case.id)
        elif verdict == "mismatch":
            mismatches.append(f"{case.id} {msg}")
        if n % 25 == 0:
            print(f"{n}/{len(cases)} cases "
                  f"({time.perf_counter() - t0:.0f}s)", flush=True)

    report = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "cases_total": len(cases),
        "cases_compared": compared,
        "bf16_pass_only": bf16_pass_only,
        "mismatches": mismatches,
        "errors": errors,
        "f32_tol": F32_TOL, "mxu_tol": MXU_TOL, "grad_factor": GRAD_FACTOR,
        "elapsed_s": round(time.perf_counter() - t0, 1),
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    for m in mismatches:
        print("mismatch", m)
    for cid, err in errors.items():
        print("error", cid, err)
    counted = ("bf16_pass_only", "mismatches", "errors")
    print(json.dumps({k: (len(v) if k in counted else v)
                      for k, v in report.items()}))
    ok = compared == len(cases) > 0 and not mismatches and not errors
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
