"""One run of one cell of BENCHMARK.json, in a new process, on the chips the
cell asks for:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads, warms up every shape the cell's traffic uses (set-up), measures for
``--seconds``, compares what the timed path produced with the plain
reference, and prints one JSON line last on standard output.  It fails
(non-zero, no result) when jax's first device is not a TPU or there are
fewer chips than the cell asks for; it never falls back to the CPU.

``--rehearse 1`` is the benchmark's own CPU switch: the tiny presets of the
configuration and traffic files, for the tests under tests/benchmark/ and a
rehearsal before chip time is spent.  A rehearsal prints its numbers under
``rehearsal`` and carries no metric of BENCHMARK.json.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Clock:
    t_process = T_PROCESS


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_devices(cell, rehearse):
    """The chips of this run, or None when jax has no accelerator or fewer
    chips than the cell asks for."""
    import jax

    devs = jax.devices()
    if rehearse:
        return devs[:cell.chips] if len(devs) >= cell.chips else None
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        return None
    return devs[:cell.chips]


def configure_jax():
    """Every program lands in the persistent cache, the small eager ones too
    (jax's default keeps only compiles over a second).  The directory is the
    package's rule: JAX_COMPILATION_CACHE_DIR where set, else
    <checkout>/.jax_cache, a fixed path inside the checkout."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def run_cell(cell, args, devices):
    """Everything after the look for a chip -> (result dict, stderr lines).
    The tests drive this with the timed path broken underneath."""
    import jax

    import mxnet_tpu as mx

    from benchmark import check, loader, spans

    rec = spans.Recorder()
    rec.compiles = spans.CompileCounter()
    ctx = mx.cpu() if devices[0].platform == "cpu" else mx.tpu(devices[0].id)
    parts = cell.driver().run(cell, args, rec, Clock, devices, ctx)

    correct, rows, lines = check.verdict(parts["numbers"], cell.limits())
    obs, trace = parts["obs"], parts.get("trace")
    obs.update(rec=rec, cell=cell, trace=trace,
               device_kind=devices[0].device_kind,
               compiles_in_window=rec.compiles.between(obs["t0"], obs["t1"]))
    metrics = {}
    if args.trace:
        from benchmark import peaks

        obs["peaks"] = None if args.rehearse else peaks.peaks_for(
            devices[0].device_kind)
        for m in cell.per_layer():
            spec, read = loader.metric_reader(m["name"])
            value = read(obs, spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end():
            metrics[m["name"]] = {"value": parts["e2e"][m["name"]],
                                  "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": parts["memory_peak_bytes"]}
    result = {"correct": bool(correct), "attempted": parts["attempted"],
              "failed": parts["failed"]}
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
    if args.rehearse:
        # a rehearsal carries no metric of BENCHMARK.json under its name
        result["rehearsal"] = {"cpu." + k: v["value"]
                               for k, v in metrics.items()}
        result["metrics"] = {}
    else:
        result["metrics"] = metrics
    result["device"] = device
    if args.trace and trace is not None and trace.get("breakdown"):
        result["breakdown"] = trace["breakdown"]
    result["notes"] = {k: v for k, v in rec.counters.items()
                       if isinstance(v, (int, float))}
    result["notes"]["compiles_in_window"] = obs["compiles_in_window"]
    result["check"] = rows          # last: each number beside its limit
    return result, lines


def main(argv=None):
    args = parse(sys.argv[1:] if argv is None else argv)
    from benchmark import loader

    cell = loader.Cell(loader.load_benchmark(), args.workload,
                       rehearse=bool(args.rehearse))
    configure_jax()
    devices = find_devices(cell, args.rehearse)
    if devices is None:
        print("benchmark: jax found no TPU, or fewer chips than the cell "
              f"asks for ({cell.chips}); nothing was run", file=sys.stderr)
        return 3
    result, lines = run_cell(cell, args, devices)
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
