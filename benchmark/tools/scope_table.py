"""Where a cell's step spends its device time, by the program's own scopes,
from ONE traced run of the cell (PERF.md section 5 is made with this):

    python3 benchmark/tools/scope_table.py --workload <cell> --seed <n> \
        [--seconds 20] [--by-instance] [--ops N] [--rehearse 1]
    python3 benchmark/tools/scope_table.py --from <file> [--text <file>] \
        [--by-instance] [--ops N]

Runs the cell as ``benchmark/run.py --trace 1`` does (the result line is
printed too), joins the traced window's ENTRY events with
``telemetry.scope_map()`` as ``metrics/scope_ms_per_step.py`` does, and
prints kind x direction in ms a step, with the rows ``other`` (under a block
that no kind lists: a layer's own norms and residual adds), ``unscoped``
and ``unknown``, what XLA fused across blocks (``mixed``), the join's
coverage, the sum against ``busy_s``, and what the map cost.  ``--by-instance``
gives one row a block instance (which layer is slow); ``--ops N`` the N
costliest ENTRY ops, each with its scope: the op dump a ``model_config`` PR
scores a ``checks/<cell>.json`` pattern against.  Every event with its scope
goes to ``chiprun_out/scope_table/<cell>.json`` and the compiled module's text
to ``<cell>.hlo.txt.gz`` beside it; ``--from`` prints from such a file, with no
chip, and ``--text`` first makes the map again from that text (to try a rule of
``mxnet_tpu/hlo_scopes.py`` on a run already made).
"""
import argparse
import gzip
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(HERE))

DIRS = ("fwd", "remat", "bwd")


def scope_reader():
    from benchmark import loader

    return loader.load_module(
        os.path.join(loader.HERE, "metrics", "scope_ms_per_step.py"),
        "benchmark_metric_scope_ms_per_step")


def events_of(op_seconds, scopes):
    """[[instruction name, seconds, short text, the map's row or None]]."""
    from benchmark import tracing

    return [[name, sec, tracing.short(text, 160), row]
            for (name, sec, row), text in zip(
                scope_reader().joined(op_seconds, scopes), op_seconds)]


def traced_run(args):
    """One traced run -> (result line's dict, the record ``--from`` reads)."""
    import run as bench_run
    from benchmark import loader, tracing
    from mxnet_tpu import hlo_scopes, telemetry

    cell = loader.Cell(loader.load_benchmark(), args.workload,
                       rehearse=bool(args.rehearse))
    bench_run.configure_jax()
    devices = bench_run.find_devices(cell, args.rehearse)
    if devices is None:
        sys.exit("scope_table: jax found no TPU for the cell")
    seen, cost = {}, {"ask_s": 0.0}
    reduced, parse, ask = (tracing.Window.reduced, hlo_scopes.scope_map_of,
                           telemetry.scope_map)

    def keep_trace(window):
        seen["trace"] = reduced(window)
        return seen["trace"]

    def timed_parse(text):
        t = time.perf_counter()
        out = parse(text)
        cost.update(text_bytes=len(text), instructions=len(out),
                    parse_s=time.perf_counter() - t)
        seen["text"] = text
        return out

    def timed_ask():
        t = time.perf_counter()
        out = ask()
        cost["ask_s"] += time.perf_counter() - t   # the first one is the map
        return out

    tracing.Window.reduced = keep_trace
    hlo_scopes.scope_map_of = timed_parse
    telemetry.scope_map = timed_ask
    try:
        run_args = argparse.Namespace(
            workload=args.workload, seed=args.seed, seconds=args.seconds,
            trace=1, rehearse=args.rehearse)
        result, lines = bench_run.run_cell(cell, run_args, devices)
    finally:
        tracing.Window.reduced = reduced
        hlo_scopes.scope_map_of = parse
        telemetry.scope_map = ask
    for line in lines:
        print(line, file=sys.stderr)
    tr = seen["trace"]
    merged = {k: v for m in ask().values() for k, v in m.items()}
    record = {"workload": args.workload, "seed": args.seed, "result": result,
              "steps": tr["steps"], "busy_s": tr["busy_s"],
              "window_s": tr["window_s"], "map_cost": cost,
              "events": events_of(tr["op_seconds"], merged)}
    return result, record, seen.get("text", "")


def render(record, by_instance=False, ops=0, out=sys.stdout):
    scope = scope_reader()
    kinds = scope.kinds()
    steps = record["steps"] or 1
    ms = lambda sec: 1e3 * sec / steps  # noqa: E731
    rows, mixed, inst, entry, known, top = {}, {}, {}, 0.0, 0.0, []
    for name, sec, text, row in record["events"]:
        if row is not None and not row["entry"]:
            continue
        entry += sec
        if row is None:
            kind, direction = "unknown", "fwd"
        else:
            known += sec
            kind, direction = scope.kind_of(row["scope"], kinds), row["dir"]
            other = row["mixed"] and scope.kind_of(row["mixed"], kinds)
            if other and other != kind:
                mixed[kind, other] = mixed.get((kind, other), 0.0) + sec
            if by_instance:     # the path down to the element that decides
                parts, path = row["scope"].split("/"), row["scope"]
                for i, part in enumerate(parts):
                    if scope.kind_of(part, kinds) != "other":
                        path = "/".join(parts[:i + 1])
                        break
                cell = inst.setdefault((kind, path),
                                       dict.fromkeys(DIRS, 0.0))
                cell[direction] += sec
        rows.setdefault(kind, dict.fromkeys(DIRS, 0.0))[direction] += sec
        top.append((sec, name, text, row))
    p = lambda *a: print(*a, file=out)  # noqa: E731
    p(f"# {record['workload']} seed {record['seed']}: {steps} steps, busy "
      f"{ms(record['busy_s']):.2f} ms a step, ENTRY events "
      f"{ms(entry):.2f} ({100 * entry / max(record['busy_s'], 1e-12):.2f}% of "
      f"busy), known to the map {100 * known / max(entry, 1e-12):.3f}%")
    c = record["map_cost"]
    if c.get("text_bytes"):
        p(f"# the map: {c['ask_s']:.2f} s to ask (parse {c['parse_s']:.2f}), "
          f"text {c['text_bytes']:,} bytes, {c['instructions']:,} "
          "instructions")
    p(f"{'kind':<12}{'fwd':>9}{'remat':>9}{'bwd':>9}{'all':>9}{'share':>8}")
    for kind, cell in sorted(rows.items(), key=lambda kv: -sum(
            kv[1].values())):
        tot = sum(cell.values())
        p(f"{kind:<12}" + "".join(f"{ms(cell[d]):9.2f}" for d in DIRS)
          + f"{ms(tot):9.2f}{100 * tot / max(entry, 1e-12):7.1f}%")
    for (kind, other), sec in sorted(mixed.items(), key=lambda kv: -kv[1]):
        p(f"mixed: {ms(sec):8.2f} ms of {kind} holds a product of {other}")
    if by_instance:
        p("")
        for (kind, path), cell in sorted(inst.items(), key=lambda kv: (
                kv[0][0], kv[0][1])):
            p(f"{kind:<10}" + "".join(f"{ms(cell[d]):8.2f}" for d in DIRS)
              + f"{ms(sum(cell.values())):8.2f}  {path}")
    if ops:
        p("")
        for sec, name, text, row in sorted(top, key=lambda r: -r[0])[:ops]:
            where = "?" if row is None else (
                f"{row['dir']} {row['scope'] or '-'}"
                + (f" [mixed {row['mixed']}]" if row["mixed"] else ""))
            p(f"{ms(sec):8.3f}  {text}\n          {where}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    ap.add_argument("--by-instance", action="store_true")
    ap.add_argument("--ops", type=int, default=0)
    ap.add_argument("--from", dest="source")
    ap.add_argument("--text")
    args = ap.parse_args(argv)
    if args.source:
        with open(args.source) as f:
            record = json.load(f)
        if args.text:
            from mxnet_tpu import hlo_scopes

            with gzip.open(args.text, "rt") as f:
                scopes = hlo_scopes.scope_map_of(f.read())
            for event in record["events"]:
                event[3] = scopes.get(event[0])
    else:
        if args.workload is None or args.seed is None:
            ap.error("--workload and --seed, or --from")
        result, record, text = traced_run(args)
        path = os.path.join(ROOT, "chiprun_out", "scope_table",
                            ("cpu." if args.rehearse else "") + args.workload)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".json", "w") as f:
            json.dump(record, f)
        with gzip.open(path + ".hlo.txt.gz", "wt") as f:
            f.write(text)
        print(json.dumps(result), flush=True)
    render(record, args.by_instance, args.ops)
    return 0


if __name__ == "__main__":
    sys.exit(main())
