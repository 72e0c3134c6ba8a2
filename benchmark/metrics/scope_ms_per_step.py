"""Device time of one KIND of the model's blocks per step of the traced
window, in ms, found by the program's own scopes and not by shapes.

The join: an event of the trace's ``XLA Ops`` line is named by its HLO
instruction's text, whose leading ``%name`` is the key of
``mxnet_tpu.telemetry.scope_map()`` (``DataParallelStep.scope_map``: the
scope path each instruction of the compiled step lies under, its pass
``fwd`` / ``remat`` / ``bwd``, whether it is in the ENTRY computation, what
XLA fused into it from another block).  Only ENTRY events are summed: a
``while`` there is one event that spans its body, and the body's
instructions are events too.

A kind is data, ``metrics/<metric>.json`` ``args``: ``kind`` (its name),
``classes`` (Gluon block classes) and ``scopes`` (``mx_*`` names).  Every
file that names this reader and a ``kind`` is one row of the table; ``rows``
(in ``recompute_ms.train.json``, the metric that reads every kind) names
the rows no metric of their own reads.  An event's kind is that of the
OUTERMOST listed element of its scope path (a shared expert is a
``GatedMLP`` inside ``XingMoE``: with the experts); ``other`` where the path
lists none, ``unscoped`` where the path is empty, ``unknown`` where the map
has no such instruction.  A metric sums its ``kind`` over ``dirs`` (all
three where it names none); without ``kind`` it sums every scoped kind.

Nothing where the program hands out no map (a parent commit) or the trace
holds no whole step.
"""
import glob
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DIRS = ("fwd", "remat", "bwd")
READER = "scope_ms_per_step"


def kinds(metrics_dir=HERE):
    """{kind: {"classes": set, "scopes": set}} from the metric files."""
    out = {}
    for path in sorted(glob.glob(os.path.join(metrics_dir, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("reader") != READER:
            continue
        args = spec.get("args", {})
        rows = dict(args.get("rows", {}))
        if "kind" in args:
            rows[args["kind"]] = args
        for kind, row in rows.items():
            out[kind] = {"classes": set(row.get("classes", ())),
                         "scopes": set(row.get("scopes", ()))}
    return out


def kind_of(scope, table):
    """The kind of one scope path: its outermost listed element's."""
    if not scope:
        return "unscoped"
    for part in scope.split("/"):
        cls = part.split(".", 1)[0] if "." in part else None
        for kind, row in table.items():
            if part in row["scopes"] or cls in row["classes"]:
                return kind
    return "other"


def joined(op_seconds, scope_map):
    """[(instruction name, seconds, the map's row or None)] for every event
    name of the trace."""
    names = ((text.split(" ", 1)[0].lstrip("%"), sec)
             for text, sec in op_seconds.items())
    return [(name, sec, scope_map.get(name)) for name, sec in names]


def table(op_seconds, scope_map, kind_table):
    """{kind: {dir: seconds}} over the ENTRY events, with ``unknown`` for
    events the map has no instruction for, and ``entry_s``, their sum."""
    out, total = {}, 0.0
    for _name, sec, row in joined(op_seconds, scope_map):
        if row is None:
            kind, direction = "unknown", "fwd"
        elif not row["entry"]:
            continue
        else:
            kind, direction = kind_of(row["scope"], kind_table), row["dir"]
        cell = out.setdefault(kind, dict.fromkeys(DIRS, 0.0))
        cell[direction] += sec
        total += sec
    out["entry_s"] = total
    return out


def cell_table(obs):
    """The run's table, made once a run; None without a map or a step."""
    if "scope_table" not in obs:
        from mxnet_tpu import telemetry

        ask = getattr(telemetry, "scope_map", None)
        tr = obs["trace"]
        maps = ask() if ask is not None and tr and tr["steps"] else None
        merged = {k: v for m in (maps or {}).values() for k, v in m.items()}
        obs["scope_table"] = table(tr["op_seconds"], merged,
                                   kinds()) if merged else None
    return obs["scope_table"]


def read(obs, args):
    tab = cell_table(obs)
    if tab is None:
        return None
    dirs = args.get("dirs", DIRS)
    if "kind" in args:
        rows = [tab.get(args["kind"], {})]
    else:
        rows = [v for k, v in tab.items()
                if k not in ("unscoped", "unknown", "entry_s")]
    secs = sum(row.get(d, 0.0) for row in rows for d in dirs)
    return 1e3 * secs / obs["trace"]["steps"] if secs else None
