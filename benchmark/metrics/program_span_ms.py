"""Host time of one of the program's own spans per training step of the
traced window, in ms (source: ``mxnet_tpu.telemetry.spans_between``, the
finished spans the program keeps in memory while a profiler session is
live, on the ``perf_counter`` clock ``t_on``/``t_off`` are stamped with).

For every ``train_step`` span lying wholly inside ``[t_on, t_off]`` (one
that straddles either end is left out) the step's value is the duration of
``args["span"]``: the step itself less its direct children named in
``args["less"]`` (its self time with respect to those), or its direct
children of that name summed (0 where it has none).  The metric is
the MEDIAN over the steps, not the mean: the traced window holds about a
dozen steps and opens on a sync, so the in-flight ring is empty and the
first steps do not wait for the device; a mean of ``block_wait`` would
read about 208 ms where every later step waits 245 to 250.

Nothing where the program has no such store (a parent commit) or kept no
``train_step`` span in the window.
"""
import statistics

STEP = "train_step"


def per_step_ms(spans, name, less=()):
    """[(step span, ms)] for the ``train_step`` spans of ``spans``."""
    children = {}
    for s in spans:
        children.setdefault(s.parent, []).append(s)

    def total(step, names):
        return sum(c.t1 - c.t0 for c in children.get(step.span, ())
                   if c.name in names)

    out = []
    for step in (s for s in spans if s.name == STEP):
        if name == STEP:   # the step's self time with respect to ``less``
            sec = step.t1 - step.t0 - total(step, less)
        else:
            sec = total(step, (name,))
        out.append((step, 1e3 * sec))
    return out


def read(obs, args):
    from mxnet_tpu import telemetry

    between = getattr(telemetry, "spans_between", None)
    tr = obs["trace"]
    if between is None or not tr:
        return None
    steps = per_step_ms(between(tr["t_on"], tr["t_off"]), args["span"],
                        args.get("less", ()))
    return statistics.median(ms for _s, ms in steps) if steps else None
