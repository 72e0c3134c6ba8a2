"""The comparison that decides ``correct``: numbers, each beside its limit.

Training compares the program's first steps with the plain reference's:
each step's loss, the norm of the first gradient as the optimizer got it
and the norm of the parameters' change after the last step, the last two
by the worst leaf: the gap between the two norms (not the norm of the
difference) against the reference's norm of that leaf or of the median
leaf, whichever is larger.  Norms alone cannot tell bfloat16 from 8-bit
floats (rounding noise lies across the gradient and lengthens it by the
square of its share), so the first gradient is also compared by direction:
``sketch`` projects every leaf on the same few seeded sign vectors on both
sides, and the share of the reference's projections that the program's
miss estimates the norm of the difference over the reference's norm, leaf
by leaf; the median leaf's is the number.
"""
import statistics

SKETCH_K = 16


def sketch(tree, k=SKETCH_K):
    """name -> (k,) float32 projections of each leaf on k sign vectors drawn
    from a fixed key and the leaf's place in the sorted names (the same on
    the program's side and the reference's).  One jitted call, on the
    device the leaves live on."""
    import jax
    import jax.numpy as jnp

    def run(t):
        key = jax.random.PRNGKey(20230923)
        out = {}
        for i, name in enumerate(sorted(t)):
            v = t[name].astype(jnp.float32).reshape(-1)

            def proj(j, v=v, i=i):
                kk = jax.random.fold_in(jax.random.fold_in(key, i), j)
                sign = jax.random.bernoulli(kk, 0.5, v.shape)
                return jnp.sum(jnp.where(sign, v, -v))

            out[name] = jax.lax.map(proj, jnp.arange(k))
        return out

    return jax.jit(run)(tree)


def sketch_gap(got, ref, leaves):
    """(median over ``leaves`` of |got - ref| / |ref| in the sketch's
    coordinates, the leaf that reads the median)."""
    rows = []
    for name in leaves:
        a, b = got[name], ref[name]
        den = sum(float(x) ** 2 for x in b)
        num = sum((float(x) - float(y)) ** 2 for x, y in zip(a, b))
        rows.append(((num / den) ** 0.5 if den > 0 else 0.0, name))
    rows.sort()
    return rows[len(rows) // 2] if rows else (0.0, None)


def worst_leaf_gap(got, ref, leaves=None):
    """(gap, leaf) over ``leaves`` (all of ``ref`` when None)."""
    leaves = list(ref) if leaves is None else list(leaves)
    if set(got) != set(ref):
        raise ValueError("the program's leaves and the reference's differ")
    med = statistics.median(ref[k] for k in leaves) if leaves else 0.0
    worst, at = 0.0, None
    for k in leaves:
        scale = max(ref[k], med)
        gap = abs(got[k] - ref[k]) / scale if scale > 0 else abs(got[k])
        if gap > worst or at is None:
            worst, at = gap, k
    return worst, at


def moving_leaves(ref_grad_norm):
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's.  The others (a key's bias under
    softmax, leaves no loss reaches) move under Adam by round-off alone and
    are left out of the parameters' change."""
    med = statistics.median(ref_grad_norm.values())
    return [k for k, v in ref_grad_norm.items() if v >= 1e-3 * med]


def training_numbers(got, ref):
    """name -> (value, detail) for the readings of ``train`` (reference) and
    of the program: {"loss": [..], "grad_norm": {..}, "delta_norm": {..},
    "grad_sketch": {..}}."""
    out = {}
    for i, (a, b) in enumerate(zip(got["loss"], ref["loss"])):
        out[f"loss_step{i + 1}_rel"] = (abs(a - b) / abs(b), f"{a!r} vs {b!r}")
    if len(got["loss"]) != len(ref["loss"]):
        raise ValueError("the program and the reference ran other steps")
    g, at = worst_leaf_gap(got["grad_norm"], ref["grad_norm"])
    out["grad_norm_gap"] = (g, at)
    moving = moving_leaves(ref["grad_norm"])
    d, at = worst_leaf_gap(got["delta_norm"], ref["delta_norm"], moving)
    out["delta_norm_gap"] = (d, at)
    out["grad_sketch_gap"] = sketch_gap(got["grad_sketch"],
                                        ref["grad_sketch"], moving)
    return out


def verdict(numbers, limits):
    """(correct, {name: {"value", "limit"}}, lines).  Every number a limit
    names has to be there and finite and at or under its limit; a number with
    no limit is printed and not held (``PERF.md`` says which and why)."""
    rows, lines, ok = {}, [], True
    for name, limit in limits.items():
        if name not in numbers:
            ok = False
            lines.append(f"check {name}: MISSING (limit {limit})")
            continue
        value, detail = numbers[name]
        good = value == value and value <= limit
        ok = ok and good
        rows[name] = {"value": value, "limit": limit}
        lines.append(f"check {name}: {value!r} limit {limit!r} "
                     f"{'ok' if good else 'FAIL'} ({detail})")
    for name, (value, detail) in numbers.items():
        if name not in limits:
            rows[name] = {"value": value, "limit": None}
            lines.append(f"check {name}: {value!r} not held ({detail})")
    return ok, rows, lines
