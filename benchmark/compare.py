"""The comparison that decides `correct`: each number compared beside its
limit. Training cells compare the program's first three steps with the plain
reference's (see `training`); serving cells the served tokens' logit gap.
"""
import statistics


def leaf_gaps(prog, ref, skip=()):
    """{leaf: gap} between the program's norm and the reference's, measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some norms are all but zero)."""
    names = [k for k in ref if k not in skip]
    floor = statistics.median(ref[k] for k in names)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
            for k in names}


def worst_and_median(gaps):
    """(widest gap, its leaf, the median leaf's gap)."""
    where = max(gaps, key=gaps.get)
    return gaps[where], where, statistics.median(gaps.values())


def still_leaves(ref_grad_norm):
    """Leaves whose gradient is nought to rounding in the reference (under a
    thousandth of the median leaf's): they move by round-off alone, and are
    left out of the parameters' change."""
    med = statistics.median(ref_grad_norm.values())
    return {k for k, v in ref_grad_norm.items() if v < 1e-3 * med}


def training(prog, ref, cos_gap=None):
    """{name: value} of the numbers a training cell can compare (its file
    under cells/ holds a limit for those it does). `prog` and `ref` hold
    `loss` (per step), `grad_norm` and `delta_norm` (per leaf). The worst
    leaf is the contract's number; the median leaf's is the steady one for a
    configuration whose small leaves are noise in its stated precision.
    `cos_gap` is each leaf's 1 - cosine between the two sides' first
    gradients: where a gap of norms cannot part the stated precision from
    the one below (PERF.md, PR 24), the direction does. Leaves whose
    gradient is nought to rounding have no direction and are left out."""
    out = {}
    for i, (a, b) in enumerate(zip(prog["loss"], ref["loss"])):
        out["loss_step%d" % (i + 1)] = abs(a - b) / abs(b)
    out["grad_norm_worst_leaf"], gl, out["grad_norm_median_leaf"] = \
        worst_and_median(leaf_gaps(prog["grad_norm"], ref["grad_norm"]))
    out["param_change_worst_leaf"], dl, out["param_change_median_leaf"] = \
        worst_and_median(leaf_gaps(prog["delta_norm"], ref["delta_norm"],
                                   skip=still_leaves(ref["grad_norm"])))
    where = {"grad_norm_worst_leaf": gl, "param_change_worst_leaf": dl}
    if cos_gap:
        still = still_leaves(ref["grad_norm"])
        out["grad_cos_gap_worst_leaf"], cl, out["grad_cos_gap_median_leaf"] = \
            worst_and_median({k: v for k, v in cos_gap.items()
                              if k not in still})
        where["grad_cos_gap_worst_leaf"] = cl
    return out, where


def judge(values, limits):
    """[(name, value, limit)] for every limit of the cell, and whether all
    hold. A number that is missing or not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        v = values.get(name)
        good = v is not None and v == v and v <= limit
        ok = ok and good
        rows.append((name, v, limit))
    return rows, ok
