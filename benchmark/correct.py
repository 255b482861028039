"""The comparison that decides `correct`, and nothing else.  Each number
compared has a limit of its own, kept in the cell's file under `limits`
with the readings it was set from in PERF.md."""

from __future__ import annotations

import statistics


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """{leaf: gap} between the program's norm of a leaf and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger, since some gradients are all but zero."""
    names = [n for n in ref if keep is None or n in keep]
    if set(names) - set(prog):
        raise KeyError(f"program lacks leaves {sorted(set(names) - set(prog))[:5]}")
    med = statistics.median(ref[n] for n in names)
    return {n: abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30) for n in names}


def worst_leaf_gap(prog: dict, ref: dict, keep=None):
    """(largest gap, its leaf)."""
    gaps = leaf_gaps(prog, ref, keep)
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def median_leaf_gap(prog: dict, ref: dict, keep=None):
    """The median leaf's gap: steady from seed to seed where the worst
    leaf is one small leaf's noise."""
    return statistics.median(leaf_gaps(prog, ref, keep).values()), None


def whole_norm_gap(prog: dict, ref: dict):
    """Gap between the norms over all leaves together."""
    tot = lambda d: sum(d[n] ** 2 for n in ref) ** 0.5  # noqa: E731
    return abs(tot(prog) - tot(ref)) / tot(ref), None


def moving_leaves(ref_grad: dict):
    """Leaves whose reference gradient is at least a thousandth of the
    median leaf's.  The others (a key's bias under softmax) have a
    gradient that is nought to rounding, and under Adam move by
    round-off alone: they are left out of the change, by this rule and
    not by name."""
    med = statistics.median(ref_grad.values())
    return {n for n, g in ref_grad.items() if g >= 1e-3 * med}


def training_numbers(prog: dict, ref: dict) -> dict:
    """{short name: (value, leaf or None)} for one training cell: each
    step's loss, the first gradient's norm and the change's norm after
    the last step followed, both by the worst leaf, by the median leaf,
    and the gradient's norm over all leaves together.  Which of them a
    cell holds, its file's `limits` says (null: printed, not held)."""
    out = {}
    for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]), 1):
        out[f"loss_step{i}"] = (abs(p - r) / abs(r), None)
    moving = moving_leaves(ref["grad_norm"])
    out["grad_norm_gap"] = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    out["change_norm_gap"] = worst_leaf_gap(
        prog["change_norm"], ref["change_norm"], keep=moving)
    out["grad_norm_median_gap"] = median_leaf_gap(prog["grad_norm"],
                                                  ref["grad_norm"])
    out["change_norm_median_gap"] = median_leaf_gap(
        prog["change_norm"], ref["change_norm"], keep=moving)
    out["grad_norm_whole_gap"] = whole_norm_gap(prog["grad_norm"],
                                                ref["grad_norm"])
    if "grad_cosine_gap" in ref:
        # 1 - cosine between the program's whole first gradient and the
        # reference's: a norm hides unbiased rounding noise, a direction
        # does not (PERF.md: why fp8 reads like bf16 on every norm)
        out["grad_cosine_gap"] = (ref["grad_cosine_gap"], None)
    return out


def judge(numbers: dict, limits: dict):
    """(correct, compared): `compared` holds each number beside its
    limit.  A number whose limit is null in the cell's file is printed
    and not held (PERF.md names it and says why)."""
    compared, ok_all = {}, True
    for name, (value, leaf) in numbers.items():
        limit = limits.get(name)
        ok = (limit is None) or (value == value and value <= limit)
        ok_all = ok_all and ok
        compared[name] = {"value": value, "limit": limit, "ok": ok}
        if leaf is not None:
            compared[name]["leaf"] = leaf
    missing = set(limits) - set(numbers)
    if missing:
        raise KeyError(f"limits for numbers never compared: {sorted(missing)}")
    return ok_all, compared
