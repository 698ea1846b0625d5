"""The comparison that decides ``correct``: what the timed ``fit`` call
produced in its first steps against the plain reference's first steps."""

from __future__ import annotations

import math
import statistics


def leaf_gaps(got: dict, want: dict, *, skip=()) -> dict:
    """``|got - want|`` of every leaf, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but nought)."""
    if set(got) != set(want):
        raise ValueError(
            f"leaves differ: {sorted(set(got) ^ set(want))[:6]}"
        )
    names = [n for n in want if n not in skip]
    median = statistics.median(want[n] for n in names)
    gaps = {n: abs(got[n] - want[n]) / max(want[n], median) for n in names}
    return {n: g if math.isfinite(g) else float("inf")
            for n, g in gaps.items()}


def worst(gaps: dict) -> tuple[float, str]:
    where = max(gaps, key=gaps.get)
    return gaps[where], where


def dead_leaves(reference: dict, *, whole: float = 1e-3,
                share: float = 0.01) -> set:
    """Leaves whose gradient is nought to rounding in the reference — as a
    whole (norm under ``whole`` of the median leaf's) or on more than
    ``share`` of their elements (a fused q/k/v bias: the key's third has no
    gradient under softmax). Adam moves such elements by round-off alone,
    so these leaves' change is not compared. A rule on the reference's
    gradient, not on names."""
    norms = reference["grad_norms"]
    median = statistics.median(norms.values())
    dead = {n for n, g in norms.items() if g < whole * median}
    shares = reference.get("grad_dead_share") or {}
    return dead | {n for n, s in shares.items() if s > share}


def compare_first_steps(program: dict, reference: dict) -> dict:
    """``program`` / ``reference``: ``{"losses": [l1, l2, l3],
    "grad_norms": {leaf: n}, "change_norms": {leaf: n}}``. Returns the
    numbers compared, by short plain names."""
    n = len(reference["losses"])
    loss_gaps = [
        abs(p - r) / abs(r)
        for p, r in zip(program["losses"][:n], reference["losses"])
    ]
    if len(program["losses"]) < n or not all(map(math.isfinite, loss_gaps)):
        loss_gap = float("inf")
    else:
        loss_gap = max(loss_gaps)
    grad = leaf_gaps(program["grad_norms"], reference["grad_norms"])
    dead = dead_leaves(reference)
    change = leaf_gaps(
        program["change_norms"], reference["change_norms"], skip=dead
    )
    (grad_gap, grad_leaf), (change_gap, change_leaf) = worst(grad), worst(change)
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": grad_gap,
        "change_norm_gap": change_gap,
        # the median leaf's: steady from seed to seed where the worst leaf
        # is the rounding noise of one small vector
        "grad_norm_gap_median": statistics.median(grad.values()),
        "change_norm_gap_median": statistics.median(change.values()),
        "_where": {"grad_norm_gap": grad_leaf, "change_norm_gap": change_leaf,
                   "dead_leaves": sorted(dead)},
        "_leaves": {"grad": grad, "change": change},
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number in ``limits`` against its limit. Returns ``correct``
    and ``{name: {"value", "limit"}}``; a number that is missing or not
    finite fails. A ``None`` limit marks a number that is shown and not
    compared (it has no upper reading; PERF.md says why)."""
    table, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        table[name] = {"value": value, "limit": limit}
        if limit is not None:
            ok = ok and value is not None and math.isfinite(value) \
                and value <= limit
    return ok, table
