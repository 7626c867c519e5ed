"""The comparisons that decide `correct`: what the timed path produced
against the plain reference. Each returns rows of
{"name", "value", "limit"}; a run is correct when every value is at or
under its limit. Limits live in the cell's file with the readings they
were set from (PERF.md section 2 has the table).
"""
import statistics


def worst_leaf_gap(prog, ref, skip=()):
    """Largest gap between the program's and the reference's norm of a
    leaf, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Returns (gap, leaf name)."""
    med = statistics.median(ref.values())
    worst, where = 0.0, None
    for name, r in ref.items():
        if name in skip:
            continue
        gap = abs(prog[name] - r) / max(r, med, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where


def flat_gradient_leaves(ref_grad_norm, share=1e-3):
    """Leaves whose reference gradient is nought to rounding: under
    `share` of the median leaf's. Under Adam they move by round-off
    alone, so they are left out of the change comparison."""
    med = statistics.median(ref_grad_norm.values())
    return {k for k, g in ref_grad_norm.items() if g < share * med}


def train_rows(prog, ref, limits):
    """prog/ref: {"losses": [..], "grad_norm": {leaf: n},
    "change_norm": {leaf: n}}. The losses are not compared: the control
    reads under the program on them (PERF.md section 2), so a limit
    could only fail sound runs; `loss_gaps` gives them for the log."""
    rows = []
    g, leaf = worst_leaf_gap(prog["grad_norm"], ref["grad_norm"])
    rows.append({"name": "grad_norm_gap", "value": g, "leaf": leaf,
                 "limit": limits["grad_norm_gap"]})
    if "change_norm_gap" in limits:
        skip = flat_gradient_leaves(ref["grad_norm"])
        c, leaf = worst_leaf_gap(prog["change_norm"], ref["change_norm"],
                                 skip)
        rows.append({"name": "change_norm_gap", "value": c, "leaf": leaf,
                     "limit": limits["change_norm_gap"]})
    return rows


def loss_gaps(prog, ref):
    return [abs(a - b) for a, b in zip(prog["losses"], ref["losses"])]


def token_gap(best, picked, first, count):
    """Widest gap by which a served token's logit lies below the
    reference's best, over the `count` served tokens of one request
    whose first is predicted at position `first`."""
    worst = 0.0
    for t in range(first, first + count):
        worst = max(worst, float(best[t]) - float(picked[t]))
    return worst
