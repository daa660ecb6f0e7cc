"""The comparison that decides ``correct`` (PERF.md, section 2).

What is compared, for the timed path at the timed sizes, over the first
three steps that set-up drives through the window's own call and feed:

* ``loss_step{0,1,2}``: |program's loss - reference's| / |reference's|;
* ``grad1_worst_leaf``: the first gradient as the optimizer gets it
  (preconditioned, KL-clipped), recovered from the program's state after one
  step (its momentum is ``g + wd p0`` then), by the worst leaf;
* ``delta3_worst_leaf``: the parameters' change after three steps, by the
  worst leaf, leaving out leaves whose reference gradient is under a
  thousandth of the median leaf's;
* ``reference_inverse_residual``: the reference's own check that its
  iterated inverses are inverses, ``max ||I - M X||_F / sqrt(n)``;
* ``window_compiles`` and ``nonfinite_losses``: exact, limit 0.

``grad1_median_leaf`` and ``delta3_median_leaf`` are the same two by the
median leaf: steady from seed to seed where the worst leaf is one whose
gradient nearly cancels (PERF.md, section 6). A cell's file lists, under
``limits``, the numbers it is held to.

"By the worst leaf" is the gap between the program's norm and the
reference's, against the reference's norm of that leaf or of the median
leaf, whichever is larger.
"""

from __future__ import annotations

import math
import statistics


def leaf_gaps(prog, ref, keep=None):
    """``[(gap, leaf, prog norm, ref norm)]``, worst first, with
    gap = |prog - ref| / max(ref, median ref); a norm that is not finite
    gives an infinite gap."""
    names = [n for n in ref if keep is None or n in keep]
    floor = statistics.median(ref[n] for n in names)
    rows = []
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
        rows.append((gap if math.isfinite(gap) else float("inf"), n, prog[n], ref[n]))
    return sorted(rows, reverse=True)


def moved_leaves(ref_grad):
    """Leaves whose reference gradient is at least a thousandth of the median
    leaf's: the others move by round-off alone."""
    floor = 1e-3 * statistics.median(ref_grad.values())
    return {n for n, v in ref_grad.items() if v >= floor}


def readings(prog, ref):
    """The numbers compared, ``{name: value}``, and for each worst-leaf
    number where it lies: the leaf, the median leaf's gap and the five worst
    leaves with both norms. ``prog`` and ``ref`` are
    ``{"loss": [3 floats], "grad1": {leaf: norm}, "delta3": {leaf: norm}}``."""
    out, where = {}, {}
    for k, (lp, lr) in enumerate(zip(prog["loss"], ref["loss"])):
        out[f"loss_step{k}"] = abs(lp - lr) / max(abs(lr), 1e-30)
    if "inverse_residual" in ref:
        out["reference_inverse_residual"] = ref["inverse_residual"]
    for name, rows in (
        ("grad1_worst_leaf", leaf_gaps(prog["grad1"], ref["grad1"])),
        ("delta3_worst_leaf", leaf_gaps(prog["delta3"], ref["delta3"], keep=moved_leaves(ref["grad1"]))),
    ):
        out[name] = rows[0][0]
        out[name.replace("worst", "median")] = statistics.median(r[0] for r in rows)
        where[name] = {
            "leaf": rows[0][1],
            "median_gap": statistics.median(r[0] for r in rows),
            "worst": [list(r) for r in rows[:5]],
        }
    return out, where


def decide(values, limits):
    """``(correct, rows)`` with ``rows = {name: {"value", "limit"}}``. Every
    number in ``limits`` has to be there, finite and within its limit."""
    rows, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        rows[name] = {"value": v, "limit": limit}
        if v is None or not math.isfinite(v) or v > limit:
            ok = False
    return ok, rows
