"""The comparison that decides ``correct``.

Two numbers, each the worst leaf's gap between the program's norm and the
reference's norm of one per-leaf quantity (not the norm of their
difference), measured against the reference's norm of that leaf or of the
median leaf, whichever is larger:

``update_gap``  over the compared steps, the change the step made to the
                estimator g: g^{k+1} - g^k on a difference round, g^{k+1}
                on a full-gradient round;
``change_gap``  the parameters' change x^3 - x^0 after three steps.

Which leaves count follows from the inputs (``feed.g0``): g^0 moves the
leaves it is set on, by several steps of their storage precision, and is
zero elsewhere.  So the parameters' change is compared on the leaves g^0
moves; the change of g on a difference round on the other leaves, where g
holds the aggregates alone (on a moved leaf it lies under g's storage
precision); g itself after a full round on every leaf.  Of those, a leaf
whose reference norm is under a thousandth of the median leaf's is nought
to rounding in the reference and is left out.
"""
import numpy as np

NOUGHT = 1e-3


def left_out(reference):
    """Indices of the leaves nought to rounding in ``reference``."""
    reference = np.asarray(reference, np.float64)
    return np.flatnonzero(reference < NOUGHT * np.median(reference))


def worst_gap(program, reference, counted=None):
    program = np.asarray(program, np.float64)
    reference = np.asarray(reference, np.float64)
    if counted is not None:
        program, reference = program[counted], reference[counted]
    if not np.all(np.isfinite(program)):
        return float("inf")
    med = float(np.median(reference))
    counted = np.ones(reference.shape, bool)
    counted[left_out(reference)] = False
    if not counted.any():
        return 0.0
    gap = np.abs(program - reference) / np.maximum(reference, med)
    return float(np.max(gap[counted]))


def gaps(program, reference, moved):
    """program: {"update_norms": [per step: {"diff": norms, "full": norms}],
    "change_norms": norms of the moved leaves}; reference: what
    ``reference.algorithm.run`` returns; moved: per leaf, whether g^0 is
    set on it.  The round kind comes from the reference."""
    moved = np.asarray(moved, bool)
    update = 0.0
    for k, rnd in enumerate(reference["rounds"]):
        kind, counted = ("full", None) if rnd["full"] else ("diff", ~moved)
        update = max(update, worst_gap(program["update_norms"][k][kind],
                                       rnd["update_norms"], counted))
    return {"update_gap": update,
            "change_gap": worst_gap(program["change_norms"],
                                    np.asarray(reference["change_norms"])[moved])}


def verdict(values, limits):
    """(correct, checks) with checks = {name: {"value", "limit"}}."""
    checks = {n: {"value": values[n], "limit": limits[n]} for n in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
