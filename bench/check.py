"""What decides ``correct``: the program's first phase against the
reference's, number by number, each against its own limit.

Both sides give, for the cell's first phase (K steps, the averaging
event at its end): the loss and the Eq. 4 dispersion of every step, and
per worker and per leaf the norm of the parameters' change from the
seeded weights and the norm of the momentum (the gradients as the
optimizer holds them). Norms are compared by the worst leaf: the gap
between the program's and the reference's norm, over the larger of that
leaf's reference norm and the median leaf's. A leaf whose reference
momentum is under a thousandth of the median leaf's has no gradient to
speak of and moves by round-off alone; it is left out of the change.
"""
from __future__ import annotations

import math
import statistics

NAMES = ("loss_gap", "dispersion_gap", "change_gap", "velocity_gap")


def series_gap(prog, ref) -> float:
    """Widest relative gap of two per-step series."""
    if len(prog) != len(ref):
        return math.inf
    return max((abs(p - r) / abs(r) if r else math.inf)
               for p, r in zip(prog, ref))


def leaf_gap(prog, ref, skip=()) -> tuple:
    """Worst-leaf gap of per-worker ``name -> norm`` dicts; returns
    (gap, "worker:leaf")."""
    worst, where = 0.0, ""
    for w, (pw, rw) in enumerate(zip(prog, ref)):
        med = statistics.median(rw.values())
        for k, r in rw.items():
            if k in skip:
                continue
            g = abs(pw[k] - r) / max(r, med) if max(r, med) > 0 else math.inf
            if not g <= worst:  # NaN too
                worst, where = g, f"{w}:{k}"
    return worst, where


def idle_leaves(ref) -> set:
    """Leaves whose reference momentum is under 1e-3 of the median's."""
    top = {k: max(w[k] for w in ref["velocity"]) for k in ref["velocity"][0]}
    med = statistics.median(top.values())
    return {k for k, v in top.items() if v < 1e-3 * med}


def readings(prog, ref) -> dict:
    """Every compared number, with where its worst leaf lies."""
    skip = idle_leaves(ref)
    ch, ch_at = leaf_gap(prog["change"], ref["change"], skip)
    vel, vel_at = leaf_gap(prog["velocity"], ref["velocity"])
    return {"loss_gap": series_gap(prog["loss"], ref["loss"]),
            "dispersion_gap": series_gap(prog["dispersion"],
                                         ref["dispersion"]),
            "change_gap": ch, "velocity_gap": vel,
            "worst_change_leaf": ch_at, "worst_velocity_leaf": vel_at,
            "idle_leaves": sorted(skip)}


def decide(read: dict, limits: dict) -> tuple:
    """(correct, checks): each compared number beside its limit."""
    checks = {n: {"value": read[n], "limit": limits[n]} for n in NAMES}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
