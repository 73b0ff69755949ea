"""The comparison that decides `correct`: the timed path's own readings
against the plain reference's, each number beside its limit."""

from __future__ import annotations

import statistics


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, |program's norm - reference's norm| over the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    med = statistics.median(ref.values())
    return {
        name: abs(prog[name] - r) / max(r, med, 1e-30)
        for name, r in ref.items() if keep is None or name in keep
    }


def direction_gaps(prog: dict, ref: dict, keep) -> dict:
    """Per leaf, the distance between the program's first gradient and the
    reference's, each scaled to unit length: 0 where they point the same way,
    about the relative size of unbiased noise, 1 where the program's is
    nought. A gap of norms is blind to such noise; this is not."""
    import numpy as np

    out = {}
    for name in keep:
        r = np.asarray(ref[name], np.float64).ravel()
        p = np.asarray(prog[name], np.float64).ravel()
        rn, pn = np.linalg.norm(r), np.linalg.norm(p)
        out[name] = float(np.linalg.norm(p / pn - r / rn)) if pn > 0.0 else 1.0
    return out


def train_numbers(prog: dict, ref: dict) -> dict:
    """prog / ref: {"loss": [per step], "grad_norm": {leaf: norm of the first
    gradient as the moments got it}, "first_grad": {leaf: that gradient},
    "update_norm": {leaf: norm of the parameters' change after the last
    step}}.

    Leaves whose reference gradient is under a thousandth of the median
    leaf's (a key's bias under softmax: nought to rounding) move under Adam
    by round-off alone and have no direction: they are left out of the change
    and of the direction."""
    loss_gap = max(
        abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"])
    )
    med = statistics.median(ref["grad_norm"].values())
    moved = {k for k, g in ref["grad_norm"].items() if g >= 1e-3 * med}
    grad = leaf_gaps(prog["grad_norm"], ref["grad_norm"])
    upd = leaf_gaps(prog["update_norm"], ref["update_norm"], moved)
    turn = direction_gaps(prog["first_grad"], ref["first_grad"], sorted(moved))
    return {
        "loss_gap": loss_gap,
        "grad_norm_gap": max(grad.values()),
        "update_norm_gap": max(upd.values()),
        "grad_direction_gap": max(turn.values()),
        "grad_direction_gap_median": statistics.median(turn.values()),
        "grad_norm_gap_median": statistics.median(grad.values()),
        "update_norm_gap_median": statistics.median(upd.values()),
        "_at": {"grad_norm_gap": max(grad, key=grad.get),
                "update_norm_gap": max(upd, key=upd.get),
                "grad_direction_gap": max(turn, key=turn.get)},
        "_leaves": {"grad": grad, "update": upd, "direction": turn},
    }


def decide(numbers: dict, limits: dict, extra_ok: bool = True) -> tuple[bool, dict]:
    """Every number named in `limits` must be at or under its limit."""
    compared, ok = {}, extra_ok
    for name, limit in limits.items():
        value = numbers[name]
        compared[name] = {"value": value, "limit": limit}
        if not (value <= limit):  # NaN fails
            ok = False
    return ok, compared
