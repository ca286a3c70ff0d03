"""The numbers that decide ``correct``: the trainer's first steps against the
reference's, each beside its limit from ``bench/limits/<workload>.json``.

* ``loss_gap``: the widest relative gap between the trainer's and the
  reference's loss over the checked steps;
* ``grad_gap``: over the gradient's leaves, the widest gap between the norm
  of the first clipped gradient as the optimizer got it (the trainer's first
  Adam moment over ``1 - b1``) and the reference's, relative to the larger
  of the reference's norm of that leaf and of the median leaf;
* ``change_gap``: the same for the norm of each leaf's change over the
  checked steps, over the leaves whose reference gradient is at least a
  thousandth of the median leaf's (the others move under Adam by round-off).
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
MOVED_SHARE = 1e-3


def _worst_gap(ours, ref, counted):
    ours, ref = np.asarray(ours, np.float64), np.asarray(ref, np.float64)
    base = np.maximum(ref, np.median(ref[counted]))
    gaps = np.where(counted, np.abs(ours - ref) / base, 0.0)
    i = int(np.argmax(gaps))
    return float(gaps[i]), i


def numbers(ours: dict, ref: dict, names=None) -> dict:
    """{number: {"value", "leaf"?}} from two readings of the same steps."""
    lp, lr = np.asarray(ours["loss"]), np.asarray(ref["loss"])
    loss = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    gr = np.asarray(ref["grad_norms"], np.float64)
    every = np.ones_like(gr, bool)
    grad, gi = _worst_gap(ours["grad_norms"], gr, every)
    moved = gr >= MOVED_SHARE * np.median(gr)
    change, ci = _worst_gap(ours["change_norms"], ref["change_norms"], moved)
    name = (lambda i: names[i]) if names else (lambda i: i)
    return {"loss_gap": {"value": loss},
            "grad_gap": {"value": grad, "leaf": name(gi)},
            "change_gap": {"value": change, "leaf": name(ci)},
            "unmoved_leaves": [name(i) for i in np.flatnonzero(~moved)]}


def judge(nums: dict, limits: dict) -> tuple:
    """(correct, {number: {"value", "limit"}}) — every number at or under
    its limit, and none missing or not finite."""
    checks, ok = {}, True
    for key in NUMBERS:
        value = nums[key]["value"]
        limit = limits[key]["limit"]
        checks[key] = {"value": value, "limit": limit}
        ok = ok and bool(np.isfinite(value)) and value <= limit
    return ok, checks
