"""The comparison that decides ``correct`` for a training cell.

Both sides start from the same seeded weights and take the same first
three steps on the same three batches.  The numbers, each compared against
its limit in ``chipbench/limits/<cell>.json`` where that names it:

- ``loss_gap``: the largest |loss - reference loss| / |reference loss| over
  the three steps (``loss1_gap``: the first step's alone);
- ``grad_gap``: the first gradient as the optimizer gets it (clipped), by
  the worst leaf: | |g| - |g_ref| | / max(|g_ref|, median leaf |g_ref|);
- ``change_gap``: the parameters' change after three steps, by the worst
  leaf, measured the same way.  Leaves whose reference gradient is under a
  thousandth of the median leaf's move under Adam by round-off alone and
  are left out;
- ``grad_diff_median``: the median leaf's |g - g_ref| / max(|g_ref|,
  median leaf |g_ref|), the two first gradients compared element by
  element on the host.
"""
from __future__ import annotations

import numpy as np

QUIET_LEAF = 1e-3


def _worst_leaf(got, want, keep):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    floor = np.maximum(want, np.median(want))
    gap = np.abs(got - want) / floor
    gap = np.where(keep, gap, 0.0)
    i = int(np.argmax(gap))
    return float(gap[i]), i


def _norms(leaves) -> np.ndarray:
    return np.asarray([np.linalg.norm(np.ravel(x)) for x in leaves],
                      np.float64)


def readings(prog: dict, ref: dict, names: list) -> dict:
    """``prog``/``ref``: {"losses": [3], "grad": [host leaves],
    "change_norms": [leaves]} -> the numbers, per-leaf gaps and the worst
    leaves."""
    lp = np.asarray(prog["losses"], np.float64)
    lr = np.asarray(ref["losses"], np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.all(np.isfinite(lp)):
        loss_gap = float("inf")
    g_ref, g_got = _norms(ref["grad"]), _norms(prog["grad"])
    all_leaves = np.ones(len(names), bool)
    grad_gap, gi = _worst_leaf(g_got, g_ref, all_leaves)
    moving = g_ref >= QUIET_LEAF * np.median(g_ref)
    change_gap, ci = _worst_leaf(prog["change_norms"], ref["change_norms"],
                                 moving)
    g_floor = np.maximum(g_ref, np.median(g_ref))
    gd = _norms([a - b for a, b in zip(prog["grad"], ref["grad"])]) / g_floor
    for v in (grad_gap, change_gap, gd.max()):
        if not np.isfinite(v):
            loss_gap = float("inf")
    c_ref = np.asarray(ref["change_norms"], np.float64)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap,
            "grad_diff_median": float(np.median(gd)),
            "loss1_gap": float(abs(lp[0] - lr[0]) / abs(lr[0])),
            "leaves": {
                "grad_gap": (np.abs(g_got - g_ref) / g_floor).tolist(),
                "grad_diff": gd.tolist(),
                "change_gap": (np.abs(np.asarray(prog["change_norms"])
                                      - c_ref)
                               / np.maximum(c_ref, np.median(c_ref))).tolist(),
            },
            "worst_grad_leaf": names[gi], "worst_change_leaf": names[ci],
            "left_out_of_change": [n for n, k in zip(names, moving) if not k]}


def verdict(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) for every number the cell's
    limits name: each at or under its limit; a number that is not finite
    fails."""
    checks = {k: {"value": read[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
