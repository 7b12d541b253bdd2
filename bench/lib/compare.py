"""The comparison that decides ``correct``.

The numbers, each against the cell's limit where it sets one:

  loss_gap    the largest relative gap of a step's loss over the steps the
              reference follows: |L_sys - L_ref| / |L_ref|
  grad_gap    the worst leaf's gap between the norms of the first gradient
              as the optimizer gets it (clipped): | |g_sys| - |g_ref| | over
              the larger of |g_ref| of that leaf and of the median leaf
  update_gap  the same for the change of the parameters over those steps
  mask_margin (sparse attention, a step of one sequence) the median over
              the compared steps' layers of the hash margin that rounding
              must have crossed to give the system's live-tile count of the
              layer; 1 where no flip of the bits nearest their planes gives
              it (``dense_reference.explained_mask``,
              ``runner.compared``).  Sound runs match most layers without a
              flip, so it reads 0; a mask that is off in every layer does
              not.

Beside them, for the record: the first step's loss gap and the median
leaf's gap of either norm.

A leaf whose reference gradient norm is under a thousandth of the median
leaf's takes no part: its gradient is nought to rounding, and Adam moves it
by round-off alone.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

QUIET = 1e-3


def _leaf_gaps(sys: Dict[str, float], ref: Dict[str, float],
               keep: List[str]) -> Dict[str, float]:
    med = float(np.median([ref[k] for k in keep]))
    return {k: abs(sys[k] - ref[k]) / max(ref[k], med) for k in keep}


def gaps(sys_losses, sys_grads, sys_delta, ref: dict) -> dict:
    n = len(ref["losses"])
    if len(sys_losses) < n or sys_grads is None or sys_delta is None:
        raise ValueError("the run stopped before the compared steps")
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(sys_losses[:n], ref["losses"]))
    g = ref["grad_norms"]
    med = float(np.median(list(g.values())))
    keep = [k for k in g if g[k] >= QUIET * med]
    out = {"loss_gap": float(loss_gap),
           "first_loss_gap": float(abs(sys_losses[0] - ref["losses"][0])
                                   / abs(ref["losses"][0])),
           "leaves": len(keep), "quiet_leaves": sorted(set(g) - set(keep))}
    for name, sys, r in (("grad", sys_grads, g),
                         ("update", sys_delta, ref["delta_norms"])):
        gaps = _leaf_gaps(sys, r, keep)
        leaf = max(gaps, key=gaps.get)
        out[name + "_gap"] = float(gaps[leaf])
        out[name + "_leaf"] = leaf
        out[name + "_gap_median"] = float(np.median(list(gaps.values())))
    return out


def judge(g: dict, limits: Dict[str, float]) -> tuple:
    """(correct, {name: {value, limit}}) for every limited number; a number
    the run could not read fails."""
    checks = {k: {"value": g.get(k), "limit": limits[k]} for k in limits}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return bool(ok), checks
