"""The comparison that decides ``correct`` for a training cell.

Three numbers, each against its limit (the cell's ``limits/<cell>.json``;
``PERF.md`` gives the readings each was set from):

* ``loss_gap``: over the compared steps, the largest gap between the
  program's pool-mean loss and the reference's, relative to the
  reference's;
* ``grad_gap``: over the leaves (stacked block leaves one per layer), the
  largest gap between the norms of the program's first gradient as the
  optimizer sees it and the reference's, relative to the larger of that
  leaf's reference norm and the median leaf's;
* ``change_gap``: the same for each leaf's change over the compared
  steps, leaving out leaves whose first reference gradient is under a
  thousandth of the median leaf's, which move by round-off alone.

A missing or non-finite reading fails its number.
"""

from __future__ import annotations

import math
import statistics

#: leaves whose first reference gradient is under this share of the median
#: leaf's are left out of ``change_gap``
STILL_LEAF = 1e-3


def _leaf_gap(prog: dict, ref: dict, keep) -> float:
    if set(prog) != set(ref):
        return math.inf
    med = statistics.median(ref.values())
    gaps = [
        abs(prog[k] - ref[k]) / max(ref[k], med)
        for k in ref if keep(k)
    ]
    gaps = [g if math.isfinite(g) else math.inf for g in gaps]
    return max(gaps) if gaps else math.inf


def numbers(prog: dict, ref: dict) -> dict[str, float]:
    """The compared numbers of a run's readings against the reference's."""
    lp, lr = prog["losses"], ref["losses"]
    if len(lp) != len(lr):
        loss_gap = math.inf
    else:
        loss_gap = max(abs(a - b) / abs(b) for a, b in zip(lp, lr))
    if not math.isfinite(loss_gap):
        loss_gap = math.inf
    g_ref = ref["grad_norms"]
    med = statistics.median(g_ref.values())
    return {
        "loss_gap": loss_gap,
        "grad_gap": _leaf_gap(prog["grad_norms"], g_ref, lambda k: True),
        "change_gap": _leaf_gap(
            prog["change_norms"], ref["change_norms"],
            lambda k: g_ref[k] >= STILL_LEAF * med,
        ),
    }


def judge(nums: dict[str, float], limits: dict[str, float]) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})``."""
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
