"""The numbers that decide ``correct``, from two sets of readings.

Each reading is ``{"losses": [...], "grad_norms": {leaf: norm},
"delta_norms": {leaf: norm}}``: the loss of each of the first steps, the
norm of each leaf of the first gradient, and the norm of each leaf's
change over those steps. Against the reference:

* ``loss_gap``: the largest ``|loss - ref| / |ref|`` over the steps;
* ``grad_gap`` and ``delta_gap``: over the leaves, the largest
  ``|norm - ref_norm| / max(ref_norm, median ref_norm)``: a gap of norms,
  not the norm of a difference, held against the median leaf where a
  leaf's own norm is all but zero.

Leaves whose reference gradient is under ``STILL`` times the median
leaf's move under Adam by round-off alone; they are left out of both
leaf gaps (the rule is on the reference's gradient, never on a name).
"""

from __future__ import annotations

import statistics
from typing import Dict, List

STILL = 1e-3
NUMBERS = ("loss_gap", "grad_gap", "delta_gap")


def still_leaves(ref: Dict) -> List[str]:
    norms = ref["grad_norms"]
    med = statistics.median(norms.values())
    return sorted(k for k, v in norms.items() if v < STILL * med)


def _leaf_gap(got: Dict[str, float], ref: Dict[str, float], skip) -> float:
    if set(got) != set(ref):
        raise KeyError(
            f"leaves differ: only program {sorted(set(got) - set(ref))}, "
            f"only reference {sorted(set(ref) - set(got))}"
        )
    keep = [k for k in ref if k not in skip]
    med = statistics.median(ref[k] for k in keep)
    return max(abs(got[k] - ref[k]) / max(ref[k], med) for k in keep)


def gaps(got: Dict, ref: Dict) -> Dict[str, float]:
    skip = set(still_leaves(ref))
    return {
        "loss_gap": max(
            abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])
        ),
        "grad_gap": _leaf_gap(got["grad_norms"], ref["grad_norms"], skip),
        "delta_gap": _leaf_gap(got["delta_norms"], ref["delta_norms"], skip),
    }


def state_unchanged(ref: Dict) -> Dict:
    """The readings of a step that returns its state unchanged: no
    first moment (so no gradient) and no change."""
    return {
        "losses": list(ref["losses"]),
        "grad_norms": {k: 0.0 for k in ref["grad_norms"]},
        "delta_norms": {k: 0.0 for k in ref["delta_norms"]},
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every number at or under its limit (a NaN fails)."""
    return all(numbers[k] <= limits[k] for k in limits)
