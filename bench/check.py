"""The comparison that decides ``correct`` for a training cell.

Both sides give the same readings of the first steps of a fit:

  loss      the loss of each step, in order;
  grad      per leaf, the norm of the first step's gradient as the
            optimizer got it (clipped), worked out from the first moment
            after one step: m_1 = (1 - b1) g_1;
  change    per leaf, the norm of the parameters' change after the
            compared steps;
  proj      per constrained leaf, the norm of the leaf after the steps in
            which its projection fired.

Each number is a gap between a norm of the program and the reference's
norm of the same thing, never the norm of their difference, taken at the
worst leaf and measured against the reference's norm of that leaf or of
the median leaf, whichever is larger. ``change`` leaves out leaves whose
reference gradient is below a thousandth of the median leaf's: under Adam
those move by round-off alone.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

from .plain import median

NUMBERS = ("loss_gap", "grad_gap", "change_gap", "proj_gap")
QUIET_GRAD = 1e-3


def _max(values) -> float:
    """The largest value, NaN if any is NaN (Python's max skips them)."""
    values = list(values)
    if any(math.isnan(v) for v in values):
        return math.nan
    return max(values, default=0.0)


def _worst(prog: Dict[str, float], ref: Dict[str, float], keys,
           floor: float) -> float:
    return _max(abs(prog[k] - ref[k]) / max(ref[k], floor, 1e-30)
                for k in keys)


def gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers of ``prog`` against ``ref`` (see module doc)."""
    if set(prog["grad"]) != set(ref["grad"]):
        raise ValueError("the program's and the reference's leaves differ: "
                         f"{sorted(set(prog['grad']) ^ set(ref['grad']))}")
    if len(prog["loss"]) != len(ref["loss"]):
        raise ValueError("the two sides compared different numbers of steps")
    g_med = median(ref["grad"].values())
    moving = [k for k, g in ref["grad"].items() if g >= QUIET_GRAD * g_med]
    c_med = median(ref["change"][k] for k in moving)
    return {
        "loss_gap": _max(abs(a - b) / abs(b)
                         for a, b in zip(prog["loss"], ref["loss"])),
        "grad_gap": _worst(prog["grad"], ref["grad"], ref["grad"], g_med),
        "change_gap": _worst(prog["change"], ref["change"], moving, c_med),
        "proj_gap": _worst(prog["proj"], ref["proj"], ref["proj"], 0.0),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[dict]]:
    """(every number within its limit, [{name, value, limit}, ...]). A
    number that is not finite fails."""
    rows, ok = [], True
    for name in NUMBERS:
        value, limit = numbers[name], limits[name]
        good = value == value and value <= limit   # NaN compares false
        ok &= good
        rows.append({"name": name, "value": value, "limit": limit})
    return ok, rows
