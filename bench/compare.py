"""The comparison that decides ``correct`` for a training cell.

Both sides report, from the same seeded weights and batches:

* ``losses``: the loss of each of the first steps;
* ``grad``: per weight (per layer for stacked weights), the norm of the
  gradient as the optimizer received it at step 0, which runs the refresh
  program (full-rank gradients, a new projection);
* ``grad1``: the same at step 1, which runs the plain program the window
  times, for the weights without a projection (norms, the embedding):
  their gradients run through the plain program's forward, attention,
  head and input-gradient passes whole, where a projected weight's lies
  in each side's own random subspace;
* ``change``: per weight, the norm of its change over those steps.

Step 0 runs at learning rate 0 where the traffic says so, so that step 1
runs the same weights on both sides.

The numbers, each with a limit of its own where it is compared:

* ``loss_gap.<step>``, for each of the three steps:
  ``|loss - loss_ref| / loss_ref``. Steps 0 and 1 run the same weights on
  the same batch on both sides (step 1 only after a step at rate 0), so
  only arithmetic separates them; step 2 follows an update that each side
  rounds and projects with randoms of its own;
* ``grad_gap``, ``grad1_gap`` and ``change_gap``: over the weights, the
  largest ``|norm - norm_ref| / max(norm_ref, median_ref)``, where
  ``median_ref`` is the reference's median over the weights compared. A
  weight whose reference gradient at step 0 is under a thousandth of the
  median weight's is left out of all three: it moves by round-off alone;
* ``grad_gap.median``: the median of those per-weight gaps of the first
  gradient, steadier from seed to seed than the largest, which a few
  weights set.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

STEPS = 3
NUMBERS = tuple(f"loss_gap.{i}" for i in range(STEPS)) + (
    "grad_gap", "grad_gap.median", "grad1_gap", "change_gap")
GRAD_FLOOR = 1e-3


def _flat(d: Dict[str, np.ndarray]) -> Dict[str, float]:
    out = {}
    for name, v in d.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        if v.size == 1:
            out[name] = float(v[0])
        else:
            for i, x in enumerate(v):
                out[f"{name}[{i}]"] = float(x)
    return out


def norm_gaps(prog, ref, ref_grad) -> Dict[str, float]:
    """Per-weight gaps of norms, the weights moved by round-off alone left
    out."""
    p, r, g = _flat(prog), _flat(ref), _flat(ref_grad)
    if set(p) != set(r):
        missing = sorted(set(r) ^ set(p))[:4]
        raise ValueError(f"the two sides report different weights: "
                         f"{missing}")
    med = float(np.median(list(r.values())))
    gmed = float(np.median(list(g.values())))
    return {k: (abs(p[k] - r[k]) / max(r[k], med, 1e-30)
                if np.isfinite(p[k]) else float("inf"))
            for k in sorted(r) if g[k] >= GRAD_FLOOR * gmed}


def numbers(prog: dict, ref: dict) -> Dict[str, dict]:
    """The compared numbers, each with where it was found."""
    out = {}
    for i in range(STEPS):
        p, r = prog["losses"][i], ref["losses"][i]
        gap = abs(p - r) / abs(r)
        out[f"loss_gap.{i}"] = {"value": gap if np.isfinite(gap)
                                else float("inf")}
    for key in ("grad", "grad1", "change"):
        gaps = norm_gaps(prog[key], ref[key], ref["grad"])
        at = max(gaps, key=gaps.get)
        out[f"{key}_gap"] = {"value": gaps[at], "at": at}
        if key == "grad":
            out["grad_gap.median"] = {
                "value": float(np.median(list(gaps.values())))}
    return out


def judge(nums: Dict[str, dict], limits: Dict[str, float]) -> bool:
    """True when every number that has a limit is at or under it."""
    return all(nums[k]["value"] <= limits[k] for k in NUMBERS
               if k in limits)
