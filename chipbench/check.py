"""The comparison that decides ``correct``: each sampled request's x0, as
the window served it, against the plain reference run on the same seeded
weights, condition, budget and PRNG key. The number compared is the worst
relative RMS error over the sample, beside the check that every request
was served at its own budget and that there was something to compare."""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def reference_x0(cell, params, sample: List, precision: str = "f32"
                 ) -> Dict[int, np.ndarray]:
    """The reference's x0 for each sampled request (index into sample),
    one batch per budget tier, on the steps the configuration's work
    module gives the budget."""
    s = cell.traffic["sampler"]
    groups: Dict[float, List[int]] = {}
    for i, r in enumerate(sample):
        groups.setdefault(r.budget, []).append(i)
    out: Dict[int, np.ndarray] = {}
    for budget, idx in sorted(groups.items()):
        modes = cell.work.step_modes(cell.config, s["T"], budget)
        x0 = cell.reference.sample(
            params, cell.config, np.stack([sample[i].key for i in idx]),
            [sample[i].cond for i in idx], modes, s, precision=precision)
        for j, i in enumerate(idx):
            out[i] = x0[j]
    return out


def rel_rms(got, want) -> float:
    """Relative RMS error; infinite when either side is not finite."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        return math.inf
    return math.sqrt(float(np.mean((got - want) ** 2))
                     / float(np.mean(want ** 2)))


def worst_rel_rms(x: Dict[int, np.ndarray], ref: Dict[int, np.ndarray]
                  ) -> float:
    return max(rel_rms(x[i], ref[i]) for i in ref)


def compare(cell, params, sample: List, x_served: Dict[int, np.ndarray],
            budgets_served: List[float]) -> Dict[str, Dict]:
    """Each number compared beside its limit, for the sampled requests'
    x0 as served (``x_served`` by index into ``sample``)."""
    mismatched = sum(b != r.budget for b, r in zip(budgets_served, sample))
    worst = (worst_rel_rms(x_served, reference_x0(cell, params, sample))
             if sample else math.inf)
    return {"compared": {"value": len(sample), "limit": 1},
            "budget_mismatches": {"value": mismatched, "limit": 0},
            "rel_rms_max": {"value": worst if math.isfinite(worst) else 1e30,
                            "limit": cell.limits["rel_rms"]["limit"]}}


def passes(name: str, c: Dict) -> bool:
    """``compared`` is a floor; every other number is a ceiling."""
    if name == "compared":
        return c["value"] >= c["limit"]
    return c["value"] <= c["limit"]


def correct(failed: int, checks: Dict[str, Dict]) -> bool:
    return failed == 0 and all(passes(n, c) for n, c in checks.items())
