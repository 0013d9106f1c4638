"""The comparison that decides ``correct``: every answer of the window,
judged in f64 by ``benchmark/reference`` against the run's own problem.

Each cell's numbers and their limits are in ``benchmark/limits/<cell>.json``
(with the readings each limit was set from).  A run is correct when it
failed nothing, compiled nothing inside the window, and every number is
at or under its limit.
"""

from __future__ import annotations

import numpy as np

from .drivers.library import global_iterate
from .reference import pgo


def library_numbers(drv) -> tuple[dict, list[bool]]:
    """Worst reading over the window's answers, per number.  Identical
    answers (the same bytes of X) are judged once."""
    tr, cfg = drv.traffic, drv.config
    certify = tr.get("params", {}).get("certify_mode", "off") != "off"
    seen: dict[str, dict] = {}
    for s in drv.solves:
        if s["key"] in seen:
            continue
        prob = drv.problems[s["k"]]
        # A GNC answer is a trajectory and the weights it settled on: it
        # has to be stationary for the problem so weighted, and its
        # weights are judged against the planted outliers.
        w = s["w"] if tr.get("outlier_fraction") else None
        Xg = global_iterate(s["X"], prob.n)
        r = {"grad_X": pgo.grad_norm(prob, Xg, w),
             "grad_T": pgo.grad_norm(prob, s["T"], w)}
        if certify:
            tol = cfg["certify_eta"] * pgo.weight_scale(prob, w)
            lam, resid = pgo.lambda_min(prob, Xg, tol, w)
            r["cert_deficit"] = max(0.0, -(lam - resid)) / tol
            r["lambda_min"] = lam
        if tr.get("outlier_fraction"):
            out = np.zeros(len(prob), bool)
            out[drv.outlier_rows] = True
            lc = prob.j > prob.i + 1
            r["outliers_kept"] = float(np.sum(s["w"][out] >= 0.5))
            r["inliers_dropped"] = float(np.sum(s["w"][lc & ~out] < 0.5))
        seen[s["key"]] = r
    nums = {}
    for r in seen.values():
        for k, v in r.items():
            nums[k] = max(nums.get(k, -np.inf), v) if k != "lambda_min" \
                else min(nums.get(k, np.inf), v)
    if certify:
        nums["uncertified"] = float(sum(not s["certified"]
                                        for s in drv.solves))
    return nums, [True] * len(drv.solves)


def served_numbers(drv) -> tuple[dict, list[bool]]:
    """Worst f64 gradient norm over every reply's trajectory."""
    worst, ok = 0.0, []
    for rep in drv.replies:
        if not rep["ok"]:
            ok.append(False)
            continue
        g = pgo.grad_norm(drv.pool[rep["k"]], np.asarray(rep["T"]))
        worst = max(worst, g)
        ok.append(True)
    return {"grad_T": worst}, ok


NUMBERS = {"library": library_numbers, "served": served_numbers}


def judge(nums: dict, limits: dict) -> list[tuple[str, float, float]]:
    """(name, reading, limit) for every limited number, in the limits'
    order.  A number the run could not read counts as beyond its limit."""
    return [(k, float(nums.get(k, np.inf)), float(v["limit"]))
            for k, v in limits.items()]
