"""The comparison that decides ``correct``.

The program's readings of a cell's first steps (per-step loss, per-leaf
norms of the first gradient as the optimizer took it, per-leaf norms of the
parameters' change as the stored dtype kept it) against the reference's.
Norms are compared leaf by leaf, the gap between the two norms over the
larger of the reference's norm of that leaf and of the median leaf; a
number takes the worst leaf, or the median leaf where small leaves swing
from seed to seed. ``grad_diff`` is the norm of the first gradient's
difference over the reference's norm, over the whole vector: unbiased
rounding moves a norm only in second order, and this moves in first. A
cell's limits file names the numbers it compares.
"""
from __future__ import annotations

import numpy as np

# a leaf whose first reference gradient is under this share of the median
# leaf's moves by round-off alone, and its change is not compared
STILL_LEAF = 1e-3


def _leaf_gaps(prog: dict, ref: dict, leaves) -> dict:
    med = float(np.median([ref[p] for p in leaves]))
    return {p: abs(prog[p] - ref[p]) / max(ref[p], med, 1e-30) for p in leaves}


def numbers(prog: dict, ref: dict) -> dict:
    """{name: (value, detail)} for the readings of the program and of the
    reference (``reference.follow``)."""
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    if lp.shape != lr.shape or not np.all(np.isfinite(lp)):
        loss = float("inf")
    else:
        loss = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    leaves = sorted(ref["grad0"])
    med = float(np.median([ref["grad0"][p] for p in leaves]))
    moving = [p for p in leaves if ref["grad0"][p] >= STILL_LEAF * med]
    out = {"loss_gap": (loss, "max over steps of |loss - ref| / ref")}
    for name, key, keep in (("grad", "grad0", leaves), ("update", "update", moving)):
        gaps = _leaf_gaps(prog[key], ref[key], keep)
        worst = max(gaps, key=gaps.get)
        out[f"{name}_gap"] = (gaps[worst], worst)
        out[f"{name}_gap_median"] = (float(np.median(list(gaps.values()))),
                                     "median leaf")
    diff = sum(float(np.sum((prog["grad0_vec"][p].astype(np.float64)
                             - ref["grad0_vec"][p]) ** 2)) for p in leaves)
    out["grad_diff"] = (float(np.sqrt(diff)) / float(np.sqrt(sum(
        ref["grad0"][p] ** 2 for p in leaves))), "|g0 - g0_ref| / |g0_ref|")
    out["still_leaves"] = (len(leaves) - len(moving), "")
    return out


COUNTS = {"bans": "honest peers banned",
          "checksum_accused": "peers a digest checksum implicated, summed over steps"}


def judge(nums: dict, limits: dict, bans: int,
          accused: int) -> tuple[bool, dict]:
    """(correct, checks) with checks = {name: {"value", "limit"}} for each
    number the cell's limits name: every one under its limit, no honest
    peer banned and none implicated by a digest checksum."""
    checks = {k: {"value": nums[k][0], "limit": v} for k, v in limits.items()}
    checks["bans"] = {"value": bans, "limit": 0}
    checks["checksum_accused"] = {"value": accused, "limit": 0}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
