"""The comparison that decides ``correct``: the program's first rounds
against the reference's, number by number, each against its limit.

Numbers (each a relative gap; ``limits/<cell>.json`` says which are
compared and with what limit):

- ``loss0_gap``: the first round's per-client losses;
- ``loss_gap``: every checked round's per-client losses;
- ``grad_gap``: the norm of each leaf of the global first moment M after
  the first round (the gradient as the server's optimizer holds it);
- ``update_gap``: the norm of each leaf of W's change over the checked
  rounds.

Leaf numbers are taken at the worst leaf: the gap between the program's
norm and the reference's, over the reference's norm of that leaf or of
the median leaf, whichever is larger.  ``update_gap`` leaves out the
leaves whose reference gradient norm is under a thousandth of the
median leaf's: they move under Adam by round-off alone.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
TINY_GRAD = 1e-3


def _loss_gap(p, r) -> float:
    return max(abs(a - b) / abs(b) for a, b in zip(p, r))


def _leaf_gap(p, r, keep) -> float:
    p, r = np.asarray(p, np.float64)[keep], np.asarray(r, np.float64)[keep]
    den = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / den))


def worst_leaves(prog: dict, ref: dict, key: str, top: int = 3) -> list:
    """``[(leaf index, program norm, reference norm)]`` of the ``top``
    leaves of reading ``key`` with the largest gaps."""
    p = np.asarray(prog[key], np.float64)
    r = np.asarray(ref[key], np.float64)
    gap = np.abs(p - r) / np.maximum(r, np.median(r))
    return [(int(i), float(p[i]), float(r[i]))
            for i in np.argsort(-gap)[:top]]


def numbers(prog: dict, ref: dict) -> dict:
    """The gaps between two sets of readings (``reference.run``'s keys)."""
    grad = np.asarray(ref["m1_norms"], np.float64)
    moving = grad >= TINY_GRAD * np.median(grad)
    out = {
        "loss0_gap": _loss_gap(prog["losses"][0], ref["losses"][0]),
        "loss_gap": max(_loss_gap(p, r) for p, r in
                        zip(prog["losses"], ref["losses"])),
        "grad_gap": _leaf_gap(prog["m1_norms"], ref["m1_norms"],
                              np.ones_like(moving)),
        "update_gap": _leaf_gap(prog["dw_norms"], ref["dw_norms"], moving),
    }
    return {k: (v if math.isfinite(v) else float("inf"))
            for k, v in out.items()}


def limits(cell: str) -> dict:
    """``{number: limit}`` for ``cell``; empty where none is set yet."""
    path = HERE / "limits" / f"{cell}.json"
    if not path.exists():
        return {}
    return {k: v["limit"] for k, v in json.loads(path.read_text()).items()}


def judge(nums: dict, lims: dict):
    """``(correct, checks)``: correct when at least one number is compared
    and every compared number is within its limit."""
    checks = {k: {"value": nums[k], "limit": lims[k]} for k in lims}
    ok = bool(checks) and all(c["value"] <= c["limit"]
                              for c in checks.values())
    return ok, checks
