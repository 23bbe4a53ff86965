"""How the program's readings are held against the reference's.

Norms are compared leaf by leaf, and a leaf's gap is measured against the
reference's norm of that leaf or of the median leaf, whichever is larger,
since some leaves' norms are all but zero.  Leaves whose raw reference
gradient is under a thousandth of the median leaf's are left out: under
Adam they move by round-off alone.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

NOUGHT = 1e-3


def kept_leaves(raw_grad: np.ndarray) -> np.ndarray:
    raw = np.asarray(raw_grad, np.float64)
    return raw >= NOUGHT * np.median(raw)


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: np.ndarray) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog[keep])):
        return float("inf")
    floor = np.median(ref[keep])
    den = np.maximum(ref, floor)
    return float(np.max(np.abs(prog - ref)[keep] / den[keep]))


def loss_gap(prog, ref) -> float:
    prog = np.asarray(prog, np.float64)
    ref = np.asarray(ref, np.float64)
    if not np.all(np.isfinite(prog)):
        return float("inf")
    return float(np.max(np.abs(prog - ref) / np.abs(ref)))


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """The numbers a train cell compares."""
    keep = kept_leaves(ref["raw_grad"])
    return {"loss_gap": loss_gap(prog["losses"], ref["losses"]),
            "grad_gap": worst_leaf_gap(prog["first_grad"], ref["first_grad"],
                                       keep),
            "change_gap": worst_leaf_gap(prog["change"], ref["change"], keep),
            "leaves_left_out": float(np.sum(~keep))}


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """{name: {"value", "limit"}} for every number that has a limit, and
    whether all are within theirs.  A number with no limit is not judged;
    a cell with no limits at all is not correct."""
    checks = {}
    ok = bool(limits)
    for name, limit in limits.items():
        v = numbers.get(name)
        val = float("nan") if v is None else float(v)
        checks[name] = {"value": val, "limit": float(limit)}
        if not (val <= float(limit)):
            ok = False
    return {"correct": ok, "checks": checks}


def format_checks(checks: Dict[str, dict]) -> str:
    return "\n".join(f"check {k}: {v['value']!r} (limit {v['limit']!r})"
                     for k, v in checks.items())

