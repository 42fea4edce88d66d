"""The comparison that decides ``correct``.  Each number compared has a
limit of its own (from the cell's file); every run prints each number
beside its limit.
"""

from __future__ import annotations

import math

import numpy as np


def worst_leaf_gap(prog: dict, ref: dict, keep: dict = None):
    """The worst leaf's gap between the program's norm and the
    reference's (a gap of norms, not the norm of a difference), measured
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger.  ``keep`` masks leaves out (same layout).
    Returns (gap, leaf label)."""
    if sorted(prog) != sorted(ref):
        raise ValueError(f"leaves differ: {sorted(prog)} / {sorted(ref)}")
    all_ref = np.concatenate([np.asarray(ref[k], float) for k in sorted(ref)])
    median = float(np.median(all_ref))
    worst, where = 0.0, ""
    for name in sorted(ref):
        r = np.asarray(ref[name], float)
        p = np.asarray(prog[name], float)
        if p.shape != r.shape:
            raise ValueError(f"{name}: {p.shape} against {r.shape}")
        gap = np.abs(p - r) / np.maximum(r, median)
        gap = np.where(np.isfinite(gap), gap, np.inf)
        if keep is not None:
            gap = np.where(np.asarray(keep[name], bool), gap, 0.0)
        i = int(np.argmax(gap))
        if gap[i] > worst:
            worst, where = float(gap[i]), f"{name}[{i}]"
    return worst, where


def moving_leaves(ref_grad: dict, floor: float = 1e-3) -> dict:
    """Leaves whose reference gradient is not nought to rounding: at
    least ``floor`` of the median leaf's.  The others move under Adam by
    round-off alone and are left out of the change."""
    all_ref = np.concatenate([np.asarray(v, float) for v in ref_grad.values()])
    cut = floor * float(np.median(all_ref))
    return {k: np.asarray(v, float) >= cut for k, v in ref_grad.items()}


def train_numbers(prog: dict, ref: dict) -> dict:
    """name -> (value, note), the numbers a training cell is held to.
    ``prog`` / ``ref``: ``grad1_norm`` and ``change_norm`` as the
    reference returns them."""
    return {
        "grad1_norm_gap": worst_leaf_gap(prog["grad1_norm"],
                                         ref["grad1_norm"]),
        "change_norm_gap": worst_leaf_gap(
            prog["change_norm"], ref["change_norm"],
            keep=moving_leaves(ref["grad1_norm"])),
    }


def loss_gaps(prog: dict, ref: dict) -> list:
    """Each step's relative loss gap.  Printed, not compared: at
    initialisation no control or fault reads three times the program's
    own gap on every seed (PERF.md section 2)."""
    return [abs(lp - lr) / abs(lr) if math.isfinite(lp) else math.inf
            for lp, lr in zip(prog["losses"], ref["losses"])]


def judge(numbers: dict, limits: dict):
    """-> (correct, compared) where compared is name -> {value, limit}
    (and ``at`` where the number names its worst place).  A number
    without a limit in the cell's file is an error: nothing is compared
    by guess."""
    compared, correct = {}, True
    for name, (value, note) in numbers.items():
        limit = limits[name]
        ok = bool(value <= limit)
        correct = correct and ok
        compared[name] = {"value": value, "limit": limit, "at": note}
    return correct, compared


def stderr_lines(compared: dict) -> str:
    return "\n".join(
        f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g}"
        f" ({c.get('at', '')})" for name, c in compared.items())
