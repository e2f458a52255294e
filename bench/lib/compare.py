"""The comparison that decides ``correct`` for a training cell.

Readings are plain floats: per-step losses, and per-leaf norms keyed by
(owner, leaf index).  Norms are compared by the worst leaf: the gap between
the program's norm and the reference's, over the larger of the reference's
norm of that leaf and of the median leaf.  A leaf whose first gradient in
the reference is under a thousandth of the median leaf's is nought to
rounding (a bias under softmax, say): under Adam it moves by round-off
alone, so the caller leaves it out of every comparison (``nought_leaves``).
"""
from __future__ import annotations

import numpy as np

NOUGHT = 1e-3


def nought_leaves(grad: dict) -> set:
    """Keys of ``grad`` (reference first-gradient norms) under a thousandth
    of the median leaf's."""
    med = float(np.median(list(grad.values())))
    return {k for k, v in grad.items() if v < NOUGHT * med}


def leaf_gaps(prog: dict, ref: dict, left_out=frozenset()) -> np.ndarray:
    """Per-leaf gaps of the leaves not ``left_out``; a leaf missing from
    the program's readings reads as a full gap."""
    keys = sorted(set(ref) - set(left_out))
    r = np.array([ref[k] for k in keys], np.float64)
    p = np.array([prog.get(k, np.nan) for k in keys], np.float64)
    if not len(r):
        return r
    gap = np.abs(p - r) / np.maximum(r, float(np.median(r)))
    return np.where(np.isnan(gap), 1.0, gap)


def worst_leaf(prog: dict, ref: dict, left_out=frozenset()) -> float:
    gap = leaf_gaps(prog, ref, left_out)
    return float(np.max(gap)) if len(gap) else float("nan")



def loss_gap(prog: list, ref: list) -> float:
    """Largest relative gap of the per-step losses."""
    if len(prog) < len(ref):
        return float("inf")
    return float(max(abs(a - b) / abs(b) for a, b in zip(prog, ref)))


def rows(numbers: dict, limits: dict) -> list:
    """[[name, value, limit], ...] in the order of ``limits``; a number
    absent from ``numbers`` or not finite fails its limit."""
    out = []
    for name, limit in limits.items():
        v = numbers.get(name, float("nan"))
        out.append([name, float(v), float(limit)])
    return out


def passed(table: list) -> bool:
    return all(np.isfinite(v) and v <= lim for _, v, lim in table)
