"""The comparisons that decide ``correct``: each returns the numbers that
are held to the cell's limits (``bench/limits/<cell>.json``).

Training, over the first three rounds that set-up drives through the
window's own call: the largest gap of a round's loss; and, by the worst
leaf, the gap between the program's and the reference's norm of the
round-1 update over the learning rate (the first gradient as SGD takes
it) and of the change after three rounds, each against the reference's
norm of that leaf or of the median leaf, whichever is larger. Leaves whose
reference update is under a thousandth of the median leaf's move by
round-off alone and are left out.

Serving: the widest gap by which a served greedy token's logit lies below
the reference's best, over a sample of finished requests.
"""
from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List

NOUGHT = 1e-3


def widest(gaps: Iterable[float]) -> float:
    """The largest gap; infinite where any gap is not a number."""
    gaps = list(gaps)
    if not gaps or not all(math.isfinite(g) for g in gaps):
        return math.inf
    return max(gaps)


def kept_leaves(ref_update: Dict[str, float]) -> List[str]:
    med = statistics.median(ref_update.values())
    return sorted(k for k, v in ref_update.items() if v >= NOUGHT * med)


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               kept: List[str]) -> float:
    med = statistics.median(ref[k] for k in kept)
    return widest(abs(prog[k] - ref[k]) / max(ref[k], med) for k in kept)


def train_numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog``/``ref``: ``{"losses": [3], "update": {leaf: norm},
    "change": {leaf: norm}}``."""
    kept = kept_leaves(ref["update"])
    return {
        "loss_gap": widest(abs(a - b) for a, b in zip(prog["losses"],
                                                       ref["losses"])),
        "grad_gap": worst_leaf(prog["update"], ref["update"], kept),
        "change_gap": worst_leaf(prog["change"], ref["change"], kept),
    }


def serve_numbers(gaps: List[float]) -> Dict[str, float]:
    return {"served_gap": widest(gaps)}
