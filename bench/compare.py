"""The comparison that decides `correct`: one study of the program against
the plain reference at the same seed and learning rate.

Four numbers, each held to its configuration's limit:

- ``trace``: arrivals whose (vehicle, serving RSU) differ from the
  reference's event timeline, plus any difference in their count.  The
  timeline never depends on training, so it must match exactly (limit 0).
- ``model``: the final global model's median leaf, where a leaf reads the
  distance between the program's leaf and the reference's over how far the
  reference moved that leaf from the model every vehicle first downloads
  (the initial weights at the storage width), or over the median leaf's
  move where that is larger.  Leaves the reference moves by less than a
  thousandth of the median leaf are left out.  The median is steady from
  seed to seed and catches a fault spread over the model.
- ``model_worst``: the same readings' worst leaf, which catches a fault
  confined to one leaf (a leaf left unmoved reads about 1).  With bf16
  uploads its sound readings carry the odd flipped rounding, so its limit
  sits between those and the faults'.
- ``eval_loss``: the largest gap in test-set loss over the evaluated
  snapshots, which must fall on the same rounds.
"""
from __future__ import annotations

import math

import numpy as np

EXCLUDE_BELOW = 1e-3      # of the median leaf's move


def trace_gap(prog, ref) -> float:
    return float(sum(a != b for a, b in zip(prog, ref))
                 + abs(len(prog) - len(ref)))


def leaf_gaps(prog: dict, ref: dict, start: dict) -> dict:
    """Per leaf ``(gap, move)``: the norm of the program's leaf minus the
    reference's, and of the reference's leaf minus ``start``'s; leaves
    left out by the rule above are not listed."""
    moves = {k: float(np.linalg.norm(np.asarray(ref[k], np.float64)
                                     - np.asarray(start[k], np.float64)))
             for k in ref}
    med = float(np.median(list(moves.values())))
    return {k: (float(np.linalg.norm(np.asarray(prog[k], np.float64)
                                     - np.asarray(ref[k], np.float64))),
                move)
            for k, move in moves.items() if move >= EXCLUDE_BELOW * med}


def leaf_readings(prog: dict, ref: dict, start: dict) -> dict:
    """Each leaf's gap over its move, the move floored at the median
    leaf's."""
    gaps = leaf_gaps(prog, ref, start)
    med = float(np.median([m for _, m in gaps.values()]))
    return {k: g / max(m, med) for k, (g, m) in gaps.items()}


def model_gaps(prog: dict, ref: dict, start: dict) -> tuple:
    """``(median leaf, worst leaf)`` of ``leaf_readings``."""
    vals = list(leaf_readings(prog, ref, start).values())
    return tuple(v if math.isfinite(v) else math.inf
                 for v in (float(np.median(vals)), float(np.max(vals))))


def eval_gap(prog, ref) -> float:
    """``prog``/``ref``: ``[(round, loss)]``."""
    if [r for r, _ in prog] != [r for r, _ in ref]:
        return math.inf
    gap = max((abs(a - b) for (_, a), (_, b) in zip(prog, ref)), default=0.0)
    return gap if math.isfinite(gap) else math.inf


def numbers(prog: dict, ref: dict, start: dict) -> dict:
    """``prog``/``ref``: ``{"trace": [(vehicle, rsu)], "final": {leaf:
    array}, "losses": [(round, loss)]}``."""
    mid, worst = model_gaps(prog["final"], ref["final"], start)
    return {"trace": trace_gap(prog["trace"], ref["trace"]),
            "model": mid,
            "eval_loss": eval_gap(prog["losses"], ref["losses"]),
            "model_worst": worst}


def within(nums: dict, limits: dict) -> bool:
    return all(nums[k] <= limits[k] for k in limits)
