"""Work a study needs, counted from the configuration's shapes and the
event trace: the model's FLOPs and the bytes ``ring_agg``'s chains must
move.  The model's own counts (FLOPs per sample, the packed width P) come
from the module the configuration names (``bench/models``).  Nothing here
reads how the program implements either, so a count stays the same
whatever computes the work."""
from __future__ import annotations

import models


def study_flops(cfg: dict, eval_every: int) -> int:
    """Model FLOPs of one study: every trained sample of every arrival and
    every evaluated test sample."""
    sc, model = cfg["scenario"], models.of(cfg)
    trained = sc["rounds"] * sc["l_iters"] * minibatch(sc)
    evals = len(eval_rounds(sc["rounds"], eval_every))
    return (trained * model.train_flops(cfg)
            + evals * sc["n_test"] * model.forward_flops(cfg))


def minibatch(sc: dict) -> int:
    """The fleet's one minibatch size: the smallest shard (vehicle 1 has
    the least data, D_1 = 6,000 before scaling), at most 128."""
    smallest = max(int(6000 * sc["scale"]), 8)
    if sc["max_per_vehicle"] is not None:
        smallest = min(smallest, sc["max_per_vehicle"])
    return min(128, smallest)


def eval_rounds(rounds: int, eval_every: int) -> list:
    return [r for r in range(1, rounds + 1)
            if r % eval_every == 0 or r == rounds]


def ring_agg_bytes(cfg: dict, veh, rsu, eval_every: int) -> tuple:
    """Least memory traffic of a study's aggregation: every upload read once
    at its stored width, plus the f32 model of an RSU read and written once
    per chain of uploads between the points where the plan needs that
    model whole: a later download reads it, the cohorts reconcile, the
    test set is evaluated, or the study ends.

    ``veh``/``rsu`` are the per-round vehicle and serving RSU of the event
    trace.  Returns ``(bytes read, bytes written)``."""
    sc = cfg["scenario"]
    M, R = sc["rounds"], sc["n_rsus"]
    P = models.of(cfg).packed_params(cfg)
    width = {"f32": 4, "bf16": 2}[sc["ring_dtype"]]
    last, needed = {}, set()
    for r, v in enumerate(veh):
        if v in last:
            needed.add(last[v] + 1)          # the post-round row it reads
        last[v] = r
    evals = set(eval_rounds(M, eval_every))
    every = sc["reconcile_every"] if R > 1 else 0
    chains, open_ = 0, set()
    for r in range(M):
        j = int(rsu[r])
        open_.add(j)
        b = r + 1
        if b in needed:
            chains += j in open_
            open_.discard(j)
        if b in evals or b == M or (every and b % every == 0):
            chains += len(open_)
            open_.clear()
    return M * P * width + chains * 4 * P, chains * 4 * P
