"""Shared benchmark scaffolding: the simulation world matching Section V-A
(scaled for CPU; relative D_i/delta_i heterogeneity preserved exactly)."""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.channel.params import ChannelParams
from repro.compile_cache import configure_compile_cache
from repro.core import run_simulation
from repro.data import partition_vehicles, synth_mnist

configure_compile_cache()       # reuse XLA programs across benchmark runs

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")

# CPU-budget scaling knobs (documented in EXPERIMENTS.md §Repro):
N_TRAIN, N_TEST = 6000, 800
SCALE = 0.02              # shrinks every D_i proportionally
NOISE = 0.5
ROUNDS = 40
L_ITERS = 10
LR = 0.03
SEEDS = (0, 1, 2)         # the paper averages 3 experiments


def world(seed=0):
    tr_i, tr_l, te_i, te_l = synth_mnist(n_train=N_TRAIN, n_test=N_TEST,
                                         seed=0, noise=NOISE)
    p = ChannelParams()
    veh = partition_vehicles(tr_i, tr_l, p, seed=seed, scale=SCALE)
    return veh, te_i, te_l, p


def averaged_curves(scheme: str, rounds=ROUNDS, eval_every=4, params=None,
                    seeds=SEEDS, interpretation="mixing", l_iters=L_ITERS,
                    engine="batched"):
    """Mean accuracy/loss curves over seeds (paper: 3 experiments).

    Runs on the vehicle-batched wave engine by default (DESIGN.md §3) —
    identical event semantics to the serial engine, a fraction of the
    dispatches."""
    accs, losses, axes = [], [], []
    for seed in seeds:
        veh, te_i, te_l, p = world(seed)
        r = run_simulation(veh, te_i, te_l, scheme=scheme, rounds=rounds,
                           l_iters=l_iters, lr=LR, eval_every=eval_every,
                           seed=seed, params=params or p,
                           interpretation=interpretation, engine=engine)
        accs.append([a for _, a in r.acc_history])
        losses.append([l for _, l in r.loss_history])
        axes.append([rd for rd, _ in r.acc_history])
    # every seed must evaluate at the same rounds: np.mean would silently
    # average ragged rows element-by-position otherwise (or crash on a
    # ragged array), pairing round-8 accuracy with round-12 accuracy
    if any(ax != axes[0] for ax in axes[1:]):
        raise ValueError(
            "averaged_curves: per-seed eval rounds diverge — "
            + "; ".join(f"seed {s}: {ax}" for s, ax in zip(seeds, axes))
            + " — mean curves would mis-pair rounds; fix eval_every/rounds")
    return (axes[0], np.mean(accs, axis=0).tolist(),
            np.mean(losses, axis=0).tolist())


def save_result(name: str, payload: dict):
    """Write the artifact under ``benchmarks/results/``, creating it."""
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    payload["timestamp"] = time.strftime("%Y-%m-%d %H:%M:%S")
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path
