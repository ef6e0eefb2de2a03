"""Multi-world sweep-engine benchmark (DESIGN.md §15 acceptance artifact).

Runs the Fig. 5 grid — 5 betas x 3 seeds — twice: as ONE ``engine="vmap"``
dispatch of the multi-world sweep program, and as the serial solo
``engine="jit"`` loop it replaces, writing ``BENCH_sweep.json`` with the
wall-clock comparison and a per-world bitwise cross-check (the measured
serial worlds' final parameters must digest-match their vmap slices —
the same pin ``tests/test_vmap_sweep.py`` enforces).

The serial side of the full grid is measured on 3 of the 15 worlds and
extrapolated linearly (flagged ``serial_extrapolated`` in the artifact —
never silently); each serial world compiles its own program where the
sweep compiles once per batch, so both cold and warm timings are reported.

``python -m benchmarks.run sweep``; QUICK=1 swaps in a W=4 quick-k5 grid
(2 betas x 2 seeds) with every serial world measured — the CI smoke
artifact.
"""
from __future__ import annotations

import os
import time

from benchmarks.common import SEEDS, save_result
from repro.checkpointing.checkpoint import tree_digest
from repro.core.scenarios import SweepSpec, run_scenario, run_sweep

BETAS = (0.1, 0.3, 0.5, 0.7, 0.9)


def _grid_spec(quick: bool) -> SweepSpec:
    if quick:
        return SweepSpec(
            scenario="quick-k5", seeds=(0, 1),
            variants=tuple((("channel_overrides", (("beta", b),)),)
                           for b in (0.1, 0.5)),
            overrides=(("rounds", 8),), eval_every=8)
    return SweepSpec(
        scenario="paper-k10", seeds=SEEDS,
        variants=tuple((("channel_overrides", (("beta", b),)),)
                       for b in BETAS),
        overrides=(("rounds", 10), ("l_iters", 30)), eval_every=10)


def run(quick: bool = False) -> dict:
    spec = _grid_spec(quick)
    worlds = spec.worlds()
    W = len(worlds)
    betas = sorted({dict(sc.channel_overrides).get("beta", 0.5)
                    for sc, _ in worlds})
    print(f"sweep grid: W={W} worlds ({len(betas)} betas x "
          f"{len(spec.seeds)} seeds) on {worlds[0][0].name}")

    t0 = time.perf_counter()
    vm = run_sweep(spec)
    cold_vmap = time.perf_counter() - t0
    t0 = time.perf_counter()
    vm = run_sweep(spec)
    warm_vmap = time.perf_counter() - t0
    print(f"  vmap one-dispatch: cold {cold_vmap:6.1f}s  "
          f"warm {warm_vmap:6.1f}s")

    # serial baseline: the solo jit loop the sweep replaces.  The full
    # grid measures a 3-world subset (one per beta of the first three
    # variants, first seed) and extrapolates — flagged, never silent.
    n_serial = W if quick else min(3, W)
    serial_idx = (list(range(W)) if quick
                  else [i * len(spec.seeds) for i in range(n_serial)])
    cold_s = warm_s = 0.0
    digests_match = True
    for i in serial_idx:
        sc, seed = worlds[i]
        t0 = time.perf_counter()
        r = run_scenario(sc, seed=seed, engine="jit",
                         eval_every=spec.eval_every)
        cold_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        r = run_scenario(sc, seed=seed, engine="jit",
                         eval_every=spec.eval_every)
        dt_w = time.perf_counter() - t0
        warm_s += dt_w
        same = (tree_digest(r.final_params)
                == tree_digest(vm[i].final_params))
        digests_match = digests_match and same
        print(f"  serial world {i}: warm {dt_w:5.1f}s, "
              f"bitwise={'yes' if same else 'NO'}")
    scale = W / n_serial
    payload = {
        "scenario": worlds[0][0].name, "n_worlds": W,
        "betas": [float(b) for b in betas],
        "seeds": list(spec.seeds),
        "rounds": worlds[0][0].rounds, "l_iters": worlds[0][0].l_iters,
        "vmap_cold_s": round(cold_vmap, 2),
        "vmap_warm_s": round(warm_vmap, 2),
        "serial_measured_worlds": n_serial,
        "serial_extrapolated": n_serial < W,
        "serial_cold_s": round(cold_s * scale, 2),
        "serial_warm_s": round(warm_s * scale, 2),
        "speedup_cold": round(cold_s * scale / cold_vmap, 2),
        "speedup_warm": round(warm_s * scale / warm_vmap, 2),
        "bitwise_vs_serial": bool(digests_match),
        "mean_final_accuracy": round(
            float(sum(r.final_accuracy() for r in vm)) / W, 4),
    }
    print(f"  serial loop ({'extrapolated ' if n_serial < W else ''}"
          f"W={W}): cold {payload['serial_cold_s']:6.1f}s  "
          f"warm {payload['serial_warm_s']:6.1f}s -> speedup "
          f"{payload['speedup_cold']}x cold / "
          f"{payload['speedup_warm']}x warm, bitwise="
          f"{payload['bitwise_vs_serial']}")
    if not digests_match:
        raise RuntimeError(
            "sweep bench: a serial world's final parameters diverged "
            "bitwise from its vmap slice — the DESIGN.md §15 conformance "
            "pin is broken; do not publish this artifact")
    path = save_result("BENCH_sweep_quick" if quick else "BENCH_sweep",
                       payload)
    print(f"wrote {path}")
    return payload


if __name__ == "__main__":
    run(quick=bool(int(os.environ.get("QUICK", "0"))))
