"""Readings that the comparison's limits are set from, for one
configuration and traffic mix, all in one process:

- the program: one study per seed at the configuration's learning rate,
  compared with the plain reference (the lower readings);
- the control: one step below the configuration's stated precision, put
  in the program's place and compared the same way (the upper readings).
  Where the program has that step as a path of its own (a bf16 snapshot
  ring for an f32 configuration) the program with it switched on is the
  control; below bf16 it has none, and the reference computed with fp8
  storage stands in;
- the program at a lower matmul precision than the stated one (its own
  path: ``jax.default_matmul_precision``), the control of the matmul axis;
- a training fault planted in the program (``FAULTS``), read the same way.

    python3 bench/control.py --config fleet-k10000 --traffic eval10 \\
        --seeds 1,2,3 --control-seeds 1,2,3 --fault half_batch \\
        --fault-seeds 1,2,3 --matmul default,high --matmul-seeds 1,2,3

Prints one JSON line per reading and a last line with the largest
program reading and the smallest control reading of each number.  Needs
a TPU; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def frozen_step(params, images, labels, lr):
    """A local step that returns its state unchanged."""
    import jax.numpy as jnp
    return params, jnp.float32(0.0)


def half_batch(orig):
    """A local step that leaves out half of each minibatch and takes the
    mean over the rest."""
    def step(params, images, labels, lr):
        b = images.shape[1] // 2
        return orig(params, images[:, :b], labels[:, :b], lr)
    return step


FAULTS = {"state_unchanged": lambda orig: frozen_step,
          "half_batch": half_batch}


def program_answer(cfg, traffic, seed, ring_dtype=None, matmul=None):
    import jax

    import run
    from repro.core.scenarios import run_scenario
    sc = run.scenario(cfg)
    if ring_dtype:
        sc = dataclasses.replace(sc, ring_dtype=ring_dtype)
    with jax.default_matmul_precision(matmul or cfg["precision"]["matmul"]):
        return run.answer(run_scenario(sc, engine=cfg["engine"], seed=seed,
                                       eval_every=traffic["eval_every"],
                                       lr=cfg["scenario"]["lr"]))


def readings(cfg, traffic, seed, control: bool, leaves=None,
             fault=None, matmul=None) -> dict:
    """Numbers of the program (``control=False``), of the program with
    ``FAULTS[fault]`` planted or at matmul precision ``matmul``, or of the
    control against the reference at ``seed``; ``leaves``, a dict,
    receives the per-leaf ``(gap, move)`` of the final model."""
    import compare
    import reference
    stated = cfg["scenario"]["ring_dtype"]
    lower = reference.NEXT_LOWER[stated]
    world = reference.World(cfg, seed)
    lr, every = cfg["scenario"]["lr"], traffic["eval_every"]
    want = world.answer(lr, every, stated)
    if fault:
        from repro.core import client
        orig = client._local_scan
        client._local_scan = FAULTS[fault](orig)
        try:
            got = program_answer(cfg, traffic, seed)
        finally:
            client._local_scan = orig
    elif not control:
        got = program_answer(cfg, traffic, seed, matmul=matmul)
    elif lower == "bf16":
        got = program_answer(cfg, traffic, seed, ring_dtype="bf16")
    else:
        got = world.answer(lr, every, lower)
    start = world.start(stated)
    if leaves is not None:
        leaves.update(compare.leaf_gaps(got["final"], want["final"], start))
    return compare.numbers(got, want, start)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--matmul", default="",
                    help="lower matmul precisions to read, comma-separated")
    ap.add_argument("--matmul-seeds", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    import run
    run.require_devices(1)
    run.configure_cache()
    matmul = {f"matmul_{p}": p for p in args.matmul.split(",") if p}
    cfg = run.load_json("bench", "configs", args.config + ".json")
    traffic = run.load_json("bench", "traffic", args.traffic + ".json")
    worst, least = {}, {}
    for kind, seeds in (("program", args.seeds),
                        ("control", args.control_seeds),
                        (args.fault, args.fault_seeds),
                        *((k, args.matmul_seeds) for k in matmul)):
        for seed in (int(s) for s in seeds.split(",") if s):
            t0 = time.perf_counter()
            leaves = {}
            nums = readings(cfg, traffic, seed, kind == "control", leaves,
                            fault=kind if kind in FAULTS else None,
                            matmul=matmul.get(kind))
            print(json.dumps({"kind": kind, "seed": seed, "numbers": nums,
                              "leaves": leaves,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            for k, v in nums.items():
                if kind == "program":
                    worst[k] = max(worst.get(k, 0.0), v)
                else:
                    least.setdefault(kind, {})
                    least[kind][k] = min(least[kind].get(k, float("inf")), v)
    print(json.dumps({"program_max": worst,
                      **{f"{kind}_min": v for kind, v in least.items()}}))


if __name__ == "__main__":
    main()
