"""Benchmark harness — one entry per paper artifact + system extras.

  fig3_fig4  — accuracy & loss vs rounds, MAFL vs AFL (Figs. 3-4)
  fig5       — beta sweep at 10 rounds (Fig. 5)
  kernels    — Pallas kernel micro + v5e roofline projections (CSV rows)
  roofline   — render the dry-run roofline tables (deliverable g)
  scenario   — run a named scenario from the registry (DESIGN.md §8):
               ``python -m benchmarks.run scenario fleet-k100 [rounds]``
  fleet      — mega-fleet engine comparison -> BENCH_fleet.json
               (DESIGN.md §9): ``python -m benchmarks.run fleet
               [scenario] [rounds]``; QUICK=1 smokes quick-k5 through
               serial/batched/jit
  corridor   — multi-RSU corridor engine comparison ->
               BENCH_corridor.json (DESIGN.md §10): serial reference vs
               engine='corridor' at r4-k400 direct + r8-k4000;
               QUICK=1 smokes corridor-quick-r2-k8
  selection  — admission-policy comparison -> BENCH_selection.json
               (DESIGN.md §11): admit-all vs weighted-topk vs budget
               ms/round on fleet-k1000 at equal rounds; QUICK=1 smokes
               quick-k5 with topk through serial/batched/jit
  perf       — flat-parameter fast-path comparison -> BENCH_perf.json at
               the REPO ROOT (DESIGN.md §12): batched/jit-pytree/jit-flat
               (+bf16) ms/round on fleet-k1000 + corridor-r4-k400 +
               fleet-k10000, consolidating the other BENCH headline
               numbers; QUICK=1 runs the smoke lanes only.
               ``perf check`` compares fresh QUICK lanes against the
               committed baseline (2x threshold, CI perf-regression job);
               ``perf k10000-smoke`` compile-smokes fleet-k10000;
               ``perf telemetry`` measures the metrics=on/off overhead
               (DESIGN.md §14) and merges it into BENCH_perf.json.
  faults     — fault-injection comparison -> BENCH_faults.json
               (DESIGN.md §16): clean-vs-flaky ms/round overhead on
               fleet-k1000 (exit 1 past the +10% bar) + accuracy under
               churn per admission policy; QUICK=1 smokes quick-k5
  sweep      — multi-world vmap sweep vs serial jit loop ->
               BENCH_sweep.json (DESIGN.md §15): the Fig. 5 grid
               (5 betas x 3 seeds) as ONE dispatch, wall-clock compared
               against the solo-jit rerun loop with a bitwise
               cross-check; QUICK=1 smokes a W=4 quick-k5 grid

All committed (non-quick) BENCH_*.json artifacts are also copied to the
repo root, where the perf-trajectory tracker reads them.

``python -m benchmarks.run``            runs everything (QUICK=1 shrinks the
simulation rounds for CI-speed smoke runs).
``python -m benchmarks.run fig5`` etc.  runs one.
"""
from __future__ import annotations

import os
import sys
import time


def run_scenario_cmd(argv) -> None:
    from repro.core.scenarios import list_scenarios, run_scenario
    if not argv:
        print("available scenarios:", ", ".join(list_scenarios()))
        return
    name = argv[0]
    kw = {"rounds": int(argv[1])} if len(argv) > 1 else {}
    t0 = time.time()
    r = run_scenario(name, progress=lambda rd, a: print(
        f"  round {rd}: acc={a:.3f}"), **kw)
    dt = time.time() - t0
    print(f"{name}: {len(r.rounds)} rounds in {dt:.1f}s "
          f"({len(r.rounds) / max(dt, 1e-9):.2f} rounds/s), "
          f"final acc {r.final_accuracy():.3f}")


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    quick = bool(int(os.environ.get("QUICK", "0")))
    t0 = time.time()

    if which == "scenario":
        run_scenario_cmd(sys.argv[2:])
        return

    if which == "fleet":
        from benchmarks import fleet_bench
        argv = sys.argv[2:]
        kw = {}
        if argv:
            kw["scenario"] = argv[0]
        if len(argv) > 1:
            kw["rounds"] = int(argv[1])
        fleet_bench.run(quick=quick, **kw)
        return

    if which == "corridor":
        from benchmarks import corridor_bench
        argv = sys.argv[2:]
        kw = {"rounds": int(argv[0])} if argv else {}
        corridor_bench.run(quick=quick, **kw)
        return

    if which == "selection":
        from benchmarks import selection_bench
        argv = sys.argv[2:]
        kw = {"rounds": int(argv[0])} if argv else {}
        selection_bench.run(quick=quick, **kw)
        return

    if which == "faults":
        from benchmarks import faults_bench
        argv = sys.argv[2:]
        kw = {"rounds": int(argv[0])} if argv else {}
        sys.exit(faults_bench.main(quick=quick, **kw))

    if which == "sweep":
        from benchmarks import sweep_bench
        sweep_bench.run(quick=quick)
        return

    if which == "perf":
        from benchmarks import perf_bench
        sys.exit(perf_bench.main(sys.argv[2:]))

    if which in ("all", "kernels"):
        print("== kernel microbenchmarks ==")
        from benchmarks import kernel_micro
        kernel_micro.run()

    if which in ("all", "roofline"):
        print("\n== roofline (from dry-run artifacts) ==")
        from benchmarks import roofline_report
        roofline_report.run()

    if which in ("all", "fig3", "fig4", "fig3_fig4"):
        print("\n== Figs. 3-4: MAFL vs AFL accuracy/loss ==")
        from benchmarks import fig3_fig4_accuracy_loss
        fig3_fig4_accuracy_loss.run(quick=quick)

    if which in ("all", "fig5"):
        print("\n== Fig. 5: beta sweep ==")
        from benchmarks import fig5_beta_sweep
        fig5_beta_sweep.run(quick=quick)

    if which in ("all", "ablation"):
        print("\n== Beyond-paper: scheme ablation ==")
        from benchmarks import ablation_schemes
        ablation_schemes.run(quick=quick)

    if which == "all":
        print("\n== Mega-fleet engine comparison ==")
        from benchmarks import fleet_bench
        fleet_bench.run(quick=quick)

    if which == "all":
        print("\n== Corridor engine comparison ==")
        from benchmarks import corridor_bench
        corridor_bench.run(quick=quick)

    if which == "all":
        print("\n== Selection policy comparison ==")
        from benchmarks import selection_bench
        selection_bench.run(quick=quick)

    if which == "all":
        print("\n== Fault-injection comparison ==")
        from benchmarks import faults_bench
        faults_bench.run(quick=quick)

    if which == "all":
        print("\n== Multi-world sweep engine comparison ==")
        from benchmarks import sweep_bench
        sweep_bench.run(quick=quick)

    if which == "all":
        print("\n== Flat fast-path comparison ==")
        from benchmarks import perf_bench
        perf_bench.run(quick=quick)

    print(f"\ntotal {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
