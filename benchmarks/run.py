"""Benchmark harness — one entry per paper artifact + system extras.

  fig3_fig4  — accuracy & loss vs rounds, MAFL vs AFL (Figs. 3-4)
  fig5       — beta sweep at 10 rounds (Fig. 5)
  ablation   — beyond-paper aggregation-scheme ablation
  kernels    — Pallas kernel micro + v5e roofline projections (CSV rows)
  roofline   — render the dry-run roofline tables (deliverable g)
  scenario   — run a named scenario from the registry (DESIGN.md §8):
               ``python -m benchmarks.run scenario fleet-k100 [rounds]``

Speed is measured on the chip by ``bench/run.py`` (PERF.md); these entries
claim no speed.

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

    print(f"\ntotal {time.time() - t0:.0f}s")


if __name__ == "__main__":
    main()
