"""Seconds per study in the program's ``world`` phase (``PhaseTimers``,
span ``repro.world``): ``run_scenario``'s ``build_world``."""


def read(ctx):
    vals = [s["phases"]["world"] for s in ctx.studies
            if "world" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
