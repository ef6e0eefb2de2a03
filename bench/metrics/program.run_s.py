"""Seconds per study in the engine's ``run`` phase (``PhaseTimers``)."""


def read(ctx):
    vals = [s["phases"]["run"] for s in ctx.studies
            if "run" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
