"""Seconds per study in the engine's ``plan`` phase (``PhaseTimers``)."""


def read(ctx):
    vals = [s["phases"]["plan"] for s in ctx.studies
            if "plan" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
