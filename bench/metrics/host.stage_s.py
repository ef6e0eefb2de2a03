"""Seconds per study in the engine's ``stage`` phase (``PhaseTimers``)."""


def read(ctx):
    vals = [s["phases"]["stage"] for s in ctx.studies
            if "stage" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
