"""Seconds per study in the engine's ``eval`` phase (``PhaseTimers``)."""


def read(ctx):
    vals = [s["phases"]["eval"] for s in ctx.studies
            if "eval" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
