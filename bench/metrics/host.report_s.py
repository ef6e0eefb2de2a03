"""Seconds per study in the program's ``report`` phase (``PhaseTimers``,
span ``repro.report``): the run report after ``eval`` (summaries,
channels, memory probes)."""


def read(ctx):
    vals = [s["phases"]["report"] for s in ctx.studies
            if "report" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
