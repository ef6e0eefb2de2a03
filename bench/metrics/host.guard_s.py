"""Seconds per study in the program's ``guard`` phase (``PhaseTimers``,
span ``repro.guard``): the device-to-host fetch of the event trace and the
divergence guards, between ``run`` and ``eval``."""


def read(ctx):
    vals = [s["phases"]["guard"] for s in ctx.studies
            if "guard" in s["phases"]]
    return sum(vals) / len(vals) if vals else None
