"""Seconds per study outside the engine's phases: the world build
(``core/scenarios.build_world``, ``data/partition``), the study wall
clock minus the sum of ``report.phases``."""


def read(ctx):
    if not ctx.studies:
        return None
    return sum(s["wall"] - sum(s["phases"].values())
               for s in ctx.studies) / len(ctx.studies)
