"""Percent of the traced window in which no operation ran on the device:
1 minus the union of the device ops' intervals over the studies' span."""


def read(ctx):
    t = ctx.trace
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
