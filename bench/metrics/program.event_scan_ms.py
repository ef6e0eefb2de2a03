"""Device milliseconds per study of the ops traced under the
``event_scan_*`` named scopes."""


def read(ctx):
    t = ctx.trace
    if not t or "event_scan" not in t["scope_s"]:
        return None
    return t["scope_s"]["event_scan"] / t["studies"] * 1e3
