"""Device milliseconds per study of the ops traced under the
``wave_train_*`` named scopes."""


def read(ctx):
    t = ctx.trace
    if not t or "wave_train" not in t["scope_s"]:
        return None
    return t["scope_s"]["wave_train"] / t["studies"] * 1e3
