"""Device milliseconds per study of the ops traced under the
``ring_chain_*`` named scopes: the fused ``ring_agg`` aggregation chains
and the glue around the kernel."""


def read(ctx):
    t = ctx.trace
    if not t or "ring_chain" not in t["scope_s"]:
        return None
    return t["scope_s"]["ring_chain"] / t["studies"] * 1e3
