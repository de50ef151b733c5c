"""JAX runtime: backend compiles plus persistent-cache loads inside the
window, from JAX's monitoring events.  Set-up warms every shape the window
uses, so this should read 0."""


def read(ctx):
    a, b = ctx.rec.compiles_before, ctx.rec.compiles_after
    return float((b["compiles"] - a["compiles"])
                 + (b["cache_hits"] - a["cache_hits"]))
