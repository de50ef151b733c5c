"""device: share of the window in which no operation ran on the chip, in %
(1 - the union of device operation intervals over the window span, from
the profiler trace)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
