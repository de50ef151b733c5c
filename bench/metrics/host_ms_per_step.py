"""serving.engine: mean host time per engine step in the window, in ms —
the harness's wall time around ``Cluster.step()`` less the engine's own
wall-clock bracket of the jitted step (which ends in
``block_until_ready``): scheduling, sampling, bookkeeping and dispatch."""


def read(ctx):
    steps = [s for s in ctx.rec.steps if s.kind is not None]
    if not steps:
        return None
    return 1e3 * sum((s.end - s.start) - s.bracket_s for s in steps) / len(
        steps)
