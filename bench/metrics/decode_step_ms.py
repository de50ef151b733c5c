"""serving.executor: mean wall-clock bracket of the decode steps in the
window, in ms (the engine's ``WallClock``, ending in
``block_until_ready``)."""


def read(ctx):
    b = [s.bracket_s for s in ctx.rec.steps if s.kind == "decode"]
    return 1e3 * sum(b) / len(b) if b else None
