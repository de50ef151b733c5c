"""serving.executor: mean wall-clock bracket of the prefill-chunk steps in
the window, in ms."""


def read(ctx):
    b = [s.bracket_s for s in ctx.rec.steps if s.kind == "prefill"]
    return 1e3 * sum(b) / len(b) if b else None
