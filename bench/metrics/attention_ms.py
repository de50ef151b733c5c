"""models.attention: own device time of the operations tagged
``attention`` (projections, rope, the KV cache write, the attention and
the output projection), in ms per engine step of the window."""

from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("attention",))
