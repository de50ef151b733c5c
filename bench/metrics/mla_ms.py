"""models.mla: own device time of the operations tagged ``mla`` (latent
attention's projections, the latent cache write, the attention and the
output projection), in ms per engine step of the window."""

from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("mla",))
