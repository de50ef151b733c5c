"""core.moe_layer: own device time of the MoE layers' operations, tagged
``router``, ``dispatch``, ``server``, ``gemm`` or ``combine``, in ms per
engine step of the window.  The copies of each layer's expert bank that
the scan over layers makes are untagged, and not counted."""

from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("router", "dispatch", "server", "gemm",
                             "combine"))
