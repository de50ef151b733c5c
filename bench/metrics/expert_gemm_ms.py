"""kernels.grouped_gemm: own device time of the operations tagged ``gemm``
(the grouped GEMM kernel names its stage in its ``kernel_metadata``), in
ms per engine step of the window."""

from bench.stages import ms_per_step


def read(ctx):
    return ms_per_step(ctx, ("gemm",))
