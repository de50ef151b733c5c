"""kernels.grouped_gemm: the least time the routed-expert GEMMs of the
window need on this chip, over the grouped GEMM kernel's device time in
the trace, in %.  The kernel is found by the ``gemm`` tag in its own
``kernel_metadata``, not by its operands, which a rewrite changes.

The work is counted from each step's expert load (rows routed to the
experts held, summed over the MoE layers): three GEMMs of ``2 d f``
operations per row, and the bytes of every expert hit plus the rows read
and written (the family module's ``expert_gemm_work``).  Padding, empty
tiles and replica slots are not counted, so a kernel that skips them is
not charged for them.  The load is summed over layers: an expert with any
row is taken as read in every MoE layer, which holds at the loads of
these cells (every expert is hit in every layer at 32 rows and top-8).
Least time per step is the larger of operations over peak bf16 rate and
bytes over HBM bandwidth."""

import numpy as np

from bench.stages import kernel_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    kernel_s = kernel_seconds(ctx.trace, "gemm")
    if not kernel_s:
        return None
    rec, a, pk = ctx.rec, ctx.arch, ctx.peaks
    least = 0.0
    for s in rec.steps:
        if s.expert_load is None or s.end >= rec.t1:
            continue
        load = np.asarray(s.expert_load)
        ops, nbytes = ctx.family.expert_gemm_work(
            a, int(load.sum()), int((load > 0).sum()) * a.moe_layers)
        least += max(ops / pk["bf16_flops_per_s"],
                     nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s if least else None
