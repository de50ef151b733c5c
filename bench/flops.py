"""Operations and bytes of the routed-expert GEMMs, counted from shapes.

These count the work the algorithm needs, never what an implementation
spends: the rows actually routed (not padding or replica slots), the
weights of each expert hit.  A multiply-add is two operations.  Every
family whose experts are SwiGLU FFNs counts them here; what else a model
needs (attention, dense layers, head) its family module counts
(``bench/models``).
"""

from __future__ import annotations

BF16_BYTES = 2


def expert_gemm_work(a, rows: int, experts_hit: int):
    """(operations, bytes) of the routed-expert GEMMs of one step: ``rows``
    (token, expert) pairs over all MoE layers, ``experts_hit`` (expert,
    layer) pairs whose weights must be read at least once.  Rows are read
    in and written out of each of the three GEMMs."""
    d, f = a.d_model, a.d_expert
    ops = rows * 3 * 2 * d * f
    weights = experts_hit * 3 * d * f * BF16_BYTES
    acts = rows * 3 * (d + f) * BF16_BYTES
    return ops, weights + acts
