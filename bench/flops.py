"""Operations and bytes the model needs, counted from its shapes.

These count the work the algorithm needs, never what an implementation
spends: causal attention over the tokens actually in context (not the
cache's full width), the top-k routed experts of each token (not padding
or replica slots), the head over the vocabulary slice.  A multiply-add is
two operations.
"""

from __future__ import annotations

from bench.arch import Arch

BF16_BYTES = 2


def dense_flops_per_token(a: Arch) -> float:
    """Matmul operations of one token through the whole model, attention
    scores excluded (see :func:`attention_flops`)."""
    d = a.d_model
    attn = 2 * d * (2 * a.num_heads * a.head_dim
                    + 2 * a.num_kv_heads * a.head_dim)
    dense_ffn = 2 * 3 * d * a.d_ff
    expert = 2 * 3 * d * a.d_expert
    moe = expert * (a.top_k + a.num_shared_experts) + 2 * d * a.num_experts
    return (a.num_layers * attn + a.first_k_dense * dense_ffn
            + a.moe_layers * moe + 2 * d * a.vocab)


def attention_flops(a: Arch, keys: int) -> float:
    """Score and value operations of one query over ``keys`` positions,
    summed over layers."""
    return a.num_layers * 2 * 2 * a.num_heads * a.head_dim * keys


def token_flops(a: Arch, position: int) -> float:
    """One token at ``position`` (0-based), attending causally."""
    return dense_flops_per_token(a) + attention_flops(a, position + 1)


def chunk_flops(a: Arch, start: int, length: int) -> float:
    """A prefill chunk of ``length`` tokens starting at ``start``."""
    keys = length * (start + 1) + length * (length - 1) // 2
    return length * dense_flops_per_token(a) + attention_flops(a, keys)


def expert_gemm_work(a: Arch, rows: int, experts_hit: int):
    """(operations, bytes) of the routed-expert GEMMs of one step: ``rows``
    (token, expert) pairs over all MoE layers, ``experts_hit`` (expert,
    layer) pairs whose weights must be read at least once.  Rows are read
    in and written out of each of the three GEMMs."""
    d, f = a.d_model, a.d_expert
    ops = rows * 3 * 2 * d * f
    weights = experts_hit * 3 * d * f * BF16_BYTES
    acts = rows * 3 * (d + f) * BF16_BYTES
    return ops, weights + acts
