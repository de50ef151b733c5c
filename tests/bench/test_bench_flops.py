"""Operation and byte counts against shapes worked by hand (the Mixtral
family's, and the shared grouped GEMM work), and the peaks table keyed by
device kind."""

import pytest

from bench import flops
from bench.models import load_config, mixtral
from bench.peaks import peaks

SMALL = mixtral.Arch(
    name="t", d_model=8, num_layers=2, first_k_dense=1, num_heads=2,
    num_kv_heads=1, head_dim=4, d_ff=16, d_expert=4, num_experts=4, top_k=2,
    num_shared_experts=1, vocab=10, rope_theta=1e4, rms_norm_eps=1e-6,
    score_fn="sigmoid", normalize_topk=True)


def test_dense_flops_by_hand():
    # attention per layer: q 8x8, k 8x4, v 8x4, o 8x8 -> 2*8*(8+4+4+8)=384
    # dense FFN: 2*3*8*16 = 768; MoE: (2 routed + 1 shared) * 2*3*8*4 = 576
    # plus router 2*8*4 = 64; head 2*8*10 = 160
    assert mixtral.dense_flops_per_token(SMALL) == 2 * 384 + 768 + 640 + 160


def test_attention_and_chunk_flops():
    # 2 layers x (QK + PV) x 2 ops x 2 heads x 4 dims per key
    assert mixtral.attention_flops(SMALL, 5) == 2 * 2 * 2 * 2 * 4 * 5
    d = mixtral.dense_flops_per_token(SMALL)
    assert mixtral.token_flops(SMALL, 4) == d + mixtral.attention_flops(
        SMALL, 5)
    # positions 3, 4, 5 attend to 4, 5, 6 keys
    assert mixtral.chunk_flops(SMALL, 3, 3) == 3 * d + mixtral.attention_flops(
        SMALL, 15)


def test_expert_gemm_work_by_hand():
    ops, nbytes = flops.expert_gemm_work(SMALL, rows=10, experts_hit=3)
    assert ops == 10 * 3 * 2 * 8 * 4
    assert nbytes == 3 * 3 * 8 * 4 * 2 + 10 * 3 * (8 + 4) * 2


def test_mixtral_cut_sizes():
    a = mixtral.arch(load_config("mixtral-8x7b.pp8"))
    assert (a.d_model, a.num_heads, a.head_dim, a.num_kv_heads) == (
        4096, 32, 128, 8)
    assert (a.d_expert, a.num_experts, a.top_k) == (14336, 8, 2)
    assert (a.num_layers, a.first_k_dense, a.num_shared_experts) == (4, 0, 0)
    assert (a.vocab, a.score_fn, a.normalize_topk) == (32000, "softmax",
                                                       True)
    # 4 x (attention 83.9 M + 2 experts 704.6 M + router) + head 262.1 M
    assert mixtral.dense_flops_per_token(a) == 4 * (
        2 * 4096 * 10240 + 2 * 6 * 4096 * 14336 + 2 * 4096 * 8) + (
        2 * 4096 * 32000)


def test_peaks_by_device_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v99")
