"""deepseek_r1 — DeepSeek-R1 671B (the EAAS paper's evaluation model).

[arXiv:2412.19437 / 2501.12948]  61L, 256 routed experts top-8 + 1 shared,
first 3 layers dense, sigmoid gating.  NOTE: DeepSeek uses MLA attention; this
substrate models attention as GQA (kv=8) of matched KV-cache footprint — the
EAAS technique concerns the MoE/FFN tier, which is reproduced exactly.
This config is *additional* to the 10 assigned archs (used by the paper-figure
benchmarks); it is not one of the 40 graded dry-run cells.

``CHIP`` (``deepseek-r1-chip``) is one v5e chip's share of a deployment in
which 16 chips divide each MoE layer by expert parallelism.  Every width of
``CONFIG`` is kept: d_model 7168, 128 heads x 64, kv 8, d_ff 2048, d_expert
2048, top-8, one shared expert, sigmoid routing.  What is cut
(``CHIP_REDUCED``), published value -> value here:

* depth: num_layers 61 -> 5, first_k_dense 3 -> 1 (one dense layer and four
  MoE layers; the layers left out would sit on further chips as pipeline
  stages);
* experts held: num_experts 256 -> 16, this chip's share of the 16-chip
  expert-parallel deployment;
* vocabulary: 129280 -> 16160, one eighth (request ids are drawn from it).

Departure: the router here routes over the 16 experts held, not over all
256 with only this chip's share computed.  A layer that routes over the
published 256 and computes its own share is still to come.

Memory at max_batch 32 x max_seq 2048 (bf16): params 9.9 GiB with 4
logical servers x (4 primaries + 2 redundant slots), KV cache 0.63 GiB.
"""

import dataclasses

from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    arch_id="deepseek-r1",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=8,
    d_ff=2048,
    vocab_size=129280,
    d_head=64,
    rope_theta=10000.0,
    activation="swiglu",
    moe=MoEConfig(
        num_experts=256,
        top_k=8,
        d_expert=2048,
        num_shared_experts=1,
        first_k_dense=3,
        router_score_fn="sigmoid",
        normalize_topk=True,
    ),
    subquadratic=False,
    source="arXiv:2412.19437",
)

# chips that divide each MoE layer in the deployment CHIP stands for
CHIP_DEPLOYMENT_EP = 16

CHIP_REDUCED = {
    "num_layers": (CONFIG.num_layers, 5),
    "first_k_dense": (CONFIG.moe.first_k_dense, 1),
    "num_experts": (CONFIG.moe.num_experts,
                    CONFIG.moe.num_experts // CHIP_DEPLOYMENT_EP),
    "vocab_size": (CONFIG.vocab_size, CONFIG.vocab_size // 8),
}

CHIP = CONFIG.replace(
    arch_id="deepseek-r1-chip",
    num_layers=CHIP_REDUCED["num_layers"][1],
    vocab_size=CHIP_REDUCED["vocab_size"][1],
    moe=dataclasses.replace(
        CONFIG.moe,
        num_experts=CHIP_REDUCED["num_experts"][1],
        first_k_dense=CHIP_REDUCED["first_k_dense"][1]),
    source="arXiv:2412.19437 (one chip's share of 16-way expert parallelism)",
)
