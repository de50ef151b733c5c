"""deepseek_r1 — DeepSeek-R1 671B (the EAAS paper's evaluation model).

[arXiv:2412.19437 / 2501.12948]  DeepSeek-R1 publishes DeepSeek-V3's
architecture (``model_type`` ``deepseek_v3``, config.json of
deepseek-ai/DeepSeek-V3): d_model 7168, 61 layers, the first 3 dense with
FFN width 18432; multi-head latent attention with 128 heads (q_lora_rank
1536, kv_lora_rank 512, 128 + 64 query/key dims, 128 value dims) and YaRN
rope (factor 40 over 4096 positions, beta 32 / 1, mscale 1 / 1, theta
10000); 256 routed experts of width 2048 plus one shared, top-8, routed by
sigmoid scores limited to the best 4 of 8 groups, with a score-correction
bias, renormalised and scaled by 2.5; vocabulary 129280, RMSNorm eps 1e-6.
This config is *additional* to the 10 assigned archs (used by the
paper-figure benchmarks); it is not one of the 40 graded dry-run cells.

Departure: no multi-token-prediction module (``num_nextn_predict_layers``
1): a step yields one token.

``CHIP`` (``deepseek-r1-chip``) is one v5e chip's share of a deployment in
which 16 chips divide each MoE layer by expert parallelism; it is the cut
of the benchmark's ``deepseek-r1.ep16`` configuration.  Every width of
``CONFIG`` is kept, and the router still scores all 256 experts.  What is
cut (``CHIP_REDUCED``), published value -> value here:

* depth: num_layers 61 -> 7 with first_k_dense 3 kept: the three leading
  dense layers once, then 4 MoE layers, as the first stage of a pipeline;
* experts held: num_experts 256 -> 16, global experts 0-15 (half of group
  0: a node-limited deployment keeps a node's experts inside one group);
  a route to any other expert is left to the chips that hold it;
* vocabulary: 129280 -> 16160, one eighth (request ids are drawn from it).

Memory (bf16): MLA 374 MB a layer, dense FFN 793 MB a layer, an expert
88 MB, embedding + head 470 MB at the padded 16384 rows: about 11.5 GB of
parameters with 4 logical servers x 4 experts and no redundant slot; the
latent cache at max_batch 32 x max_seq 2048 is 7 x 32 x 2048 x 1152 B =
0.53 GB.
"""

import dataclasses

from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YarnScaling

CONFIG = ModelConfig(
    arch_id="deepseek-r1",
    family="moe",
    num_layers=61,
    d_model=7168,
    num_heads=128,
    num_kv_heads=128,
    d_ff=18432,
    vocab_size=129280,
    d_head=128,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    activation="swiglu",
    moe=MoEConfig(
        num_experts=256,
        top_k=8,
        d_expert=2048,
        num_shared_experts=1,
        first_k_dense=3,
        router_score_fn="sigmoid",
        normalize_topk=True,
        n_group=8,
        topk_group=4,
        correction_bias=True,
        routed_scaling_factor=2.5,
    ),
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    rope_scaling=YarnScaling(factor=40.0, original_max_position=4096,
                             beta_fast=32.0, beta_slow=1.0,
                             mscale_all_dim=1.0),
    max_seq_len=163840,
    subquadratic=False,
    source="arXiv:2412.19437",
)

# chips that divide each MoE layer in the deployment CHIP stands for
CHIP_DEPLOYMENT_EP = 16

CHIP_REDUCED = {
    "num_layers": (CONFIG.num_layers, 7),
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
        routed_experts=CONFIG.moe.num_experts,
        first_expert=0),
    source="arXiv:2412.19437 (one chip's share of 16-way expert parallelism)",
)
