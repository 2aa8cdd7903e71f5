"""deepseek-v2-lite [moe]: latent attention (MLA) without a query LoRA,
YaRN rope, one leading dense layer, then 64 routed experts (top-6 softmax
gates, not renormalised) plus 2 shared experts.
[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434]

``CONFIG`` is the chip's share of an expert-parallel deployment over 8
chips: every layer, width, head and the whole vocabulary as published, the
router's 64 outputs, and of each layer's routed experts the 8 this chip
holds (experts 0-7). ``REDUCED`` keeps every mechanism (YaRN, the leading
dense layer, a held share of the experts) at CPU-test size."""
from repro.configs.base import (ClusterKVConfig, MLAConfig, ModelConfig,
                                MoEConfig, YarnConfig)

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    first_k_dense=1,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=10944,
    vocab=102400,
    norm_eps=1e-6,
    rope_theta=10_000.0,
    mla=MLAConfig(
        q_lora_rank=0,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        yarn=YarnConfig(factor=40.0, original_max_position=4096,
                        beta_fast=32.0, beta_slow=1.0,
                        mscale_all_dim=0.707),
    ),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408,
                  n_shared_experts=2, norm_topk_prob=False,
                  experts_held=8, first_held=0),
    clusterkv=ClusterKVConfig(enabled=True),
    dtype="bfloat16",
    param_dtype="bfloat16",
    remat=False,
)

REDUCED = ModelConfig(
    name="deepseek-v2-lite-reduced",
    family="moe",
    n_layers=4,
    first_k_dense=1,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=96,
    vocab=256,
    norm_eps=1e-6,
    mla=MLAConfig(
        q_lora_rank=0,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        yarn=YarnConfig(factor=40.0, original_max_position=4096,
                        beta_fast=32.0, beta_slow=1.0,
                        mscale_all_dim=0.707),
    ),
    moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared_experts=2,
                  norm_topk_prob=False, experts_held=4, first_held=0),
    remat=False,
)
