"""deepseek-v2-lite [moe, mla]: 27L d=2048 16H, multi-head latent attention
(kv_lora_rank 512, no query latent, q/k heads 128 nope + 64 rope, v 128)
with YaRN rotary (factor 40 over 4,096 positions), layer 0 dense (d_ff
10944), 26 DeepSeekMoE layers of 64 routed experts (d_ff 1408) top-6
softmax without renormalisation + 2 shared, untied vocab 102400.

[hf:deepseek-ai/DeepSeek-V2-Lite; arXiv:2405.04434]
"""
from .base import LayerSpec, ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    d_model=2048, n_heads=16, n_kv_heads=16, d_head=192,
    d_ff=1408, vocab_size=102400,
    moe_experts=64, moe_top_k=6, moe_shared_experts=2, moe_d_ff=1408,
    moe_norm_topk=False,
    # the paper's device-level token dropping in training: a budget of one
    # times the held share, the lowest-affinity assignments dropped over it
    moe_capacity_factor=1.0,
    rope_yarn_factor=40.0, rope_yarn_original_len=4096,
    rope_yarn_beta_fast=32.0, rope_yarn_beta_slow=1.0,
    rope_yarn_mscale=0.707, rope_yarn_mscale_all_dim=0.707,
    mla_kv_rank=512, mla_nope_dim=128, mla_rope_dim=64, mla_v_dim=128,
    prelude=(LayerSpec(mixer="mla", mlp="dense"),),
    prelude_d_ff=10944,
    period=(LayerSpec(mixer="mla", mlp="moe"),),
    n_periods=26,
    sharding="fsdp_tp",
)
