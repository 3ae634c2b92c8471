"""DeepSeek-V2-Lite — latent attention (MLA) and fine-grained experts.

[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite] 27L d_model=2048 16H,
no q compression, kv latent 512, q/k heads of 128 + 64 rotated, v heads of
128, YaRN rope (factor 40 over 4096 positions).  Layer 0 keeps a dense FFN
(d_ff=10944); layers 1-26 have 64 routed experts of 1408 (softmax top-6,
weights not renormalised) and 2 shared.  Untied head, vocab 102400.
"""
from repro.configs.base import ModelConfig, MoEConfig, YarnConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    dense_d_ff=10944,
    vocab_size=102400,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=10000.0,
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    moe=MoEConfig(
        n_experts=64, top_k=6, n_shared_experts=2, expert_d_ff=1408,
        moe_start_layer=1, moe_every=1, aux_loss_coef=0.001,
        norm_topk_prob=False),
    source="DeepSeek-V2 [arXiv:2405.04434]",
)
