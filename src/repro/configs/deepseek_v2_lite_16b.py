"""DeepSeek-V2-Lite 16B — MLA + fine-grained MoE [arXiv:2405.04434].

Published settings (hf deepseek-ai/DeepSeek-V2-Lite, config.json): 27
layers at d_model 2048; MLA with 16 heads, latent rank 512 (RMSNorm on the
latent), q/k heads of 128 + 64 (nope + rope), v heads of 128, no q
compression; layer 0 a dense SwiGLU of 10944; 26 MoE layers of 64 routed
experts of 1408 (softmax router, greedy top-6, gates the raw scores: not
renormalised, ``routed_scaling_factor`` 1; per-sequence balance loss with
``aux_loss_alpha`` 0.001) and 2 shared experts; YaRN rotary (factor 40
over 4096 original positions, beta 32 / 1, mscale 0.707 on both); RMSNorm
eps 1e-6; vocabulary 102400, untied head.
"""
from repro.configs.base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,       # MLA: kv heads == q heads after up-projection
    head_dim=192,          # qk_nope (128) + qk_rope (64)
    attn_type="mla",
    kv_lora_rank=512,
    q_lora_rank=0,         # V2-Lite has no q compression
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    d_ff=10944,            # dense prefix layer width (model card)
    vocab_size=102400,
    act="swiglu",
    norm="rmsnorm",
    norm_eps=1e-6,
    rope_theta=10_000.0,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=True,
    num_experts=64,
    num_shared_experts=2,
    experts_per_token=6,
    moe_d_ff=1408,
    first_k_dense=1,
    capacity_factor=1.0,
    router_aux_coef=0.001,
    norm_topk_prob=False,
    seq_aux=True,
    tie_embeddings=False,
    source="arXiv:2405.04434 (DeepSeek-V2); hf:deepseek-ai/DeepSeek-V2-Lite",
)
