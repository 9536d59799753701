"""DeepSeek-V2-Lite [arXiv:2405.04434; huggingface.co/deepseek-ai/
DeepSeek-V2-Lite config.json]: 27 layers of latent attention (MLA, no query
LoRA) with YaRN RoPE; layer 0 a dense SwiGLU, layers 1-26 DeepSeekMoE with
64 routed experts (top-6, softmax gate, weights not renormalised) and 2
shared ones; 15.7 B parameters, 2.4 B active."""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, norm_eps=1e-6, act="silu",
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_theta=10_000.0, rope_factor=40.0, rope_original_max_len=4096,
    rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale_all_dim=0.707,
    num_experts=64, num_experts_per_tok=6, moe_d_ff=1408,
    num_shared_experts=2, first_k_dense=1,
)
