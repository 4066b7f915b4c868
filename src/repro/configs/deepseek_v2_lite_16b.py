"""DeepSeek-V2-Lite-16B [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite
config.json] — MLA (no q-LoRA, kv_lora 512) with YaRN RoPE, one leading
dense layer (SwiGLU 10944), then MoE layers of 64 routed experts (top-6 by a
greedy softmax, gates not renormalised) and 2 shared experts."""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, YarnConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, vocab_size=102400, rope_theta=1e4, norm_eps=1e-6,
    first_k_dense=1, dense_d_ff=10944,
    mla=MLAConfig(kv_lora_rank=512, qk_rope_dim=64, qk_nope_dim=128,
                  v_head_dim=128),
    yarn=YarnConfig(factor=40.0, original_max_position=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared_experts=2,
                  norm_topk_prob=False, routed_scaling=1.0,
                  aux_loss_alpha=0.001),
    source="arXiv:2405.04434",
)
