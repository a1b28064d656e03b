"""DeepSeek-V2-Lite-16B [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

27 layers of MLA (kv_lora 512, no query compression, YaRN rope x40); layer 0
has a dense SwiGLU FFN of width 10944, layers 1-26 are DeepSeekMoE: 2 shared
+ 64 routed experts of width 1408, softmax top-6 with unnormalised gates and
the sequence-wise balance loss (aux_loss_alpha 0.001).
"""
from .base import ArchConfig, MLAConfig, MoEConfig, YaRNConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    citation="arXiv:2405.04434",
    n_layers=27,
    first_dense=1,          # first_k_dense_replace
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,             # the dense FFN of layer 0
    vocab=102400,
    norm_eps=1e-6,
    mla=MLAConfig(q_lora=0, kv_lora=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
                  yarn=YaRNConfig(factor=40.0, original_max=4096, beta_fast=32.0,
                                  beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707)),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared=2, expert_ff=1408, aux_alpha=0.001),
)
