"""deepseek-v2-lite — MLA + DeepSeekMoE [arXiv:2405.04434; hf].

27L d_model=2048 16H; MLA with kv_lora_rank=512, qk_nope 128, qk_rope
64, v 128 and no q compression; layer 0 dense (intermediate 10944),
layers 1-26 MoE with 64 routed experts (top-6) and 2 shared experts of
width 1408; vocab=102400
(https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json).

Its latent attention is costed by ``workloads.llm_zoo._mla_layer``.
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv=16,
    head_dim=128 + 64, d_ff=10944, vocab=102400,
    n_experts=64, top_k=6, moe_d_ff=1408, n_shared_experts=2,
    first_k_dense=1, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
)
