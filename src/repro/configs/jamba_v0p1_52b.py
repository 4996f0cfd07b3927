"""jamba-v0.1-52b — Mamba+attention 1:7 interleave, MoE
[arXiv:2403.19887; hf].

32L d_model=4096 32H (GQA kv=8) d_ff=14336 vocab=65536, MoE 16e top-2
on every second sublayer.  Super-block layout (attn_every=8): one
attention mixer at index 4 of each 8-layer block, Mamba elsewhere
(Jamba's published 1:7 ratio; Mamba-1 state size 16).
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="jamba-v0.1-52b", family="hybrid",
    n_layers=32, d_model=4096, n_heads=32, n_kv=8, head_dim=128,
    d_ff=14336, vocab=65536, n_experts=16, top_k=2, moe_every=2,
    ssm_state=16, ssm_expand=2, ssm_headdim=64, ssd_chunk=128,
    attn_every=8, attn_index=4,
)
