"""internvl2-76b — InternViT + LLM backbone [arXiv:2404.16821; unverified].

80L d_model=8192 64H (GQA kv=8) d_ff=28672 vocab=128256.  As a tenant
it is its LM backbone: the vision tower and the projector are not
costed.
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="internvl2-76b", family="vlm",
    n_layers=80, d_model=8192, n_heads=64, n_kv=8, head_dim=128,
    d_ff=28672, vocab=128256,
)
