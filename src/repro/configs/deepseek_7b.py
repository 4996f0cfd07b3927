"""deepseek-7b — llama-arch dense [arXiv:2401.02954; hf].

30L d_model=4096 32H (GQA kv=32, i.e. MHA) d_ff=11008 vocab=102400.
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="deepseek-7b", family="dense",
    n_layers=30, d_model=4096, n_heads=32, n_kv=32, head_dim=128,
    d_ff=11008, vocab=102400,
)
