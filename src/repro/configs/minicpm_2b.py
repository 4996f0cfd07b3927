"""minicpm-2b — llama-like arch [arXiv:2404.06395; hf].

40L d_model=2304 36H (MHA kv=36) d_ff=5760 vocab=122753, tied
embeddings.
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="minicpm-2b", family="dense",
    n_layers=40, d_model=2304, n_heads=36, n_kv=36, head_dim=64,
    d_ff=5760, vocab=122753,
)
