"""llama3-405b — GQA, 128k vocab [arXiv:2407.21783; unverified].

126L d_model=16384 128H (GQA kv=8) d_ff=53248 vocab=128256.
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="llama3-405b", family="dense",
    n_layers=126, d_model=16384, n_heads=128, n_kv=8, head_dim=128,
    d_ff=53248, vocab=128256,
)
