"""whisper-tiny — enc-dec [arXiv:2212.04356; unverified].

4L encoder + 4L decoder, d_model=384 6H (MHA kv=6) d_ff=1536
vocab=51865, 1500 audio frames.  The log-mel + conv frontend is not
costed: the encoder starts from 1500 frame embeddings and runs once,
in the prefill pass.
"""
from repro.configs.base import ArchConfig

FULL = ArchConfig(
    name="whisper-tiny", family="encdec",
    n_layers=4, enc_layers=4, d_model=384, n_heads=6, n_kv=6, head_dim=64,
    d_ff=1536, vocab=51865, n_frames=1500,
)
