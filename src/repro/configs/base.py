"""Architecture descriptors of the LM tenants (``workloads.llm_zoo``
turns each into per-layer cost rows)."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str            # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int = 0
    n_kv: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab: int = 0
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1      # every k-th layer is MoE (jamba: 2)
    moe_d_ff: int = 0       # routed/shared expert width (0: d_ff)
    n_shared_experts: int = 0   # experts every token passes through
    first_k_dense: int = 0  # leading layers with a dense FFN of d_ff
    # --- latent attention (MLA, DeepSeek-V2): 0 = plain GQA ---
    kv_lora_rank: int = 0   # the cached latent per token
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0   # the decoupled RoPE key, cached too
    v_head_dim: int = 0
    # --- SSM (Mamba-2) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssd_chunk: int = 128
    # --- attention flavour ---
    window: int = 0          # sliding-window size (Mixtral: 4096)
    # --- hybrid (Jamba): attn at index attn_index of every attn_every ---
    attn_every: int = 0
    attn_index: int = 0
    # --- encoder-decoder (Whisper) ---
    enc_layers: int = 0
    n_frames: int = 0        # audio frame embeddings the encoder runs over

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def n_ssm_heads(self) -> int:
        return (self.ssm_expand * self.d_model) // self.ssm_headdim if \
            self.ssm_state else 0
