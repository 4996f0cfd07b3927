"""The LM architectures served as scheduler tenants
(``workloads.llm_zoo``), by name."""
from __future__ import annotations

from repro.configs import (deepseek_7b, deepseek_v2_lite, internlm2_1p8b,
                           internvl2_76b, jamba_v0p1_52b, llama3_405b,
                           mamba2_2p7b, minicpm_2b, mixtral_8x7b,
                           olmoe_1b_7b, whisper_tiny)
from repro.configs.base import ArchConfig

TENANT_ARCHS: dict[str, ArchConfig] = {m.FULL.name: m.FULL for m in (
    mamba2_2p7b, olmoe_1b_7b, mixtral_8x7b, deepseek_7b, internlm2_1p8b,
    minicpm_2b, llama3_405b, internvl2_76b, whisper_tiny, jamba_v0p1_52b,
    deepseek_v2_lite)}
