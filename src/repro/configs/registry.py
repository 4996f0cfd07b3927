"""Architecture registry: ``--arch <id>`` lookup, per-arch shape grids,
and ``input_specs()`` (ShapeDtypeStruct stand-ins — never allocated).

Shape cells (per assignment):
  train_4k     seq 4096   x batch 256   -> train_step
  prefill_32k  seq 32768  x batch 32    -> prefill (serve)
  decode_32k   seq 32768  x batch 128   -> decode_step (1 token vs cache)
  long_500k    seq 524288 x batch 1     -> decode_step; sub-quadratic only

long_500k applicability is ``cfg.subquadratic`` (mamba2 / jamba /
mixtral-SWA); the skip for pure full-attention archs is noted in
DESIGN.md.  Modality stubs: encdec gets ``frames`` (B, n_frames, d),
vlm gets ``patches`` (B, n_patches, vit_dim) and text tokens filling
``seq_len - n_patches`` positions.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec, SHAPES
from repro.configs.deepseek_v2_lite import FULL as DEEPSEEK_V2_LITE

_MODULES = {
    "mamba2-2.7b": "mamba2_2p7b",
    "olmoe-1b-7b": "olmoe_1b_7b",
    "mixtral-8x7b": "mixtral_8x7b",
    "deepseek-7b": "deepseek_7b",
    "internlm2-1.8b": "internlm2_1p8b",
    "minicpm-2b": "minicpm_2b",
    "llama3-405b": "llama3_405b",
    "internvl2-76b": "internvl2_76b",
    "whisper-tiny": "whisper_tiny",
    "jamba-v0.1-52b": "jamba_v0p1_52b",
}


def _load(name: str):
    return importlib.import_module(f"repro.configs.{_MODULES[name]}")


ARCHS: dict[str, ArchConfig] = {}
SMOKES: dict[str, ArchConfig] = {}
for _name in _MODULES:
    _m = _load(_name)
    ARCHS[_name] = _m.FULL
    SMOKES[_name] = _m.SMOKE


# architectures served as scheduler tenants (``workloads.llm_zoo``): the
# model stack's archs plus those with no model in ``repro.models``
TENANT_ARCHS: dict[str, ArchConfig] = {**ARCHS,
                                       DEEPSEEK_V2_LITE.name: DEEPSEEK_V2_LITE}


def list_archs() -> list[str]:
    return list(ARCHS)


def get_arch(name: str, smoke: bool = False) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return SMOKES[name] if smoke else ARCHS[name]


def shapes_for(cfg: ArchConfig) -> list[str]:
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if cfg.subquadratic:
        out.append("long_500k")
    return out


def grid() -> list[tuple[str, str]]:
    """All (arch, shape) baseline cells (the 40-cell assignment grid,
    minus the spec'd long_500k skips)."""
    return [(a, s) for a, cfg in ARCHS.items() for s in shapes_for(cfg)]


# ---------------------------------------------------------------------------
# input specs (abstract): what each step is lowered against
# ---------------------------------------------------------------------------
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _act_dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.param_dtype == "bfloat16" else jnp.float32


def _text_len(cfg: ArchConfig, seq_len: int) -> int:
    return seq_len - cfg.n_patches if cfg.family == "vlm" else seq_len


def batch_specs(cfg: ArchConfig, shape: ShapeSpec) -> dict:
    """ShapeDtypeStructs for the *data* inputs of the cell's step."""
    B = shape.global_batch
    dt = _act_dtype(cfg)
    if shape.kind in ("train", "prefill"):
        S = _text_len(cfg, shape.seq_len)
        batch = {"tokens": _sds((B, S), jnp.int32)}
        if cfg.family == "encdec":
            batch["frames"] = _sds((B, cfg.n_frames, cfg.d_model), dt)
        if cfg.family == "vlm":
            batch["patches"] = _sds((B, cfg.n_patches, cfg.vit_dim), dt)
        return batch
    # decode: one new token against a cache of shape.seq_len
    return {"token": _sds((B, 1), jnp.int32), "pos": _sds((B,), jnp.int32)}


def cache_specs(cfg: ArchConfig, shape: ShapeSpec):
    """Abstract KV/state cache for decode cells (eval_shape: no alloc)."""
    from repro.models.model import build_model
    model = build_model(cfg)
    dt = _act_dtype(cfg)
    return jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len, dt))


def shape_spec(name: str) -> ShapeSpec:
    return SHAPES[name]
