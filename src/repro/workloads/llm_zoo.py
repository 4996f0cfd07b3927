"""LM-architecture layerization: LM architectures as RELMAS tenants.

The paper schedules DNN inference at *layer* granularity given per-
(layer, sub-accelerator) latency/bandwidth/energy tables.  This module
produces those tables for LM architectures so every arch is a
first-class tenant of the paper's technique (DESIGN.md
§Arch-applicability): each transformer/SSM layer becomes one sub-job,
characterized by its aggregate GEMM work and DRAM footprints.

A request is one chain (:func:`llm_request_specs`): the prefill pass
(embed, the layers, head; the head yields the first token), then one
decode pass (embed, the layers, head), which the scheduler runs once
per further output token — the job re-enters its queue at the chain's
``decode_start`` (``sim/env.py``), judged on time to first token and
time per output token.

- prefill: ingest ``prompt`` tokens (batch 1); compute-heavy, weights
  and activations streamed once per layer, attention over the prompt;
- decode: one token against a ``ctx``-long cache; bandwidth-heavy
  (weights + cache read per token) — exactly the memory-bound/
  compute-bound mix the RELMAS contention model manages.  A decode
  pass is costed at one fixed ``ctx`` per tenant, not at the context
  that grows token by token.

LM tenants run on the datacenter-class MAS (same Eyeriss/Simba dataflow
classes, scaled arrays + HBM-class shared bandwidth, Table 1 scaling in
``costmodel.accelerators``); edge CNN tenants use the paper's Table 1
instances.
"""
from __future__ import annotations

from repro.configs.base import ArchConfig
from repro.configs.registry import TENANT_ARCHS
from repro.costmodel.accelerators import DATACENTER_MAS, MASConfig
from repro.costmodel.fleets import get_fleet
from repro.costmodel.layers import LayerSpec, elementwise, gemm
from repro.costmodel.registry import Registry

BYTES = 2      # bf16 serving


def _ffn(cfg: ArchConfig, layer: int, S: int) -> tuple[int, int]:
    """(MACs, weight elements streamed) of layer ``layer``'s FFN for ``S``
    tokens.  A MoE layer (from ``first_k_dense`` on, every
    ``moe_every``-th) runs the router, ``top_k`` routed and every shared
    expert per token at the expert width; its routed experts stream in
    once each when any token picks them (at most ``S * top_k`` of them).
    """
    d = cfg.d_model
    k0 = cfg.first_k_dense
    if not (cfg.is_moe and layer >= k0
            and (layer - k0) % cfg.moe_every == cfg.moe_every - 1):
        return 3 * S * d * cfg.d_ff, 3 * d * cfg.d_ff
    width = cfg.moe_d_ff or cfg.d_ff
    E, ns = cfg.n_experts, cfg.n_shared_experts
    macs = S * (3 * d * width * (ns + cfg.top_k) + d * E)
    return macs, 3 * d * width * (ns + min(E, S * cfg.top_k)) + d * E


def _layer_spec(name: str, S: int, d: int, macs: int, w_elems: int,
                in_bytes: int, out_bytes: int) -> LayerSpec:
    # GEMM-equivalent dims: m=S tokens, k=d, n chosen to conserve MACs
    n = max(1, macs // max(S * d, 1))
    return LayerSpec(name=name, kind="gemm", gemm_m=S, gemm_k=d, gemm_n=n,
                     in_bytes=in_bytes, w_bytes=w_elems * BYTES,
                     out_bytes=out_bytes, dtype_bytes=BYTES)


def _attn_layer(cfg: ArchConfig, layer: int, name: str, S: int, ctx: int,
                decode: bool) -> LayerSpec:
    """One attention+FFN (or MoE) layer as an aggregate GEMM sub-job."""
    d, Dh = cfg.d_model, cfg.head_dim
    Hq, Hkv = cfg.n_heads, max(cfg.n_kv, 1)
    attn_span = min(ctx, cfg.window) if cfg.window > 0 else ctx
    qkvo = S * d * (2 * Hq * Dh + 2 * Hkv * Dh)
    scores = S * attn_span * Hq * Dh * 2
    ffn, w_ffn = _ffn(cfg, layer, S)
    w = (2 * Hq * Dh + 2 * Hkv * Dh) * d + w_ffn
    kv_bytes = 2 * Hkv * attn_span * Dh * BYTES if decode else 0
    return _layer_spec(name, S, d, qkvo + scores + ffn, w,
                       in_bytes=S * d * BYTES + kv_bytes,
                       out_bytes=S * d * BYTES
                       + 2 * Hkv * S * Dh * BYTES)      # kv append


def _mla_layer(cfg: ArchConfig, layer: int, name: str, S: int, ctx: int,
               decode: bool) -> LayerSpec:
    """One latent-attention (MLA, DeepSeek-V2) + FFN/MoE layer.

    The cache holds ``kv_lora_rank + qk_rope_head_dim`` per token.
    Prefill runs the plain form: q, the latent and the rope key from
    the hidden state, the latent up-projected to keys and values for
    every token, attention of each token over all ``ctx`` (= prompt)
    tokens.  Decode runs the absorbed form: the up-projections fold into
    the query and the output, so each head attends over the cached
    latent itself (scores over ``rank + rope``, values over ``rank``)
    and the whole cache is read once.
    """
    d, H = cfg.d_model, cfg.n_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, r = cfg.v_head_dim, cfg.kv_lora_rank
    w_q, w_kv_a = d * H * (nope + rope), d * (r + rope)
    w_kv_b, w_o = r * H * (nope + vd), H * vd * d
    proj = S * (w_q + w_kv_a + w_o)
    if decode:
        attn = S * H * (nope * r + ctx * (r + rope) + ctx * r + r * vd)
        kv_read = ctx * (r + rope) * BYTES
    else:
        attn = S * w_kv_b + S * ctx * H * (nope + rope + vd)
        kv_read = 0
    ffn, w_ffn = _ffn(cfg, layer, S)
    return _layer_spec(name, S, d, proj + attn + ffn,
                       w_q + w_kv_a + w_kv_b + w_o + w_ffn,
                       in_bytes=S * d * BYTES + kv_read,
                       out_bytes=S * d * BYTES
                       + S * (r + rope) * BYTES)        # latent append


def _ssm_layer(cfg: ArchConfig, name: str, S: int) -> LayerSpec:
    """Mamba-2 layer: in-proj + SSD + out-proj (state read at decode)."""
    d = cfg.d_model
    d_in = cfg.ssm_expand * d
    H, N, P, C = cfg.n_ssm_heads, cfg.ssm_state, cfg.ssm_headdim, \
        cfg.ssd_chunk
    in_dim = 2 * d_in + 2 * N + H
    ssd_per_tok = min(C, S) * N + min(C, S) * H * P + 2 * H * N * P
    macs = S * d * in_dim + S * ssd_per_tok + S * d_in * d
    w_bytes = (d * in_dim + d_in * d) * BYTES
    state_bytes = H * N * P * 4                       # f32 state r/w
    in_bytes = S * d * BYTES + state_bytes
    out_bytes = S * d * BYTES + state_bytes
    n = max(1, macs // max(S * d, 1))
    return LayerSpec(name=name, kind="ssm_scan", gemm_m=S, gemm_k=d,
                     gemm_n=n, in_bytes=in_bytes, w_bytes=w_bytes,
                     out_bytes=out_bytes, dtype_bytes=BYTES)


def llm_layer_specs(cfg: ArchConfig, *, phase: str = "decode",
                    seq: int = 128, ctx: int = 2048) -> list[LayerSpec]:
    """Layer chain (one sub-job per layer + embed + head) for one request."""
    decode = phase == "decode"
    S = 1 if decode else seq
    d, V = cfg.d_model, cfg.vocab
    ls: list[LayerSpec] = [
        elementwise(f"{cfg.name}/embed", S * d, BYTES)]
    if cfg.family == "encdec":
        for i in range(cfg.enc_layers):
            ls.append(_attn_layer(cfg, i, f"{cfg.name}/enc{i}",
                                  cfg.n_frames, cfg.n_frames, decode=False))
    attn = _mla_layer if cfg.kv_lora_rank else _attn_layer
    for i in range(cfg.n_layers):
        if cfg.family == "ssm":
            ls.append(_ssm_layer(cfg, f"{cfg.name}/l{i}", S))
        elif cfg.family == "hybrid":
            if i % cfg.attn_every == cfg.attn_index:
                ls.append(attn(cfg, i, f"{cfg.name}/l{i}a", S, ctx, decode))
            else:
                ls.append(_ssm_layer(cfg, f"{cfg.name}/l{i}m", S))
        else:
            ls.append(attn(cfg, i, f"{cfg.name}/l{i}", S, ctx, decode))
    ls.append(gemm(f"{cfg.name}/head", S, d, V, dtype_bytes=BYTES,
                   kind="fc" if S == 1 else "gemm"))
    return ls


def llm_request_specs(cfg: ArchConfig, *, prompt: int, ctx: int
                      ) -> tuple[list[LayerSpec], int]:
    """One whole request's chain and where its decode pass starts: the
    prefill pass over ``prompt`` tokens, then one decode pass against a
    ``ctx``-long cache (run again for each further output token)."""
    pre = llm_layer_specs(cfg, phase="prefill", seq=prompt, ctx=prompt)
    dec = llm_layer_specs(cfg, phase="decode", ctx=ctx)
    if cfg.family == "encdec":
        # the encoder runs once, in the prefill pass
        dec = dec[:1] + dec[1 + cfg.enc_layers:]
    return pre + dec, len(pre)


# ---------------------------------------------------------------------------
# tenant sets (LM analogues of the paper's Light/Heavy/Mixed, Table 2)
# ---------------------------------------------------------------------------
LM_LIGHT = ("whisper-tiny", "internlm2-1.8b", "minicpm-2b", "mamba2-2.7b")
LM_HEAVY = ("deepseek-7b", "olmoe-1b-7b", "mixtral-8x7b", "jamba-v0.1-52b")
LM_XL = ("llama3-405b", "internvl2-76b")
# request classes: tenant -> (arch, prompt tokens, decode context); a
# class's decode context is its prompt plus the median output (64)
LM_CLASSES = {
    "dsv2lite-p512": ("deepseek-v2-lite", 512, 512 + 64),
    "dsv2lite-p2048": ("deepseek-v2-lite", 2048, 2048 + 64),
}
LM_WORKLOADS = {
    "lm_light": LM_LIGHT,
    "lm_heavy": LM_HEAVY,
    "lm_mixed": LM_LIGHT + LM_HEAVY,
    "lm_all": LM_LIGHT + LM_HEAVY + LM_XL,
    "lm_dsv2lite": ("dsv2lite-p512", "dsv2lite-p2048"),
}


def build_llm_registry(workload: str = "lm_mixed", *, seq: int = 128,
                       ctx: int = 2048,
                       mas: MASConfig | str = DATACENTER_MAS) -> Registry:
    """Whole-request LM tenants on an HBM-class MAS.  A tenant named in
    :data:`LM_CLASSES` brings its own prompt and decode context, an arch
    name takes ``seq`` prompt tokens and a ``ctx`` decode context;
    ``mas`` accepts fleet preset names (see ``repro.costmodel.fleets``)
    like :func:`build_registry`."""
    reg = Registry(get_fleet(mas))
    for name in LM_WORKLOADS[workload]:
        arch, prompt, dctx = LM_CLASSES.get(name, (name, seq, ctx))
        layers, ds = llm_request_specs(TENANT_ARCHS[arch], prompt=prompt,
                                       ctx=dctx)
        reg.register(name, layers, decode_start=ds)
    return reg
