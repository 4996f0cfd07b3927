"""Multi-timestep fused LSTM — Pallas TPU kernel (§Perf H3 structural fix).

The RELMAS DDPG-update roofline is memory-bound: the recurrent weights
(Wh: H x 4H ~= 1 MB at h=256) are re-read from HBM at every one of the
~97 ready-queue timesteps of every LSTM pass (measured: the weight
stream is the dominant term of the per-chip memory time, EXPERIMENTS.md
§Perf).  ``lstm_cell`` fuses one step; this kernel fuses the WHOLE
sequence: grid = (B/bm, T) with T as the innermost ("arbitrary") axis —
the weight BlockSpecs have constant index maps, so Pallas keeps Wx/Wh/b
resident in VMEM across all T revisits and HBM weight traffic drops
from T fetches to ONE per batch tile.  The h/c carry lives in VMEM
scratch; per-step hidden states stream out for the projection heads.

VMEM @ h=256, bm=128, F=23 (f32):
  Wx 23x4x256 + Wh 256x4x256 + b 4x256  ~= 1.15 MB
  x 128x23 + h,c 2x128x256 + hs-out 128x256                ~= 0.4 MB
  total ~= 1.6 MB  << 16 MB v5e VMEM.

Masked timesteps (padded RQ slots) keep the carry unchanged, matching
``policy._lstm_scan`` semantics exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _lstm_seq_kernel(x_ref, m_ref, wx_ref, wh_ref, b_ref, hs_ref,
                     h_scr, c_scr):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)
        c_scr[...] = jnp.zeros_like(c_scr)

    x = x_ref[0]                    # (bm, F)
    m = m_ref[0]                    # (bm, 1) float mask
    h = h_scr[...]                  # (bm, H)
    c = c_scr[...]

    def gate(g):
        acc = jnp.dot(x, wx_ref[:, g, :],
                      preferred_element_type=jnp.float32)
        acc += jnp.dot(h, wh_ref[:, g, :],
                       preferred_element_type=jnp.float32)
        return acc + b_ref[g][None, :]

    i = jax.nn.sigmoid(gate(0))
    f = jax.nn.sigmoid(gate(1))
    g = jnp.tanh(gate(2))
    o = jax.nn.sigmoid(gate(3))
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    h_new = m * h2 + (1.0 - m) * h
    c_new = m * c2 + (1.0 - m) * c
    h_scr[...] = h_new
    c_scr[...] = c_new
    hs_ref[0] = h_new.astype(hs_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_b", "interpret"))
def lstm_seq_pallas(xs, mask, wx4, wh4, b4, *, block_b: int = 128,
                    interpret: bool = False):
    """xs (T,B,F), mask (T,B) bool; wx4 (F,4,H), wh4 (H,4,H), b4 (4,H).

    Returns hs (T, B, H): the post-mask hidden state after each step.
    """
    T, B, F = xs.shape
    H = wh4.shape[0]
    bm = min(block_b, B)
    grid = (pl.cdiv(B, bm), T)      # T innermost: weights stay resident
    mf = mask.astype(xs.dtype)[..., None]              # (T, B, 1)
    return pl.pallas_call(
        _lstm_seq_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, F), lambda i, t: (t, i, 0)),   # x_t
            pl.BlockSpec((1, bm, 1), lambda i, t: (t, i, 0)),   # mask_t
            pl.BlockSpec((F, 4, H), lambda i, t: (0, 0, 0)),    # Wx (pinned)
            pl.BlockSpec((H, 4, H), lambda i, t: (0, 0, 0)),    # Wh (pinned)
            pl.BlockSpec((4, H), lambda i, t: (0, 0)),          # b  (pinned)
        ],
        out_specs=pl.BlockSpec((1, bm, H), lambda i, t: (t, i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, H), xs.dtype),
        scratch_shapes=[
            pltpu.VMEM((bm, H), jnp.float32),           # h carry
            pltpu.VMEM((bm, H), jnp.float32),           # c carry
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(xs, mf, wx4, wh4, b4)
