"""Pallas TPU kernels for the RELMAS policy's LSTM.

Each kernel ships as a triplet:
  <name>/<name>.py — the ``pl.pallas_call`` kernel with explicit
                     BlockSpec VMEM tiling (TPU target);
  <name>/ops.py    — the jit'd public wrapper (shape plumbing, block
                     selection, interpret-mode fallback on CPU);
  <name>/ref.py    — the pure-jnp oracle used by tests.

Kernels:
  lstm_cell — fused LSTM cell, one timestep (the paper deploys the
              policy on a Simba SA — on TPU the cell is one fused
              VMEM-resident MXU kernel).
  lstm_seq  — the whole ready-queue sequence in one kernel, the
              weights resident in VMEM across timesteps; the actor's
              recurrence under ``PolicyConfig.use_pallas`` (off by
              default).
"""
