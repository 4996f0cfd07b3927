"""Operations of the RELMAS policy networks, counted from their shapes.

Matrix products only, at 2 operations per multiply-add; the gates'
sigmoids and tanhs and the masking are left out. A sequence of ``T``
steps runs the whole LSTM at every step (masked steps are computed and
then discarded), so the count does not depend on how many slots hold a
sub-job.

- LSTM step: ``[x, h] (1, in + H) @ (in + H, 4H)``
- head: ``h (1, H) @ (H, H/2)``, ReLU, ``(1, H/2) @ (H/2, out)``
"""
from __future__ import annotations


def lstm_flops(T: int, in_dim: int, hidden: int) -> int:
    return T * 2 * (in_dim + hidden) * 4 * hidden


def head_flops(T: int, hidden: int, out: int) -> int:
    return T * 2 * (hidden * (hidden // 2) + (hidden // 2) * out)


def actor_forward_flops(T: int, feat_dim: int, act_dim: int,
                        hidden: int) -> int:
    """Actor over ``T`` slots (the primer included): LSTM -> FC -> FC."""
    return lstm_flops(T, feat_dim, hidden) + head_flops(T, hidden, act_dim)


def critic_forward_flops(T: int, feat_dim: int, act_dim: int,
                         hidden: int) -> int:
    """Critic over ``T`` slots: input rows are state and action."""
    return (lstm_flops(T, feat_dim + act_dim, hidden)
            + head_flops(T, hidden, 1))


def serve_flops_per_stream_tick(cfg: dict) -> int:
    """One stream's actor pass in one serving tick."""
    M = int(cfg["tables"]["num_sas"])
    return actor_forward_flops(int(cfg["max_rq"]) + 1, 4 + 2 * M, 1 + M,
                               int(cfg["hidden"]))
