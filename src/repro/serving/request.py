"""Inference request objects and their admission into the device queue.

Admission validation lives here: :func:`resolve_request` is the single
place a host-side :class:`Request` becomes a device-queue row, and it
rejects malformed requests with clear errors (unknown model id, a
non-positive SLA budget) *before* they can scatter poisoned rows into
the device-resident queue — a bad deadline or an out-of-range model
index would otherwise silently corrupt every downstream SLA number.

An LM request (a tenant that re-enters per output token) asks for
``n_out`` output tokens: one prefill pass, then ``n_out - 1`` decode
passes.  Its ``deadline_us`` is the time-to-first-token limit
counted from arrival, and ``tpot_us`` the time-per-output-token limit:
its last token is due ``tpot_us * (n_out - 1)`` after its first.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple


@dataclasses.dataclass
class Request:
    rid: int
    tenant: str              # model name (registry key)
    arrival_us: float
    deadline_us: float
    # SLA budget used for reward-slack normalization; None derives
    # deadline - arrival (trace replays pass the trace's exact q so the
    # batched path stays bit-identical to the reference)
    q_us: float | None = None
    # whole LM requests: output tokens (passes) and the TPOT limit
    n_out: int = 1
    tpot_us: float | None = None
    # filled by the service
    finish_us: float = float("inf")
    hit: bool = False


class QueueRow(NamedTuple):
    """One request as the device queue holds it."""
    model: int
    arrival_us: float
    deadline_us: float
    q_us: float
    n_out: int
    tpot_us: float


def resolve_request(req: Request, model_names) -> QueueRow:
    """Validate + resolve one request into its device-queue row.

    Raises ``ValueError`` for an unknown model id (tenant not served by
    the registry), a non-positive SLA budget (``deadline <= arrival``, or
    an explicit ``q_us <= 0``), fewer than one output token, or a
    non-positive TPOT limit (required once a request asks for more than
    one token) — the ways a request can poison the queue's env rows.
    A queue whose tenants run one pass refuses more than one token
    (:meth:`repro.sim.env.SchedulingEnv.check_passes`).
    """
    try:
        mid = list(model_names).index(req.tenant)
    except ValueError:
        raise ValueError(
            f"request {req.rid}: unknown model id {req.tenant!r}; "
            f"this registry serves {sorted(model_names)}") from None
    budget = req.deadline_us - req.arrival_us
    q = req.q_us if req.q_us is not None else budget
    if budget <= 0 or q <= 0:
        raise ValueError(
            f"request {req.rid} ({req.tenant}): non-positive SLA budget "
            f"(arrival={req.arrival_us}, deadline={req.deadline_us}, "
            f"q={q}); the SLA multiplier must be positive")
    n_out = int(req.n_out)
    if n_out < 1:
        raise ValueError(
            f"request {req.rid} ({req.tenant}): n_out={req.n_out} output "
            f"tokens; a request asks for at least 1")
    tpot = req.tpot_us
    if tpot is None and n_out == 1:
        tpot = 0.0
    elif tpot is None or tpot <= 0:
        raise ValueError(
            f"request {req.rid} ({req.tenant}): TPOT limit "
            f"tpot_us={tpot} must be positive for {n_out} output tokens")
    return QueueRow(mid, float(req.arrival_us), float(req.deadline_us),
                    float(q), n_out, float(tpot))

