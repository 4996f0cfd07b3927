"""Load generator: scenario-preset request streams at configurable rates.

Replays the arrival-process presets of ``repro.sim.arrivals``
(steady / burst / diurnal / heavy_tail / default Pareto) as *serving*
request streams: unlike an episode trace (fixed ``max_jobs`` slots,
horizon-padded), a stream is an arbitrary-length arrival-ordered list
of :class:`~repro.serving.request.Request` objects that the batched
serving loop admits tick by tick — the queue capacity, not the trace
shape, bounds concurrency, and offered load is a free knob
(``rate_scale`` multiplies the env's calibrated base arrival rate, so
``rate_scale > 1`` drives the scheduler past saturation and SLA-under-
load is measured, not assumed).

The same inter-arrival samplers as the episode path
(:func:`repro.sim.arrivals._interarrivals`) draw the stream, so a
scenario means the same thing to the trainer, the sweep grid, and the
serving bench.  :func:`trace_to_requests` converts an episode trace
into the equivalent stream — replaying it through the batched tick
reproduces the host-loop reference bit-for-bit (the parity tests).

A re-entering registry (LM tenants, ``SchedulingEnv.reenters``) draws
whole LM requests instead (:func:`lm_request_stream`): tenants (request
classes) by ``tenant_mix``, output tokens lognormal about
``out_median``, and two limits per request — TTFT ``mult x`` the
class's isolated prefill, TPOT ``mult x`` its isolated decode pass.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.serving.request import Request
from repro.sim.arrivals import (QOS_MULT, SCENARIOS, ArrivalConfig,
                                _interarrivals)
from repro.sim.engine import INF


# output tokens of an LM request: lognormal about the median with this
# log-space sigma (chat answers: p90 about 2.8x the median), rounded and
# clipped to [OUT_MIN, OUT_MAX]
OUT_SIGMA, OUT_MIN, OUT_MAX = 0.8, 8, 256


@dataclasses.dataclass(frozen=True)
class LoadGenConfig:
    """One request stream's shape: scenario, rate, size, QoS."""
    scenario: str = "default"
    rate_scale: float = 1.0    # multiplier on the env's base arrival rate
    n_requests: int = 128      # stream length (not capped by max_jobs)
    qos_factor: float | None = None   # None: the env's ArrivalConfig's
    qos_level: str | None = None
    # whole LM requests (a re-entering registry): tenant probabilities
    # (None: uniform) and the median output tokens
    tenant_mix: tuple[float, ...] | None = None
    out_median: float = 64.0

    def __post_init__(self):
        if self.scenario not in SCENARIOS:
            raise ValueError(f"unknown scenario {self.scenario!r}; "
                             f"pick one of {SCENARIOS}")
        if self.rate_scale <= 0:
            raise ValueError(f"rate_scale must be positive, "
                             f"got {self.rate_scale}")
        if self.n_requests <= 0:
            raise ValueError(f"n_requests must be positive, "
                             f"got {self.n_requests}")


def _qos_mult(env, cfg: LoadGenConfig) -> tuple[float, str, float]:
    """``(qos_factor, qos_level, SLA multiplier)`` of a stream; a
    non-positive multiplier raises."""
    qf = cfg.qos_factor if cfg.qos_factor is not None else \
        env.arrivals.qos_factor
    level = cfg.qos_level if cfg.qos_level is not None else \
        env.arrivals.qos_level
    mult = qf * QOS_MULT[level]
    if mult <= 0:
        raise ValueError(f"non-positive SLA multiplier {mult} "
                         f"(qos_factor={qf}, level={level!r})")
    return qf, level, mult


def request_stream(env, cfg: LoadGenConfig,
                   rng: np.random.Generator) -> list[Request]:
    """Draw one arrival-ordered request stream against ``env``'s fleet.

    Rate calibration matches :func:`repro.sim.arrivals.generate_trace`
    (``lam = load * eff_parallelism / mean_min_latency``) with
    ``load`` scaled by ``cfg.rate_scale``; SLA budgets are
    ``qos_factor * QOS_MULT[level] * min_isolated_latency + slack`` per
    drawn model, exactly the episode path's deadlines.  A non-positive
    effective SLA multiplier is rejected here (it would poison every
    deadline downstream).
    """
    base = env.arrivals
    qf, level, mult = _qos_mult(env, cfg)
    acfg = dataclasses.replace(base, scenario=cfg.scenario,
                               load=base.load * cfg.rate_scale,
                               qos_factor=qf, qos_level=level)
    min_lat = np.asarray(env.min_lat)
    lam = acfg.load * acfg.eff_parallelism / float(np.mean(min_lat))
    inter = _interarrivals(acfg, 1.0 / lam, cfg.n_requests, rng)
    arrival = np.cumsum(inter)
    arrival[0] = 0.0
    model = rng.integers(0, len(min_lat), size=cfg.n_requests)
    q = mult * min_lat[model] + acfg.slack_us
    names = env.registry.model_names
    return [Request(rid=i, tenant=names[int(model[i])],
                    arrival_us=float(arrival[i]),
                    deadline_us=float(arrival[i] + q[i]),
                    q_us=float(q[i]))
            for i in range(cfg.n_requests)]


def lm_request_stream(env, cfg: LoadGenConfig,
                      rng: np.random.Generator) -> list[Request]:
    """Draw one arrival-ordered stream of whole LM requests.

    The base rate is ``load * eff_parallelism`` over the mix's mean
    isolated request at the median output (the first pass plus
    ``out_median - 1`` decode passes), times ``rate_scale``.  Drawn in
    this order: the inter-arrivals, each request's tenant, its output
    tokens.  Limits of the request's tenant: TTFT ``mult * min_first``
    (the deadline, and ``q``), TPOT ``mult * min_pass``.
    """
    _, _, mult = _qos_mult(env, cfg)
    first, step = env.min_first, env.min_pass
    mix = np.asarray(np.ones(len(first)) if cfg.tenant_mix is None
                     else cfg.tenant_mix, np.float64)
    if mix.shape != first.shape or np.any(mix < 0) or mix.sum() <= 0:
        raise ValueError(f"tenant_mix {cfg.tenant_mix} does not weigh the "
                         f"{len(first)} tenants")
    mix = mix / mix.sum()
    iso = float(np.sum(mix * (first + (cfg.out_median - 1.0) * step)))
    base = env.arrivals
    lam = base.load * cfg.rate_scale * base.eff_parallelism / iso
    n = cfg.n_requests
    arrival = np.cumsum(_interarrivals(
        dataclasses.replace(base, scenario=cfg.scenario), 1.0 / lam, n, rng))
    arrival[0] = 0.0
    model = rng.choice(len(mix), size=n, p=mix)
    n_out = np.clip(np.rint(cfg.out_median * np.exp(
        OUT_SIGMA * rng.standard_normal(n))), OUT_MIN, OUT_MAX)
    ttft = mult * first[model]
    tpot = mult * step[model]
    names = env.registry.model_names
    return [Request(rid=i, tenant=names[int(model[i])],
                    arrival_us=float(arrival[i]),
                    deadline_us=float(arrival[i] + ttft[i]),
                    q_us=float(ttft[i]), n_out=int(n_out[i]),
                    tpot_us=float(tpot[i]))
            for i in range(n)]


def request_streams(env, cfg: LoadGenConfig, streams: int,
                    seed: int = 0) -> list[list[Request]]:
    """``streams`` independent draws of the configured stream (one rng,
    split per stream — episode-style i.i.d. traffic)."""
    rng = np.random.default_rng(seed)
    draw = lm_request_stream if env.reenters else request_stream
    return [draw(env, cfg, rng) for _ in range(streams)]


def requests_to_trace(env, reqs: list[Request]):
    """Request stream -> the equivalent episode trace (the inverse of
    :func:`trace_to_requests`).

    Rows land in arrival order at the lowest slot indices — exactly the
    slot assignment :func:`repro.serving.queue.queue_admit` produces
    when the same stream is replayed into an empty queue, so the
    host-loop reference (``serve_trace_host``) and the batched tick path
    serve bit-identical episodes from one stream (the benchmark's
    equal-SLA anchor).  The stream must fit the trace shape
    (``len(reqs) <= cfg.max_jobs``).
    """
    from repro.serving.request import resolve_request
    J = env.cfg.max_jobs
    if len(reqs) > J:
        raise ValueError(f"{len(reqs)} requests > max_jobs {J}; "
                         f"shorten the stream or raise cfg.max_jobs")
    names = env.registry.model_names
    tr = dict(arrival=np.full((J,), INF, np.float32),
              deadline=np.full((J,), INF, np.float32),
              q=np.ones((J,), np.float32),
              model=np.zeros((J,), np.int32),
              n_out=np.ones((J,), np.int32),
              tpot=np.zeros((J,), np.float32))
    for j, r in enumerate(sorted(reqs, key=lambda r: r.arrival_us)):
        row = resolve_request(r, names)
        tr["arrival"][j] = row.arrival_us
        tr["deadline"][j] = row.deadline_us
        tr["q"][j] = row.q_us
        tr["model"][j] = row.model
        tr["n_out"][j] = row.n_out
        tr["tpot"][j] = row.tpot_us
    return env._finish_trace(tr)


def trace_to_requests(env, trace) -> list[Request]:
    """Episode trace -> the equivalent arrival-ordered request stream.

    Horizon-padding rows (``arrival >= INF/2``) are dropped; ``rid`` is
    the trace's slot index, so replaying the stream into an empty queue
    reassigns every job its original slot (arrivals are nondecreasing)
    and the batched tick path is bit-identical to running the trace
    through the host reference loop.
    """
    arrival = np.asarray(trace["arrival"])
    deadline = np.asarray(trace["deadline"])
    model = np.asarray(trace["model"])
    q = np.asarray(trace["q"])
    names = env.registry.model_names
    reqs = [Request(rid=j, tenant=names[int(model[j])],
                    arrival_us=float(arrival[j]),
                    deadline_us=float(deadline[j]), q_us=float(q[j]))
            for j in range(arrival.shape[0]) if arrival[j] < INF / 2]
    return sorted(reqs, key=lambda r: r.arrival_us)
