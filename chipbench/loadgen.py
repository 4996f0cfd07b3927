"""Request streams for the serving cells, drawn from a seed.

A copy of the serving load generator (``serving/loadgen.request_stream``
with the inter-arrival samplers of ``sim/arrivals._interarrivals``), so
that the traffic the benchmark offers cannot change with the program.
It reads only the configuration file (tenants, their minimum isolated
latencies, load, QoS) and the traffic file (scenario, rate scale,
session length); ``tests/test_loadgen.py`` checks that it draws exactly
the program's requests for the same seed.

Load is offered in simulated time: a stream of ``n`` requests at the
calibrated rate ``lam = load * rate_scale * eff_parallelism /
mean(min_lat)`` spans about ``n / lam`` microseconds of the session.
"""
from __future__ import annotations

import math

import numpy as np

QOS_MULT = {"high": 0.8, "medium": 1.0, "low": 1.2}


def interarrivals(scenario: str, mean_ia: float, n: int,
                  rng: np.random.Generator, *, pareto_shape: float = 2.0,
                  burst_size: int = 4, horizon_us: float = 18_000.0
                  ) -> np.ndarray:
    """``n`` inter-arrival times with mean ``mean_ia`` for a scenario."""
    if scenario in ("default", "heavy_tail"):
        a = pareto_shape if scenario == "default" else 1.2
        clip = 50.0 if scenario == "default" else 200.0
        xm = mean_ia * (a - 1.0) / a
        inter = xm * (1.0 + rng.pareto(a, size=n))
        return np.minimum(inter, clip * mean_ia)
    if scenario == "steady":
        return mean_ia * rng.uniform(0.8, 1.2, size=n)
    if scenario == "burst":
        bs = max(1, burst_size)
        intra = 0.1 * mean_ia
        gap = bs * mean_ia - (bs - 1) * intra
        inter = np.full(n, intra)
        inter[::bs] = gap * rng.uniform(0.5, 1.5, size=len(inter[::bs]))
        return inter
    if scenario == "diurnal":
        base = 1.0 / mean_ia
        peak = 1.5 * base
        H = max(horizon_us, mean_ia)
        inter = np.empty(n)
        t = prev = 0.0
        for i in range(n):
            while True:
                t += rng.exponential(1.0 / peak)
                rate = base * (1.0 + 0.5 * np.sin(2.0 * np.pi * t / H))
                if rng.uniform() <= rate / peak:
                    break
            inter[i] = t - prev
            prev = t
        return inter
    raise ValueError(f"unknown scenario {scenario!r}")


def rate_per_us(cfg: dict, traffic: dict) -> float:
    """Calibrated arrivals per microsecond of one stream."""
    min_lat = np.asarray(cfg["tables"]["min_lat_us"], np.float32)
    load = cfg["load"] * traffic["rate_scale"]
    return load * cfg["eff_parallelism"] / float(np.mean(min_lat))


def requests_per_stream(cfg: dict, traffic: dict) -> int:
    """Requests that arrive in one session of ``session_ticks`` periods."""
    span = traffic["session_ticks"] * cfg["t_s_us"]
    return math.ceil(rate_per_us(cfg, traffic) * span)


def stream(cfg: dict, traffic: dict, n: int,
           rng: np.random.Generator) -> dict[str, np.ndarray]:
    """One arrival-ordered stream as columns: ``model`` (tenant index),
    ``arrival``, ``deadline`` and ``q`` (the SLA budget), in us."""
    min_lat = np.asarray(cfg["tables"]["min_lat_us"], np.float32)
    mult = cfg["qos_factor"] * QOS_MULT[cfg["qos_level"]]
    lam = rate_per_us(cfg, traffic)
    inter = interarrivals(traffic["scenario"], 1.0 / lam, n, rng,
                          pareto_shape=traffic.get("pareto_shape", 2.0),
                          burst_size=traffic.get("burst_size", 4),
                          horizon_us=0.6 * cfg["t_s_us"] * 60)
    arrival = np.cumsum(inter)
    arrival[0] = 0.0
    model = rng.integers(0, len(min_lat), size=n)
    q = mult * min_lat[model] + cfg["slack_us"]
    return dict(model=model, arrival=arrival, deadline=arrival + q, q=q)


def streams(cfg: dict, traffic: dict, seed, n_streams: int | None = None
            ) -> list[dict[str, np.ndarray]]:
    """One session's streams: one generator seeded by ``seed`` (an int,
    or a list such as ``[run_seed, session]``), drawn stream by stream."""
    rng = np.random.default_rng(seed)
    n = requests_per_stream(cfg, traffic)
    return [stream(cfg, traffic, n, rng)
            for _ in range(n_streams or cfg["streams"])]
