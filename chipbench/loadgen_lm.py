"""Streams of whole LM requests for the LM serving cells, drawn from a seed.

A copy of the program's LM load generator
(``serving/loadgen.lm_request_stream``; the inter-arrival samplers are
``loadgen.py``'s copy), so that the traffic the benchmark offers cannot
change with the program. It reads only the configuration file (tenants,
their isolated first pass and decode pass, load, QoS) and the traffic
file (scenario, rate scale, session length, the tenants' mix and the
output lengths); ``tests/test_chipbench_lm.py`` checks that it draws
exactly the program's requests for the same seed.

A request of tenant ``c`` asks for ``n_out`` tokens, lognormal about
``out_median`` and clipped to ``[out_min, out_max]``; its limits are
``qos_factor x`` the isolated prefill of ``c`` to the first token and
``qos_factor x`` its isolated decode pass per further token. Load is
offered in simulated time at ``load * rate_scale * eff_parallelism``
over the mix's mean isolated request at the median output.
"""
from __future__ import annotations

import math

import numpy as np

import loadgen


def _passes(cfg: dict) -> tuple[np.ndarray, np.ndarray]:
    t = cfg["tables"]
    return (np.asarray(t["min_first_us"], np.float32),
            np.asarray(t["min_pass_us"], np.float32))


def mix(traffic: dict) -> np.ndarray:
    m = np.asarray(traffic["tenant_mix"], np.float64)
    return m / m.sum()


def rate_per_us(cfg: dict, traffic: dict) -> float:
    """Calibrated arrivals per microsecond of one stream."""
    first, step = _passes(cfg)
    iso = float(np.sum(mix(traffic)
                       * (first + (traffic["out_median"] - 1.0) * step)))
    return (cfg["load"] * traffic["rate_scale"] * cfg["eff_parallelism"]
            / iso)


def requests_per_stream(cfg: dict, traffic: dict) -> int:
    """Requests that arrive in one session of ``session_ticks`` periods."""
    span = traffic["session_ticks"] * cfg["t_s_us"]
    return math.ceil(rate_per_us(cfg, traffic) * span)


def stream(cfg: dict, traffic: dict, n: int,
           rng: np.random.Generator) -> dict[str, np.ndarray]:
    """One arrival-ordered stream as columns: ``model`` (tenant index),
    ``arrival``, ``deadline`` (the TTFT deadline), ``q`` (the TTFT
    limit), ``n_out`` and ``tpot`` (the TPOT limit), in us."""
    first, step = _passes(cfg)
    mult = cfg["qos_factor"] * loadgen.QOS_MULT[cfg["qos_level"]]
    p = mix(traffic)
    inter = loadgen.interarrivals(
        traffic["scenario"], 1.0 / rate_per_us(cfg, traffic), n, rng,
        burst_size=traffic.get("burst_size", 4),
        horizon_us=0.6 * cfg["t_s_us"] * 60)
    arrival = np.cumsum(inter)
    arrival[0] = 0.0
    model = rng.choice(len(p), size=n, p=p)
    n_out = np.clip(np.rint(traffic["out_median"] * np.exp(
        traffic["out_sigma"] * rng.standard_normal(n))),
        traffic["out_min"], traffic["out_max"]).astype(np.int64)
    q = mult * first[model]
    return dict(model=model, arrival=arrival, deadline=arrival + q, q=q,
                n_out=n_out, tpot=mult * step[model])


def streams(cfg: dict, traffic: dict, seed, n_streams: int | None = None
            ) -> list[dict[str, np.ndarray]]:
    """One session's streams: one generator seeded by ``seed``, drawn
    stream by stream."""
    rng = np.random.default_rng(seed)
    n = requests_per_stream(cfg, traffic)
    return [stream(cfg, traffic, n, rng)
            for _ in range(n_streams or cfg["streams"])]
