"""Serving throughput + decision latency: host loop vs batched tick.

Measures the device-resident batched serving path
(``MultiTenantService.serve_stream``: ONE jitted, donated dispatch per
scheduling tick across all streams, fed by the ``serving.loadgen``
scenario generator) against the per-period host-loop reference
(``serve_episode_host``: one dispatch per period per stream, trace
synthesized upfront — how the repo served requests before this path).

Sections (written to ``BENCH_serving.json``; schema in
docs/BENCHMARKS.md):

- ``guard`` — the CI regression/acceptance cell:
  * *parity*: the same ``streams`` episode workloads run through BOTH
    paths (``trace_to_requests`` replays each trace into the queue);
    ``sla_equal`` asserts every stream's SLA / hit / counted / energy /
    per-tenant numbers are bit-identical — the "equal SLA" half of the
    acceptance bar, established exactly rather than statistically.
  * *decision latency*: p50/p99 wall time of the batched tick (the one
    dispatch that admits + schedules + retires all streams), the
    per-stream amortized cost, the host path's per-period dispatch
    p50/p99, and the scheduler-overhead fraction of the ``t_s_us``
    scheduling period each implies (the Fig. 5 overhead axis, measured
    on the serving path).
  * *throughput*: sustained requests/sec (completed jobs / wall-clock,
    median of ``--repeats`` runs) for both arms on steady traffic at
    rate 1.0, and ``speedup``; ``meets_5x`` records the >= 5x
    acceptance bar on the CI box.
- ``scenarios`` — SLA-under-load sweep: requests/sec, achieved SLA
  rate, mean queue depth and deferral counts for each arrival-scenario
  preset x offered-rate cell (``rate_scale`` multiplies the calibrated
  base rate — 2.0 drives the scheduler past saturation, so SLA under
  overload is measured, not assumed).

All scenario cells reuse ONE compiled tick (the stream count is the
compile key; scenario/rate are trace data), so the sweep adds no
recompiles over the guard.  Compile time is excluded everywhere via
untimed warmup calls.  The bench env is CI-sized (R32/J16, 20 periods)
— small enough that the host arm's fixed per-dispatch overhead is the
honest bottleneck it is in deployment, large enough to saturate the
queue.

Usage:
  PYTHONPATH=src python -m benchmarks.serving_bench            # full
  PYTHONPATH=src python -m benchmarks.serving_bench --smoke    # CI smoke
  PYTHONPATH=src python -m benchmarks.serving_bench --only guard
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from benchmarks.common import REPO, bench_meta
from repro.launch.compile_cache import use_compile_cache
from repro.serving import (LoadGenConfig, MultiTenantService,
                           request_streams, trace_to_requests)
from repro.sim.env import EnvConfig
from repro.workloads import build_registry

# bench env shape: small periods keep the run CI-sized; R32/J16 is the
# regime where the host loop's per-dispatch overhead dominates honestly
# (at training shapes the sim itself dominates both arms)
BENCH_CFG = dict(periods=20, max_rq=32, max_jobs=16)

PARITY_KEYS = ("hits", "counted", "arrived", "sla_rate", "energy_uj")


def make_service(workload: str = "light") -> MultiTenantService:
    return MultiTenantService(build_registry(workload), policy="relmas",
                              env_cfg=EnvConfig(**BENCH_CFG))


def _pcts(xs, ps=(50, 99)):
    return {f"p{p}": round(float(np.percentile(np.asarray(xs), p)), 1)
            for p in ps}


def run_guard(svc: MultiTenantService, *, streams: int = 96,
              repeats: int = 5, n_requests: int = 32, seed: int = 0) -> dict:
    env, cfg = svc.env, svc.env.cfg
    K = cfg.max_jobs

    # ---- parity: same workloads, both paths, bit-identical metrics --
    traces = [env.new_episode(np.random.default_rng(1000 + s))[0]
              for s in range(streams)]
    refs = [svc.serve_trace_host(tr, seed=7) for tr in traces]
    out = svc.serve_stream([trace_to_requests(env, tr) for tr in traces],
                           tick_k=K, seed=7)     # also compiles the tick
    mism = [s for s, (ref, m) in enumerate(zip(refs, out["metrics"]))
            if any(ref[k] != m[k] for k in PARITY_KEYS)
            or ref["per_tenant"] != m["per_tenant"]]
    sla_equal = not mism

    # ---- host arm: requests/sec + per-period decision latency -------
    host_runs = max(repeats, 3)
    rps_host_runs, host_period_us = [], []
    svc.serve_episode_host(seed=seed)                    # warm
    for e in range(host_runs):
        t0 = time.perf_counter()
        m = svc.serve_episode_host(seed=seed + 1 + e)
        rps_host_runs.append(m["counted"] / (time.perf_counter() - t0))
    # per-dispatch latency, measured blocking (serve_episode_host
    # pipelines dispatches, so its wall time is the honest rps arm but
    # hides individual dispatch latency)
    trace, state = env.new_episode(np.random.default_rng(seed))
    key = jax.random.PRNGKey(seed)
    for _ in range(cfg.periods * 3):
        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        state, _, _ = svc._period(svc.params, state, trace, sub, sigma=0.0)
        jax.block_until_ready(state["t"])
        host_period_us.append((time.perf_counter() - t0) * 1e6)

    # ---- batched arm: requests/sec on loadgen traffic ---------------
    lg = LoadGenConfig(scenario="steady", rate_scale=1.0,
                       n_requests=n_requests)
    reqs = request_streams(env, lg, streams, seed=5)
    rps_batched_runs, sla_runs, tick_us = [], [], []
    for r in range(repeats):
        t0 = time.perf_counter()
        res = svc.serve_stream(reqs, tick_k=K, seed=10 + r)
        wall = time.perf_counter() - t0
        rps_batched_runs.append(res["aggregate"]["counted"] / wall)
        sla_runs.append(res["aggregate"]["sla_rate"])
        tick_us.extend(res["stats"]["tick_wall_us"])

    rps_b = float(np.median(rps_batched_runs))
    rps_h = float(np.median(rps_host_runs))
    tick_p = _pcts(tick_us)
    host_p = _pcts(host_period_us)
    speedup = rps_b / rps_h
    guard = dict(
        meta=dict(**bench_meta(),
                  workload="light", streams=streams, tick_k=K,
                  repeats=repeats, n_requests=n_requests, **BENCH_CFG),
        decision_latency=dict(
            tick_p50_us=tick_p["p50"], tick_p99_us=tick_p["p99"],
            per_stream_p50_us=round(tick_p["p50"] / streams, 2),
            host_period_p50_us=host_p["p50"],
            host_period_p99_us=host_p["p99"],
            # scheduling wall time as a fraction of the t_s_us period it
            # schedules — the serving-side Fig. 5 overhead number
            overhead_frac_batched=round(tick_p["p50"] / streams
                                        / cfg.t_s_us, 4),
            overhead_frac_host=round(host_p["p50"] / cfg.t_s_us, 4),
            # machine-invariant partner for the latency regression
            # guard (both arms measured in the same run)
            latency_ratio=round(tick_p["p99"] / host_p["p50"], 3)),
        throughput=dict(
            scenario="steady", rate_scale=1.0,
            rps_batched=round(rps_b, 1), rps_host=round(rps_h, 1),
            rps_batched_runs=[round(x, 1) for x in rps_batched_runs],
            rps_host_runs=[round(x, 1) for x in rps_host_runs],
            speedup=round(speedup, 2),
            sla_batched=round(float(np.median(sla_runs)), 4),
            sla_host=round(float(np.median(
                [r["sla_rate"] for r in refs])), 4),
            sla_equal=sla_equal, mismatched_streams=mism,
            meets_5x=bool(speedup >= 5.0)))
    print("serving_guard," + json.dumps(guard["throughput"]), flush=True)
    print("serving_latency," + json.dumps(guard["decision_latency"]),
          flush=True)
    return guard


def run_scenarios(svc: MultiTenantService, *, streams: int = 96,
                  scenarios=("steady", "burst", "diurnal", "heavy_tail"),
                  rates=(0.5, 1.0, 2.0), n_requests: int = 32,
                  seed: int = 0, warm: bool = True) -> dict:
    """SLA-under-load grid: one serve_stream run per scenario x rate."""
    env, K = svc.env, svc.env.cfg.max_jobs
    if warm:   # compile the S-stream tick outside the timed cells
        lg = LoadGenConfig(scenario="steady", n_requests=4)
        svc.serve_stream(request_streams(env, lg, streams, seed=1),
                         tick_k=K, seed=0)
    cells = {}
    for sc in scenarios:
        for rate in rates:
            n = max(8, int(round(n_requests * rate)))
            lg = LoadGenConfig(scenario=sc, rate_scale=rate, n_requests=n)
            reqs = request_streams(env, lg, streams, seed=seed + 17)
            t0 = time.perf_counter()
            res = svc.serve_stream(reqs, tick_k=K, seed=seed)
            wall = time.perf_counter() - t0
            agg, st = res["aggregate"], res["stats"]
            cells[f"{sc}/{rate}"] = dict(
                rps=round(agg["counted"] / wall, 1),
                sla_under_load=round(agg["sla_rate"], 4),
                mean_depth=round(st["mean_depth"] / streams, 2),
                deferred=st["deferred"], arrived=agg["arrived"],
                counted=agg["counted"], unserved=st["unserved"])
            print(f"serving_cell,{sc}/{rate},"
                  + json.dumps(cells[f"{sc}/{rate}"]), flush=True)
    return dict(streams=streams, n_requests=n_requests, cells=cells)


SECTIONS = ("guard", "scenarios")


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--streams", type=int, default=96,
                    help="concurrent request streams (the tick's vmap "
                         "width; one compile per distinct value)")
    ap.add_argument("--repeats", type=int, default=5,
                    help="timed runs per throughput arm (medians reported)")
    ap.add_argument("--n-requests", type=int, default=32,
                    help="requests per stream at rate 1.0")
    ap.add_argument("--scenarios", default="steady,burst,diurnal,heavy_tail")
    ap.add_argument("--rates", default="0.5,1.0,2.0")
    ap.add_argument("--only", choices=SECTIONS, default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized: 8 streams, steady@0.5 only, 2 repeats")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_serving.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.streams, args.repeats = 8, 2
        args.scenarios, args.rates = "steady", "0.5"

    # partial runs merge into an existing artifact (same contract as
    # rollout_throughput: the CI guard re-measures one section without
    # clobbering the committed others)
    results = {}
    if args.only is not None and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                results = {k: v for k, v in json.load(f).items()
                           if k in SECTIONS}
        except (json.JSONDecodeError, OSError):
            results = {}

    svc = make_service()
    ran_guard = False
    if args.only in (None, "guard"):
        results["guard"] = run_guard(svc, streams=args.streams,
                                     repeats=args.repeats,
                                     n_requests=args.n_requests)
        ran_guard = True
    if args.only in (None, "scenarios"):
        results["scenarios"] = run_scenarios(
            svc, streams=args.streams,
            scenarios=tuple(args.scenarios.split(",")),
            rates=tuple(float(r) for r in args.rates.split(",")),
            n_requests=args.n_requests, warm=not ran_guard)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"serving_json,{args.out}", flush=True)
    return results


if __name__ == "__main__":
    main()
