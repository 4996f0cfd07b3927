"""Multi-tenant serving driver (the paper's deployment scenario).

Schedules DNN/LM inference requests on the heterogeneous MAS with the
chosen policy and reports global + per-tenant SLA satisfaction.
Tenants: the paper's CNN zoo (Table 2 workloads) or LM architectures
(llm_zoo layerization) as whole requests: a prefill, then one decode
pass per output token, judged on time to first token and time per
output token (``lm_dsv2lite``: DeepSeek-V2-Lite at two prompt lengths).

Two serving modes:

- default: per-episode host loop (``serve_episode_host``) — one full
  trace per episode, per-tenant SLA breakdown printed per episode;
- ``--batched``: the device-resident batched path (``serve_stream``) —
  ``--streams`` concurrent request streams drawn by the
  ``serving.loadgen`` scenario generator (``--scenario``/
  ``--rate-scale``/``--requests``) and served by ONE jitted scheduling
  tick per period across all streams; prints aggregate SLA, the
  per-tenant SLA table, plus the serving telemetry (tick p50 wall
  time, deferrals, queue depth).  LM workloads always take this path:
  their requests (output lengths, two limits) come from the load
  generator.

Telemetry: ``--log-jsonl PATH`` streams schema'd records
(``run_header`` / ``serve_window`` / ``serve_episode`` / ``tenant`` /
``serve_summary`` — see ``repro.telemetry.schema``) alongside the
console lines, the ``run_end`` record carrying the process's compile
counters (``repro.telemetry.compiles``); ``--window N`` sets the
batched mode's tick-window cadence; ``--profile-dir DIR`` captures a
``jax.profiler`` trace of the serving loop, where the host spans of
``serve_stream`` (``serve.session``/``serve.tick``/...) and the
``serve``/``episode`` spans lie on the device's clock.
``scripts/metrics_summary.py`` validates/renders the stream.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --workload mixed \
      --policy relmas --ckpt runs/mixed_medium/best
  PYTHONPATH=src python -m repro.launch.serve --workload lm_dsv2lite \
      --policy herald --streams 8 --requests 4 --t-s 500
  PYTHONPATH=src python -m repro.launch.serve --workload light \
      --batched --streams 32 --scenario burst --rate-scale 1.5
"""
from __future__ import annotations

import argparse
import json

import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.serving.service import MultiTenantService
from repro.sim.arrivals import ArrivalConfig
from repro.sim.env import EnvConfig
from repro.telemetry import (compile_counts, console_line,
                             install_compile_counter, make_telemetry,
                             profile_trace)
from repro.workloads import build_registry, build_llm_registry, \
    LM_WORKLOADS, WORKLOADS


def build_service(args) -> MultiTenantService:
    if args.workload in LM_WORKLOADS:
        registry = build_llm_registry(args.workload, seq=args.seq,
                                      mas=args.fleet or "datacenter")
        t_s = 2000.0                      # LM layer latencies are larger
    else:
        registry = build_registry(args.workload, mas=args.fleet or "paper6")
        t_s = 500.0
    # bandwidth <= 0 -> SchedulingEnv resolves the fleet's dram_gbps
    ecfg = EnvConfig(t_s_us=args.t_s if args.t_s > 0 else t_s,
                     periods=args.periods, max_rq=args.max_rq,
                     max_jobs=args.max_jobs,
                     bandwidth_gbps=args.bandwidth)
    arr = ArrivalConfig(max_jobs=args.max_jobs, load=args.load,
                        qos_factor=args.qos_factor, qos_level=args.qos,
                        horizon_us=ecfg.horizon_us, slack_us=2 * ecfg.t_s_us)
    return MultiTenantService(registry, policy=args.policy,
                              ckpt_dir=args.ckpt, hidden=args.hidden,
                              env_cfg=ecfg, arrivals=arr)


def serve_batched(svc: MultiTenantService, args, tele) -> dict:
    """Drive the device-resident batched path on loadgen traffic."""
    from repro.serving.loadgen import LoadGenConfig, request_streams
    lg = LoadGenConfig(scenario=args.scenario, rate_scale=args.rate_scale,
                       n_requests=args.requests,
                       qos_factor=args.qos_factor, qos_level=args.qos)
    reqs = request_streams(svc.env, lg, args.streams, seed=9000)
    # the profiler first: a span opened before it starts is not traced
    with profile_trace(args.profile_dir), tele.span("serve"):
        res = svc.serve_stream(reqs, tick_k=args.tick_k, seed=9000,
                               telemetry=tele, window=args.window)
    agg, st = res["aggregate"], res["stats"]
    tick_p50 = float(np.median(st["tick_wall_us"]))
    tele.note(f"[serve batched] streams={args.streams} "
              f"scenario={args.scenario} rate={args.rate_scale} "
              f"sla={agg['sla_rate']:.3f} jobs={agg['counted']} "
              f"energy={agg['energy_uj']:.0f}uJ")
    tele.note(f"    ticks={st['ticks']} tick_p50={tick_p50:.0f}us "
              f"admitted={st['admitted']} deferred={st['deferred']} "
              f"unserved={st['unserved']} mean_depth={st['mean_depth']:.1f}")
    out = {"policy": args.policy, "workload": args.workload,
           "scenario": args.scenario, "rate_scale": args.rate_scale,
           "streams": args.streams, "sla_rate": agg["sla_rate"],
           "counted": agg["counted"], "deferred": st["deferred"],
           "tick_p50_us": tick_p50}
    if "ttft_rate" in agg:
        out.update(ttft_rate=agg["ttft_rate"], tpot_rate=agg["tpot_rate"])
    tele.emit("run_end", summary=out, compile=compile_counts())
    tele.close()
    console_line(json.dumps(out))
    return out


def main(argv=None):
    use_compile_cache()
    install_compile_counter()
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="mixed",
                    choices=list(WORKLOADS) + list(LM_WORKLOADS))
    ap.add_argument("--policy", default="relmas",
                    choices=["relmas", "fcfs", "prema", "herald"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--episodes", type=int, default=3)
    ap.add_argument("--periods", type=int, default=60)
    ap.add_argument("--qos", default="medium",
                    choices=["high", "medium", "low"])
    ap.add_argument("--qos-factor", type=float, default=3.0)
    ap.add_argument("--load", type=float, default=0.9)
    ap.add_argument("--bandwidth", type=float, default=-1.0,
                    help="shared DRAM GB/s (<=0: fleet default)")
    ap.add_argument("--fleet", default=None,
                    help="accelerator fleet preset "
                         "(repro.costmodel.fleets; default: paper6, "
                         "or datacenter for lm_* workloads)")
    ap.add_argument("--t-s", type=float, default=-1.0)
    ap.add_argument("--max-rq", type=int, default=96)
    ap.add_argument("--max-jobs", type=int, default=64)
    ap.add_argument("--seq", type=int, default=128,
                    help="prompt tokens of an LM tenant named by its arch")
    ap.add_argument("--batched", action="store_true",
                    help="serve loadgen streams through the batched "
                         "single-dispatch tick instead of per-episode "
                         "host loops")
    ap.add_argument("--streams", type=int, default=16,
                    help="concurrent request streams (--batched)")
    ap.add_argument("--tick-k", type=int, default=8,
                    help="max admissions per stream per tick (--batched)")
    ap.add_argument("--scenario", default="steady",
                    choices=["default", "steady", "burst", "diurnal",
                             "heavy_tail"],
                    help="loadgen arrival scenario (--batched)")
    ap.add_argument("--rate-scale", type=float, default=1.0,
                    help="offered-load multiplier on the calibrated "
                         "base arrival rate (--batched)")
    ap.add_argument("--requests", type=int, default=32,
                    help="requests per stream (--batched)")
    ap.add_argument("--log-jsonl", default="",
                    help="stream schema'd JSONL telemetry records to this "
                         "path (validate with scripts/metrics_summary.py)")
    ap.add_argument("--window", type=int, default=16,
                    help="serve_window record cadence in ticks "
                         "(--batched; 0 disables windows)")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the serving "
                         "loop into this directory")
    args = ap.parse_args(argv)

    svc = build_service(args)
    tele = make_telemetry(jsonl_path=args.log_jsonl or None)
    tele.run_header("serve", {k: v for k, v in vars(args).items()})
    if args.batched or svc.env.reenters:
        return serve_batched(svc, args, tele)
    rates, energies = [], []
    with profile_trace(args.profile_dir):
        for ep in range(args.episodes):
            with tele.span("episode", episode=ep):
                m = svc.run_episode(seed=9000 + ep)
            rates.append(m["sla_rate"])
            energies.append(m["energy_uj"])
            tele.emit("serve_episode", episode=ep,
                      sla_rate=float(m["sla_rate"]),
                      counted=int(m["counted"]),
                      energy_uj=float(m["energy_uj"]))
            for tname, tm in m["per_tenant"].items():
                if tm["jobs"]:
                    tele.emit("tenant", tenant=tname, jobs=tm["jobs"],
                              sla_rate=tm["sla_rate"])
    out = {"policy": args.policy, "workload": args.workload,
           "sla_rate_mean": float(np.mean(rates)),
           "energy_uj_mean": float(np.mean(energies))}
    tele.emit("run_end", summary=out, compile=compile_counts())
    tele.close()
    console_line(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
