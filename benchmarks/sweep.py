"""Scenario-sweep harness: fleets x arrival presets x schedulers x bandwidths.

Sweeps the full evaluation grid the batched pipeline unlocks —
accelerator-fleet presets (``repro.costmodel.fleets``) x ``{default,
steady, burst, diurnal, heavy_tail}`` arrival scenarios x ``{fcfs,
prema, herald, magma, relmas}`` x shared-DRAM bandwidths — with ONE
jitted evaluator call per cell.  Scenario presets only change the
host-side trace data (``arrivals=`` override), so each compiled
(env, policy) evaluator is reused across every scenario cell and only a
*fleet* (or bandwidth/env-shape) change recompiles; MAGMA runs its
whole per-period genetic search inside the episode scan
(``repro.core.baselines.magma_search_scan``), batched over seeds like
any other policy.

A fourth grid axis sweeps *fleet churn* presets
(``repro.sim.churn.CHURN_SCENARIOS``): each non-``none`` preset draws a
seeded per-period event schedule (SA failures, throttles, slowdowns,
elastic joins) that is — like the arrival scenarios — pure trace data,
so churn cells reuse the compiled evaluators too (one extra compile per
(env, policy) for the churn-carrying episode program).

Usage:
  PYTHONPATH=src python -m benchmarks.sweep             # CI-sized grid
  PYTHONPATH=src python -m benchmarks.sweep --full      # paper-sized
  PYTHONPATH=src python -m benchmarks.sweep --smoke     # tiny (scripts/ci.sh)
  PYTHONPATH=src python -m benchmarks.sweep --bandwidths 16,8,4
  PYTHONPATH=src python -m benchmarks.sweep --fleets paper6,8simba,8eyeriss
  PYTHONPATH=src python -m benchmarks.sweep --churn none,fail,throttle

Output: one ``sweep,...`` CSV-ish line per cell + ``BENCH_sweep.json``
(cells keyed ``<fleet>/<scenario>/<policy>/bw<B>``, with a
``/churn:<preset>`` suffix on churned cells only — no-churn keys stay
byte-stable across PRs — holding sla_rate / energy / wall seconds +
grid metadata; schema in docs/BENCHMARKS.md) for regression tracking
across PRs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

from benchmarks.common import (EVAL_LOAD, EVAL_QOS_FACTOR, REPO, bench_meta,
                               eval_policy,
                               make_env)
from repro.core import baselines as BL
from repro.costmodel.fleets import fleet_names
from repro.launch.compile_cache import use_compile_cache
from repro.sim.arrivals import SCENARIOS
from repro.sim.churn import CHURN_SCENARIOS, churn_preset
from repro.workloads import build_registry

POLICIES = ("fcfs", "prema", "herald", "magma", "relmas")

# default churn axis: the static fleet plus the two presets that bound
# the regime (hard capacity loss vs soft degradation); --churn widens
CHURNS = ("none", "fail", "throttle")

# grid presets: (periods, max_rq, max_jobs, n_seeds, magma_pop, magma_gens)
SIZES = {
    "full": (60, 96, 64, 5, 24, 12),
    "quick": (24, 48, 32, 2, 12, 6),
    "smoke": (8, 16, 8, 2, 6, 3),
}


def run(*, quick: bool = True, smoke: bool = False, workload: str = "light",
        scenarios=SCENARIOS, policies=POLICIES, bandwidths=(16.0,),
        fleets=("paper6",), churns=CHURNS,
        magma_cfg: BL.MagmaConfig | None = None,
        out: str | None = None) -> dict:
    size = "smoke" if smoke else ("quick" if quick else "full")
    periods, max_rq, max_jobs, n_seeds, pop, gens = SIZES[size]
    if smoke and scenarios is SCENARIOS:
        scenarios = ("default", "burst")
    if smoke and churns is CHURNS:
        churns = ("none", "fail")
    bad = [c for c in churns if c not in CHURN_SCENARIOS]
    if bad:
        raise ValueError(f"unknown churn preset(s) {bad}; "
                         f"choose from {CHURN_SCENARIOS}")
    mcfg = magma_cfg or BL.MagmaConfig(population=pop, generations=gens)
    seeds = range(7200, 7200 + n_seeds)

    cells: dict[str, dict] = {}
    t_all = time.time()
    for fl in fleets:
        # characterize the workload once per fleet (tables don't depend
        # on the shared bandwidth the inner loop sweeps)
        reg = build_registry(workload, mas=fl)
        for bw in bandwidths:
            # one env (and thus one compiled evaluator per policy) per
            # (fleet, bandwidth) — num_sas changes the compiled shapes;
            # scenarios below reuse it, trace data only.  bw 0 = the
            # fleet's own dram_gbps (e.g. for the datacenter preset).
            env = make_env(workload, fleet=fl, registry=reg, bandwidth=bw,
                           periods=periods, max_rq=max_rq,
                           max_jobs=max_jobs, load=EVAL_LOAD,
                           qos_factor=EVAL_QOS_FACTOR)
            for sc in scenarios:
                arr = dataclasses.replace(env.arrivals, scenario=sc)
                for ch in churns:
                    ccfg = None if ch == "none" else churn_preset(ch)
                    # churned cells get an explicit key suffix; the
                    # no-churn keys stay identical to pre-churn sweeps
                    suf = "" if ch == "none" else f"/churn:{ch}"
                    for p in policies:
                        t0 = time.time()
                        m = eval_policy(env, p, workload=workload,
                                        seeds=seeds, magma_cfg=mcfg,
                                        arrivals=arr, churn=ccfg)
                        cell = dict(sla_rate=round(m["sla_rate"], 4),
                                    energy_uj=round(m["energy_uj"], 1),
                                    wall_s=round(time.time() - t0, 2))
                        if "policy_kind" in m:
                            # heuristic | specialist | generalist — lets
                            # one BENCH_sweep.json mix per-fleet and
                            # fleet-conditioned relmas rows unambiguously
                            cell["policy_kind"] = m["policy_kind"]
                        if "trained" in m:
                            # no checkpoint matches this fleet's policy
                            # dims -> the relmas cell is a RANDOM-INIT
                            # policy; record that so the artifact stays
                            # honest
                            cell["trained"] = bool(m["trained"])
                        cells[f"{fl}/{sc}/{p}/bw{bw:g}{suf}"] = cell
                        print(f"sweep,{fl},{sc},{p},bw={bw:g},churn={ch},"
                              f"sla={cell['sla_rate']},"
                              f"wall={cell['wall_s']}", flush=True)

    best = {}
    for fl in fleets:
        for bw in bandwidths:
            for sc in scenarios:
                for ch in churns:
                    suf = "" if ch == "none" else f"/churn:{ch}"
                    row = {p: cells[f"{fl}/{sc}/{p}/bw{bw:g}{suf}"]
                           ["sla_rate"] for p in policies}
                    key = sc if len(fleets) == 1 else f"{fl}/{sc}"
                    if len(bandwidths) > 1:
                        key = f"{key}/bw{bw:g}"
                    best[key + suf] = max(row, key=row.get)
    # per-policy churn robustness: mean SLA drop vs the matching
    # no-churn cell, per preset (only when "none" anchors the grid)
    churn_drop: dict[str, dict[str, float]] = {}
    if "none" in churns:
        for ch in churns:
            if ch == "none":
                continue
            drops = {p: [] for p in policies}
            for fl in fleets:
                for bw in bandwidths:
                    for sc in scenarios:
                        for p in policies:
                            base = cells[f"{fl}/{sc}/{p}/bw{bw:g}"]
                            hit = cells[f"{fl}/{sc}/{p}/bw{bw:g}"
                                        f"/churn:{ch}"]
                            drops[p].append(base["sla_rate"]
                                            - hit["sla_rate"])
            churn_drop[ch] = {p: round(sum(v) / len(v), 4)
                              for p, v in drops.items()}
    summary = {
        "grid": f"{len(fleets)}x{len(scenarios)}x{len(policies)}"
                f"x{len(bandwidths)}x{len(churns)}",
        "best_policy_per_scenario": best,
        "churn_sla_drop": churn_drop,
        "wall_s": round(time.time() - t_all, 1),
    }
    result = dict(
        meta=dict(**bench_meta(),
                  size=size, workload=workload, periods=periods,
                  max_rq=max_rq, max_jobs=max_jobs, seeds=len(list(seeds)),
                  magma_population=mcfg.population,
                  magma_generations=mcfg.generations,
                  fleets=list(fleets), scenarios=list(scenarios),
                  policies=list(policies), bandwidths=list(bandwidths),
                  churns=list(churns)),
        cells=cells, summary=summary)
    out = out or os.path.join(REPO, "BENCH_sweep.json")
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print("sweep_summary," + json.dumps(summary), flush=True)
    print(f"sweep_json,{out}", flush=True)
    return result


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true",
                    help="paper-sized grid (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 2-scenario smoke grid (CI)")
    ap.add_argument("--workload", default="light")
    ap.add_argument("--scenarios", default=None,
                    help=f"comma list of {SCENARIOS}")
    ap.add_argument("--policies", default=None,
                    help=f"comma list of {POLICIES}")
    ap.add_argument("--bandwidths", default="16",
                    help="comma list of shared-DRAM GB/s values "
                         "(0 = each fleet's own dram_gbps)")
    ap.add_argument("--fleets", default="paper6",
                    help=f"comma list of fleet presets {fleet_names()}")
    ap.add_argument("--churn", default=None,
                    help=f"comma list of churn presets {CHURN_SCENARIOS} "
                         f"(default {','.join(CHURNS)}; smoke: none,fail)")
    ap.add_argument("--population", type=int, default=None,
                    help="MAGMA population override")
    ap.add_argument("--generations", type=int, default=None,
                    help="MAGMA generations override")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args(argv)
    mcfg = None
    if args.population or args.generations:
        size = "smoke" if args.smoke else ("full" if args.full else "quick")
        _, _, _, _, pop, gens = SIZES[size]
        mcfg = BL.MagmaConfig(population=args.population or pop,
                              generations=args.generations or gens)
    run(quick=not args.full, smoke=args.smoke, workload=args.workload,
        scenarios=tuple(args.scenarios.split(","))
        if args.scenarios else SCENARIOS,
        policies=tuple(args.policies.split(","))
        if args.policies else POLICIES,
        bandwidths=tuple(float(b) for b in args.bandwidths.split(",")),
        fleets=tuple(args.fleets.split(",")),
        churns=tuple(args.churn.split(",")) if args.churn else CHURNS,
        magma_cfg=mcfg, out=args.out)


if __name__ == "__main__":
    main()
