"""Fig. 4: SLA vs shared-memory-bandwidth reduction (Light workload).

Claim: RELMAS (bandwidth-aware features) degrades more gracefully than
bandwidth-blind heuristics as the shared DRAM bandwidth shrinks — each
policy is normalized to its own best, exactly the paper's plot.

All cells (optionally including scan-fused MAGMA, ``with_magma=True``)
run through the batched device-resident evaluators: one jitted call per
(bandwidth, policy) cell.  benchmarks/sweep.py generalizes this sweep
across arrival scenarios.
"""
from __future__ import annotations

import json

from benchmarks.common import eval_policy, make_env
from repro.launch.compile_cache import use_compile_cache

BWS = (16.0, 12.0, 8.0, 6.0, 4.0)
POLICIES = ("fcfs", "prema", "herald", "relmas")


def run(*, quick: bool = True, with_magma: bool = False) -> dict:
    seeds = range(7100, 7102 if quick else 7105)
    periods = 60
    policies = POLICIES + ("magma",) if with_magma else POLICIES
    raw: dict[str, list[float]] = {p: [] for p in policies}
    from benchmarks.common import EVAL_LOAD, EVAL_QOS_FACTOR
    for bw in BWS:
        env = make_env("light", bandwidth=bw, periods=periods,
                       load=EVAL_LOAD, qos_factor=EVAL_QOS_FACTOR)
        for p in policies:
            m = eval_policy(env, p, workload="light", seeds=seeds)
            raw[p].append(m["sla_rate"])
        print(f"fig4,bw={bw}," + ",".join(
            f"{p}={raw[p][-1]:.4f}" for p in policies), flush=True)
    norm = {p: [v / max(max(vs), 1e-6) for v in vs]
            for p, vs in raw.items() for vs in [raw[p]]}
    # degradation at the lowest bandwidth, relative to own best
    degr = {p: round(1.0 - norm[p][-1], 4) for p in raw}
    summary = {
        "normalized_drop_at_min_bw": degr,
        "relmas_degrades_least": degr["relmas"] <= min(
            v for p, v in degr.items() if p != "relmas") + 0.05,
    }
    print("fig4_summary," + json.dumps(summary), flush=True)
    return {"raw": raw, "normalized": norm, "summary": summary}


def main():
    use_compile_cache()
    run(quick=True)


if __name__ == "__main__":
    main()
