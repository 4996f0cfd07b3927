"""Beyond-paper: straggler/degradation robustness of the scheduling policies.

Built on the traced churn machinery (``repro.sim.churn``): each
degraded arm draws a seeded in-episode event schedule — ``slowdown``
multiplies a victim SA's latencies by ``magnitude`` mid-episode,
``throttle`` additionally cuts its bandwidth share — injected into the
episode scan as pure trace data (same compiled evaluator as the
nominal arm's churn-carrying program).  The primer encoding gives
RELMAS per-SA busy-time visibility and its latency features are
per-SA, so it can route around the straggler; load-balancing
heuristics that assume nominal speeds degrade harder.  (Not a figure
in the paper — an extra robustness experiment enabled by the same
simulator.)
"""
from __future__ import annotations

import json

from benchmarks.common import EVAL_LOAD, EVAL_QOS_FACTOR, eval_policy, \
    make_env
from repro.launch.compile_cache import use_compile_cache
from repro.sim.churn import churn_preset

POLICIES = ("fcfs", "herald", "relmas")
SCENARIOS = ("nominal", "slowdown", "throttle")


def run(*, quick: bool = True, magnitude: float = 4.0) -> dict:
    seeds = range(7300, 7302 if quick else 7305)
    # ONE env for every arm: degradation is trace data, not a mutated
    # latency table, so the compiled evaluators are shared
    env = make_env("light", periods=60, load=EVAL_LOAD,
                   qos_factor=EVAL_QOS_FACTOR)
    out = {}
    for scenario in SCENARIOS:
        ccfg = None if scenario == "nominal" else \
            churn_preset(scenario, magnitude=magnitude)
        row = {}
        for p in POLICIES:
            m = eval_policy(env, p, workload="light", seeds=seeds,
                            churn=ccfg)
            row[p] = round(m["sla_rate"], 4)
        out[scenario] = row
        print(f"straggler,{scenario}," + ",".join(
            f"{p}={row[p]}" for p in POLICIES), flush=True)
    drop = {sc: {p: round(out["nominal"][p] - out[sc][p], 4)
                 for p in POLICIES}
            for sc in SCENARIOS if sc != "nominal"}
    print("straggler_summary," + json.dumps({"sla_drop": drop}), flush=True)
    return {**out, "drop": drop}


def main():
    use_compile_cache()
    run()


if __name__ == "__main__":
    main()
