"""§Roofline report: render the dry-run sweep JSONL into the
per-(arch x shape x mesh) table used by EXPERIMENTS.md."""
from __future__ import annotations

import json
import os

from benchmarks.common import REPO
from repro.launch.compile_cache import use_compile_cache

SWEEP = os.path.join(REPO, "runs", "dryrun", "all.jsonl")


def load(path: str = SWEEP) -> list[dict]:
    if not os.path.exists(path):
        return []
    recs = [json.loads(l) for l in open(path)]
    # de-dup: keep the latest record per cell
    by_key = {}
    for r in recs:
        by_key[(r["arch"], r["shape"], r["multi_pod"],
                json.dumps(r.get("overrides", {}), sort_keys=True))] = r
    return list(by_key.values())


def fmt_row(r: dict) -> str:
    mem = r.get("mem", {})
    gb = mem.get("per_chip_total_bytes", 0) / 2 ** 30
    rf = r.get("roofline")
    if rf is None:
        # multi-pod rows: compile + memory evidence only (the rolled
        # module's cost_analysis counts while bodies once — terms come
        # from the single-pod unrolled cost modules)
        return (f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
                f"{'OK' if r['ok'] else 'FAIL'} | {gb:.2f} | "
                f"— | — | — | (compile-only) | — |")
    tc, tm, tl = (rf.get("t_compute_s", 0), rf.get("t_memory_s", 0),
                  rf.get("t_collective_s", 0))
    ratio = r.get("useful_flop_ratio", 0)
    return (f"| {r['arch']} | {r['shape']} | {r['mesh']} | "
            f"{'OK' if r['ok'] else 'FAIL'} | {gb:.2f} | "
            f"{tc:.4g} | {tm:.4g} | {tl:.4g} | "
            f"{rf.get('dominant', '-')} | {ratio:.3f} |")


def run(path: str = SWEEP) -> dict:
    recs = [r for r in load(path) if not r.get("overrides")]
    recs.sort(key=lambda r: (r["arch"], r["shape"], r["multi_pod"]))
    print("| arch | shape | mesh | ok | GB/chip | t_comp(s) | t_mem(s) | "
          "t_coll(s) | dominant | 6ND/HLO |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    n_fail = 0
    for r in recs:
        print(fmt_row(r))
        n_fail += not r["ok"]
    singles = [r for r in recs if not r["multi_pod"] and r["ok"]
               and r["arch"] != "relmas"]
    doms = {}
    for r in singles:
        rf = r.get("roofline") or {}
        doms[rf.get("dominant", "?")] = doms.get(rf.get("dominant", "?"),
                                                 0) + 1
    print(f"rooflinesummary,cells={len(recs)},fail={n_fail},"
          f"dominants={json.dumps(doms)}", flush=True)
    return {"cells": len(recs), "fail": n_fail, "dominants": doms}


def main():
    use_compile_cache()
    run()


if __name__ == "__main__":
    main()
