"""Benchmark harness — one entry per paper table/figure + repo extras.

  python -m benchmarks.run            # quick CI-sized pass (default)
  python -m benchmarks.run --full     # paper-sized episode counts
  python -m benchmarks.run --only fig3,fig4
  python -m benchmarks.run --only sweep     # scenario x policy x bw grid
  python -m benchmarks.run --only transfer  # cross-fleet transfer matrix

Output: CSV-ish lines per benchmark (stable prefixes: fig3, fig4, fig5,
table1, table2 — both emitted by the table1 entry — policy_latency,
straggler, sweep) + a final JSON summary line.

Machine-readable perf-trajectory artifacts (for cross-PR regression
tracking; schemas in docs/BENCHMARKS.md): ``benchmarks/sweep.py``
writes ``BENCH_sweep.json`` (per-cell SLA rates for fleet presets x
{default,steady,burst,diurnal,heavy_tail} x
{fcfs,prema,herald,magma,relmas} x bandwidths, one jitted eval per
cell — ``--fleets`` selects the platforms) and
``benchmarks/rollout_throughput.py`` writes ``BENCH_rollout.json``
(periods/sec + speedup for the batched rollout pipeline, scan-fused vs
host-loop MAGMA, the fused trainer, and small-vs-large fleet scaling);
``benchmarks/transfer.py`` writes ``BENCH_transfer.json`` (the
fleets x fleets cross-fleet transfer matrix: generalist vs per-fleet
specialist vs untrained, all policies trained in-suite — ``--fleets``
selects the platforms); ``benchmarks/serving_bench.py`` writes
``BENCH_serving.json`` (batched single-dispatch serving tick vs the
per-period host loop: p50/p99 decision latency, sustained requests/sec,
bit-exact SLA parity, and SLA-under-load per arrival scenario x rate).
"""
from __future__ import annotations

import argparse
import json
import time

from repro.launch.compile_cache import use_compile_cache


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig4,fig5,table1,policy,"
                         "serving,straggler,sweep,transfer")
    ap.add_argument("--no-magma", action="store_true",
                    help="skip the GA baseline (slowest bench)")
    ap.add_argument("--fleets", default=None,
                    help="comma list of fleet presets for the sweep/"
                         "transfer entries (repro.costmodel.fleets; "
                         "defaults: paper6 / paper6,8simba,8eyeriss)")
    args = ap.parse_args(argv)
    quick = not args.full
    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    results = {}
    t0 = time.time()
    if want("table1"):
        from benchmarks import table1_costmodel
        results["table1"] = table1_costmodel.run()
    if want("policy"):
        from benchmarks import policy_latency
        results["policy_latency"] = policy_latency.run()
        results["serving_dispatch"] = policy_latency.run_serving()
    if want("serving"):
        from benchmarks import serving_bench
        svc = serving_bench.make_service()
        streams = 96 if not quick else 16
        results["serving"] = serving_bench.run_guard(
            svc, streams=streams, repeats=5 if not quick else 2)["throughput"]
    if want("fig5"):
        from benchmarks import fig5_overhead
        results["fig5"] = fig5_overhead.run(quick=quick)["summary"]
    if want("fig3"):
        from benchmarks import fig3_sla
        results["fig3"] = fig3_sla.run(
            quick=quick, with_magma=not args.no_magma)["summary"]
    if want("fig4"):
        from benchmarks import fig4_bandwidth
        results["fig4"] = fig4_bandwidth.run(quick=quick)["summary"]
    if want("sweep"):
        from benchmarks import sweep
        pols = tuple(p for p in sweep.POLICIES
                     if p != "magma" or not args.no_magma)
        fleets = tuple(args.fleets.split(",")) if args.fleets else ("paper6",)
        results["sweep"] = sweep.run(quick=quick, policies=pols,
                                     fleets=fleets)["summary"]
    if only is not None and "transfer" in only:
        # opt-in only (--only transfer): trains len(fleets)+1 policies
        # in-suite, far heavier than the eval-only entries above
        from benchmarks import transfer
        fleets = (tuple(args.fleets.split(",")) if args.fleets
                  else transfer.DEFAULT_FLEETS)
        results["transfer"] = transfer.run(quick=quick,
                                           fleets=fleets)["summary"]
    if want("straggler"):
        from benchmarks import straggler_bench
        results["straggler"] = straggler_bench.run(quick=quick)["drop"]
    results["wall_s"] = round(time.time() - t0, 1)
    print("benchsummary," + json.dumps(results, default=str), flush=True)
    import os
    os.makedirs("runs", exist_ok=True)
    with open("runs/bench_summary.json", "w") as f:
        json.dump(results, f, default=str, indent=1)
    return results


if __name__ == "__main__":
    main()
