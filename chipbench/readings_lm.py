"""Read what an LM serving cell's compared numbers read, and find its knee.

    python3 chipbench/readings_lm.py --workload serve-dsv2lite-chat \
        --seeds 11,12 [--control-seeds 21,22] [--faults unchanged,half] \
        [--rates 4,8,16,32] [--rate-scale 20] [--out readings_lm.jsonl]

``readings.py`` for a cell of kind ``lmserve``, in one process on the
chip the cell runs on, at the cell's own size: for each seed one session
in the window and the cell's check of it (the program's readings), for
each control seed the same check with the reference at the
configuration's ``control`` precision in the program's place, for each
planted fault (``faults.py``) the check of a service built with it, on
the first seed. A program reading also says how many of the compared
stream-ticks scheduled decode-pass rows. ``--rates`` first serves one whole session at each
offered ``rate_scale`` with the first seed's weights and reports the
admissions its queues rejected (the knee is the highest rate with none);
``--rate-scale`` puts a rate in place of the traffic file's for the
readings. Prints one JSON line per reading. The benchmark's own runs do
not run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--faults", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--rate-scale", type=float, default=0.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    import faults
    import lmserve_cell
    import run
    if jax.devices()[0].platform != "tpu":
        print("readings_lm: needs a TPU", file=sys.stderr)
        return 2
    _, _, cfg, traffic = run.cell_spec(args.workload)
    if args.rate_scale:
        traffic = dict(traffic, rate_scale=args.rate_scale)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = ints(args.seeds)
    c = lmserve_cell.Cell(cfg, traffic, seeds[0])
    c.warm(False)
    for rate in [float(x) for x in args.rates.split(",") if x]:
        c.traffic = dict(traffic, rate_scale=rate)
        c.reseed(seeds[0])
        t0 = time.perf_counter()
        r = c.serve(0, c.T)
        emit(dict(workload=args.workload, kind="rate", rate_scale=rate,
                  seed=seeds[0], window_s=time.perf_counter() - t0,
                  requests=sum(len(x) for x in c.pool[0]),
                  rejected=r["stats"]["deferred"],
                  unserved=r["stats"]["unserved"], **{
                      k: r["aggregate"][k] for k in (
                          "sla_rate", "ttft_rate", "tpot_rate", "counted")},
                  mean_depth=r["stats"]["mean_depth"],
                  **c.end_to_end([r], time.perf_counter() - t0)))
    c.traffic = traffic
    for kind, seed_list in (("program", seeds),
                            ("control", ints(args.control_seeds))):
        for seed in seed_list:
            t0 = time.perf_counter()
            c.reseed(seed)
            results, wall = c.window(0.0)
            t1 = time.perf_counter()
            diag: list = []
            nums = c.check(results, diag=diag, control=(
                cfg["control"] if kind == "control" else None))
            emit(dict(workload=args.workload, kind=kind, seed=seed,
                      rate_scale=traffic["rate_scale"],
                      **c.end_to_end(results, wall),
                      check_s=time.perf_counter() - t1, window_s=t1 - t0,
                      gap_exact=max(d["gap_exact"] for d in diag),
                      decode_slots=sum(d["decode_slots"] for d in diag),
                      decode_stream_ticks=sum(d["decode_slots"] > 0
                                              for d in diag),
                      checked_stream_ticks=len(diag), **nums))
    for name in [f for f in args.faults.split(",") if f]:
        with faults.FAULTS[name]():
            fc = lmserve_cell.Cell(cfg, traffic, seeds[0])
            fc.warm(False)
            results, _ = fc.window(0.0)
            nums = fc.check(results)
        emit(dict(workload=args.workload, kind="fault:" + name,
                  seed=seeds[0], **nums))
    return 0


if __name__ == "__main__":
    sys.exit(main())
