"""Read what the numbers compared read, for setting their limits.

    python3 chipbench/readings.py --workload <cell> --seeds 11,12,... \
        [--control-seeds 21,22,23] [--faults unchanged,half,altered] \
        [--base-key 0] [--out chiprun_out/readings.jsonl]

One process on the chip the cell runs on, through the cell's own
serving path at the cell's own size: for each seed, one session in the
window and the cell's check of it (the program's readings); for each
control seed, the same check with the reference at the configuration's
``control`` precision in the program's place (the control's readings);
for each planted fault (``faults.py``), the check of a service built
with that fault, on the first seed. Each reading also gives the
session's tick p95 and periods/s, so that runs with another
``--base-key`` (the fixed key the untrained actor is drawn from) show
how far the work depends on the weights. Prints one JSON line per
reading.
The benchmark's own runs do not run this.
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
    ap.add_argument("--out", default="")
    ap.add_argument("--base-key", type=int, default=0)
    ap.add_argument("--ticks", type=int, default=0,
                    help="session length in place of the traffic's (the "
                         "traced run serves sessions of TRACE_TICKS)")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",") if x]

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    import faults
    import run
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 2
    _, _, cfg, traffic = run.cell_spec(args.workload)
    control = cfg["control"]
    import serve_cell
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    seeds = ints(args.seeds)
    c = serve_cell.Cell(cfg, traffic, seeds[0], args.base_key)
    c.warm(bool(args.ticks))
    for kind, seed_list in (("program", seeds),
                            ("control", ints(args.control_seeds))):
        for seed in seed_list:
            t0 = time.perf_counter()
            c.reseed(seed)
            results, wall = c.window(0.0, args.ticks or None)
            t1 = time.perf_counter()
            diag: list = []
            nums = c.check(results, diag=diag, control=(
                control if kind == "control" else None))
            emit(dict(workload=args.workload, kind=kind, seed=seed,
                      base_key=args.base_key, **c.end_to_end(results, wall),
                      check_s=time.perf_counter() - t1, window_s=t1 - t0,
                      gap_exact=max(d["gap_exact"] for d in diag),
                      **nums))
    for name in [f for f in args.faults.split(",") if f]:
        with faults.FAULTS[name]():
            fc = serve_cell.Cell(cfg, traffic, seeds[0])
            fc.warm(False)
            results, _ = fc.window(0.0)
            nums = fc.check(results)
        emit(dict(workload=args.workload, kind="fault:" + name,
                  seed=seeds[0], **nums))
    return 0


if __name__ == "__main__":
    sys.exit(main())
