"""Run one cell of the chip benchmark once and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``:
its configuration (``chipbench/configs/<config>.json``), its traffic
(``chipbench/traffic/<traffic>.json``, whose ``kind`` names the module
that drives it, ``<kind>_cell.py``) and its per-layer metrics
(``chipbench/layers/<metric>.py``, each a ``read(ctx)`` that returns a
number or ``None``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
a window with the JAX profiler and prints the cell's per-layer metrics,
with ``busy_s``/``window_s`` and a ``breakdown``. Every run then checks
what the window produced against the plain reference, prints each
number compared beside its limit as the last lines of standard error
and under ``checks`` in the result, and prints the result as one JSON
object on the last line of standard output.

The run uses one process and the TPU it is started on; it exits 2 with
no result where JAX finds no TPU or fewer chips than the cell asks for.
JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def cell_spec(name: str):
    """(benchmark, cell, configuration, traffic) of the cell ``name``."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, load_json(os.path.join(ROOT, cfg["file"])), traffic


def cell_metrics(bench: dict, cell: str):
    """The cell's end-to-end metrics, and the per-layer ones that apply."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def read_layer(name: str, ctx):
    spec = importlib.util.spec_from_file_location(
        "layer_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(HERE, "layers", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run_cell(bench: dict, cell: dict, cfg: dict, traffic: dict, seed: int,
             seconds: float, trace: int, t_start: float) -> dict:
    """Set up, warm, measure and check one cell; returns the result."""
    mod = importlib.import_module(traffic["kind"] + "_cell")
    c = mod.Cell(cfg, traffic, seed)
    import jax
    import reduce_trace as tr
    c.warm(bool(trace))
    setup_s = time.perf_counter() - t_start
    e2e, layer = cell_metrics(bench, cell["name"])
    metrics, extra = {}, {}
    if not trace:
        results, wall = c.window(seconds)
        values = dict(c.end_to_end(results, wall), setup_s=setup_s)
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        # host spans from the runtime only: the Python tracer would slow
        # the host and so inflate the idle share it is read for
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with tempfile.TemporaryDirectory() as log_dir:
            with jax.profiler.trace(log_dir, profiler_options=opts):
                with jax.profiler.TraceAnnotation("chipbench.window"):
                    results, wall = c.traced_window()
            evs = tr.events(log_dir)
        ctx = c.layer_context(evs, results)
        for m in layer:
            v = read_layer(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        extra["breakdown"] = ctx.breakdown
    device = device_info(c.devices)
    if trace:
        device.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
    attempted, failed = c.load(results)
    nums = c.check(results)
    checks = {k: {"value": v, "limit": mod.LIMITS[k]}
              for k, v in nums.items()}
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    return dict(correct=correct, attempted=attempted, failed=failed,
                metrics=metrics, device=device, **extra, checks=checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, cfg, traffic = cell_spec(args.workload)

    # the compile cache lives in the checkout, at a path that does not move
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform!r} "
              f"device(s). Nothing was run.", file=sys.stderr)
        return 2
    res = run_cell(bench, cell, cfg, traffic, args.seed, args.seconds,
                   args.trace, T_START)
    for k, v in res["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
