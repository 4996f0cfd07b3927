"""Read the serving path's spans, scopes and counters in one cell.

    python3 chipbench/span_probe.py --workload <cell> --seed <n> \
        [--fixture out.json.gz] [--out spans.jsonl]
    python3 chipbench/span_probe.py --workload <cell> --seed <n> \
        --hlo-out tick.hlo

One process on the chip the cell runs on, through the cell's own
serving path at the cell's own size. Set-up is the benchmark's (the
cell's ``warm`` with tracing) with the program's compile counter on,
and then one session that compiles the tick carrying the device
telemetry block. It then traces the benchmark's window (one session of
``TRACE_TICKS``) as ``run.py --trace 1`` does, and prints one JSON line:

- every per-layer metric of the cell that ``run.py`` reads;
- ``idle_by_span``: the window's device-idle seconds under each leaf
  span of ``serve_stream`` (``span_idle.LEAF_SPANS``) and under none,
  and ``idle_readback_ms`` / ``idle_host_ms`` per tick from them;
- ``counters``: the window's session served again with the device
  telemetry block: engine trips per tick and the engine's lane use,
  100 x iterations / (streams x trips);
- ``setup_compile`` / ``window_compile``: the compile counter over
  set-up and over the traced window;
- ``span_cost_us``: one tick's host spans, timed alone with and without
  a profiler running, and ``loop_us_per_tick``: untraced sessions with
  the spans and with them stripped.

``--fixture`` also traces a 3-tick session and writes its events, the
``op_name`` of each instruction that ran and the session's counters, for
the CPU tests. ``--hlo-out`` only writes the compiled telemetry-off tick
with its metadata stripped (any version of the program), and exits.
The benchmark's own runs do not run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
FIXTURE_TICKS = 3


def strip_metadata(text: str) -> str:
    """A compiled module's text without its source locations: each
    instruction's ``metadata={...}`` and the stack-frame tables."""
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text,
                  flags=re.S)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


def tick_text(c) -> str:
    """The compiled telemetry-off tick of the cell, as ``run.py`` lowers
    it for the op names."""
    import jax
    import numpy as np
    from repro.core.serve import make_serving_tick, queue_init_batch
    env, S, K = c.svc.env, c.S, c.K
    tick = make_serving_tick(env, kind=c.svc.policy_kind, pcfg=c.svc.pcfg,
                             streams=S)
    adm = dict(model=np.zeros((S, K), np.int32),
               arrival=np.zeros((S, K), np.float32),
               deadline=np.zeros((S, K), np.float32),
               q=np.ones((S, K), np.float32),
               rid=np.zeros((S, K), np.int32),
               valid=np.zeros((S, K), bool))
    return tick.lower(c.svc.params, queue_init_batch(env, S), adm,
                      jax.random.PRNGKey(0)).compile().as_text()


def traced(fn):
    """``fn()`` inside the profiler as ``run.py --trace 1`` traces its
    window; returns (result, events)."""
    import jax
    import reduce_trace as tr
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation("chipbench.window"):
                res = fn()
        return res, tr.events(log_dir)


def window_of(evs):
    win = [e for e in evs if e["name"] == "chipbench.window"][0]
    return win["start_ns"], win["start_ns"] + win["dur_ns"]


def host_spans(evs):
    return [e for e in evs if e["plane"].startswith("/host:")
            and e["name"].startswith("serve.") and e["dur_ns"] > 0]


def span_cost_us(n: int = 20000) -> dict:
    """One tick's five host spans (the step and its stage, dispatch,
    readback and record), in us, without and with a profiler running."""
    import jax
    from repro.telemetry import trace_span

    def loop():
        t0 = time.perf_counter()
        for i in range(n):
            with trace_span("serve.tick", step_num=i):
                for name in ("serve.stage", "serve.dispatch",
                             "serve.readback", "serve.record"):
                    with trace_span(name):
                        pass
        return (time.perf_counter() - t0) / n * 1e6

    off = loop()
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            on = loop()
    return {"profiler_off": off, "profiler_on": on}


def loop_us_per_tick(c, ticks: int, reps: int = 3) -> dict:
    """Host wall time per tick of untraced sessions, with the spans and
    with ``serve_stream``'s spans replaced by a no-op, alternating."""
    import repro.serving.service as service
    real = service.trace_span
    out = {"spans": [], "stripped": []}
    for _ in range(reps):
        for arm in ("spans", "stripped"):
            if arm == "stripped":
                service.trace_span = \
                    lambda name, **ids: contextlib.nullcontext()
            t0 = time.perf_counter()
            try:
                c.serve(0, ticks)
            finally:
                service.trace_span = real
            out[arm].append((time.perf_counter() - t0) / ticks * 1e6)
    return {k: statistics.median(v) for k, v in out.items()} | {
        "runs": out}


def counters(c, i: int, ticks: int) -> dict:
    """Session ``i`` served with the device telemetry block."""
    from repro.telemetry import ListSink, Telemetry
    res = c.svc.serve_stream(c.pool[i % len(c.pool)], tick_k=c.K,
                             ticks=ticks, seed=i,
                             telemetry=Telemetry([ListSink()]))
    return res["stats"]["device_tele"]


def write_fixture(path: str, c) -> None:
    """A short traced session with its events and counters."""
    import reduce_trace as tr
    c.serve(1, FIXTURE_TICKS)
    _, evs = traced(lambda: c.serve(1, FIXTURE_TICKS))
    t0, t1 = window_of(evs)
    keep = [e for e in evs if t0 <= e["start_ns"] <= t1 and (
        tr.is_device(e) or e["line"] == tr.MODULES_LINE
        or e["name"] == "chipbench.window"
        or (e["plane"].startswith("/host:")
            and e["name"].startswith("serve.")))]
    names = tr.op_names_from_hlo(tick_text(c))
    ran = {(e.get("module", ""), tr._INSTR.match(e["name"]).group(1))
           for e in keep if tr.is_device(e) and tr._INSTR.match(e["name"])}
    with gzip.open(path, "wt") as fh:
        json.dump({"ticks": FIXTURE_TICKS, "streams": c.S,
                   "counters": counters(c, 1, FIXTURE_TICKS),
                   "events": keep,
                   "hlo_op_names": [[m, i, o] for (m, i), o in names.items()
                                    if (m, i) in ran]}, fh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--fixture", default="")
    ap.add_argument("--hlo-out", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax
    import run
    if jax.devices()[0].platform != "tpu":
        print("span_probe: needs a TPU", file=sys.stderr)
        return 2
    bench, cell, cfg, traffic = run.cell_spec(args.workload)
    import serve_cell
    if args.hlo_out:
        c = serve_cell.Cell(cfg, traffic, args.seed)
        with open(args.hlo_out, "w") as fh:
            fh.write(strip_metadata(tick_text(c)))
        return 0

    from repro.launch.compile_cache import use_compile_cache
    from repro.telemetry import install_compile_counter
    install_compile_counter()
    use_compile_cache()
    rec = probe(bench, cell, cfg, traffic, args.seed, args.fixture)
    line = json.dumps(rec)
    if args.out:
        with open(args.out, "a") as fh:
            fh.write(line + "\n")
    print(line, flush=True)
    return 0


def probe(bench, cell, cfg, traffic, seed: int, fixture: str = "") -> dict:
    """Set up, trace and read one cell (see the module's docstring)."""
    import run
    import serve_cell
    import span_idle
    from repro.telemetry import compile_counts
    c = serve_cell.Cell(cfg, traffic, seed)
    c.warm(True)
    setup_s = time.perf_counter() - T_START
    setup_compile = compile_counts()
    counters(c, serve_cell.POOL, serve_cell.TRACE_TICKS)
    c0 = compile_counts()
    (results, _), evs = traced(c.traced_window)
    window_compile = compile_counts(since=c0)

    ctx = c.layer_context(evs, results)
    _, layer = run.cell_metrics(bench, cell["name"])
    metrics = {m["name"]: run.read_layer(m["name"], ctx) for m in layer}
    t0, t1 = window_of(evs)
    idle = span_idle.idle_by_span(ctx.devs, host_spans(evs), t0, t1,
                                  span_idle.LEAF_SPANS)
    nested = span_idle.idle_by_span(
        ctx.devs, host_spans(evs), t0, t1,
        span_idle.LEAF_SPANS + ("serve.tick", "serve.session"))
    idle_s = sum(idle.values())
    cnt = counters(c, 0, serve_cell.TRACE_TICKS)
    trips = cnt["engine_trips"] / cnt["ticks"]
    rec = dict(
        workload=cell["name"], seed=seed,
        device=run.device_info(c.devices) | dict(busy_s=ctx.busy_s,
                                                  window_s=ctx.window_s),
        setup_s=setup_s, setup_compile=setup_compile,
        window_compile=window_compile, metrics=metrics,
        idle_by_span=idle, idle_by_span_nested=nested,
        idle_leaf_share=100.0 * (1 - idle["none"] / idle_s)
        if idle_s else None,
        idle_readback_ms=1e3 * idle["serve.readback"] / ctx.ticks,
        idle_host_ms=1e3 * sum(idle[k] for k in span_idle.HOST_SPANS)
        / ctx.ticks,
        counters=cnt, engine_trips_counter=trips,
        engine_lane_use=100.0 * cnt["engine_iters"]
        / (c.S * cnt["engine_trips"]),
        trace_trips=span_idle.engine_trips(ctx.devs, ctx.hlo_names),
        scopes_ms={s: ctx.scope_ms(s) / ctx.ticks for s in (
            "serving.period", "env.engine", "env.act", "env.slots")},
        breakdown=ctx.breakdown)
    rec["span_cost_us"] = span_cost_us()
    rec["loop_us_per_tick"] = loop_us_per_tick(c, serve_cell.TRACE_TICKS)
    if fixture:
        write_fixture(fixture, c)
    return rec


if __name__ == "__main__":
    sys.exit(main())
