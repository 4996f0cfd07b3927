"""Device-idle time of a traced window, given to the program's host spans.

The serving loop opens host spans on the profiler's clock
(``serve.resolve``, ``serve.setup``, ``serve.stage``, ``serve.dispatch``,
``serve.readback``, ``serve.record``, ``serve.flush`` inside
``serve.tick`` and ``serve.session``). :func:`idle_by_span` splits the
window's idle time among them; :func:`engine_trips` counts the engine's
loop trips on the device trace.
"""
from __future__ import annotations

import re

import reduce_trace as tr

# the spans of ``serve_stream`` that hold no other span of it
LEAF_SPANS = ("serve.resolve", "serve.setup", "serve.stage",
              "serve.dispatch", "serve.readback", "serve.record",
              "serve.flush")
# the leaf spans in which the host works rather than waits on the device
HOST_SPANS = ("serve.resolve", "serve.setup", "serve.stage", "serve.record",
              "serve.flush")


def idle_intervals(devs: list[dict], t0: int, t1: int) -> list[tuple]:
    """The gaps between device ops inside ``[t0, t1]``, in order."""
    out, cur = [], t0
    for s, e in sorted((e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in devs):
        s, e = max(s, t0), min(e, t1)
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def idle_by_span(devs: list[dict], host: list[dict], t0: int, t1: int,
                 names) -> dict[str, float]:
    """Seconds of device idle time in ``[t0, t1]`` under each host span
    named in ``names``: each idle nanosecond goes to the innermost such
    span that covers it (the latest to start), and to ``"none"`` where
    none does. Every name is a key, with 0.0 where it holds no idle."""
    names = tuple(names)
    spans = sorted((h["start_ns"], h["start_ns"] + h["dur_ns"], h["name"])
                   for h in host if h["name"] in names and h["dur_ns"] > 0)
    out = dict.fromkeys(names + ("none",), 0)
    cuts = sorted({t for iv in idle_intervals(devs, t0, t1) for t in iv}
                  | {t for s, e, _ in spans for t in (s, e)
                     if t0 <= t <= t1})
    idle = idle_intervals(devs, t0, t1)
    i = j = 0
    active: list[tuple] = []
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j][0] <= a:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > a]
        while i < len(idle) and idle[i][1] <= a:
            i += 1
        if i < len(idle) and idle[i][0] <= a:
            inner = max(active, key=lambda s: (s[0], -s[1]), default=None)
            out[inner[2] if inner else "none"] += b - a
    return {k: v / 1e9 for k, v in out.items()}


def engine_trips(devs: list[dict], hlo_names=None) -> int:
    """Trips of the engine's event loop in ``devs``: the most times any
    one instruction of the loop body under ``env.engine`` ran."""
    scope = re.compile(r"(^|[/(])env\.engine([/)]|$)")
    counts: dict = {}
    for e in devs:
        name = tr.op_name(e, hlo_names)
        if scope.search(name) and "/while/body/" in name:
            key = (e.get("module", ""), e["name"].split(" ")[0])
            counts[key] = counts.get(key, 0) + 1
    return max(counts.values(), default=0)
