"""Reduction of a profiler trace to the numbers the per-layer metrics read.

The JAX profiler writes an ``.xplane.pb``; :func:`events` flattens it to
plain records ``(plane, line, name, start_ns, dur_ns, stats)`` so that
the reduction below works on a recorded trace as well as on a fresh one.

- device events: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
- scope of a device op: the ``op_name`` metadata that ``jax.named_scope``
  leaves on each HLO instruction, read from the event's own stats where
  the trace carries it, else from the compiled module's HLO text
  (:func:`op_names_from_hlo`), keyed by module and instruction name;
- busy time: the union of the device ops' intervals; idle share:
  1 - busy / window;
- scope time: the union of the intervals of the ops under a scope (a
  path element of ``op_name``, bare or wrapped by a transformation, as
  in ``jit(tick)/vmap(serving.period)/...``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_OP_NAME_STATS = ("tf_op", "op_name", "long_name")


def events(log_dir: str) -> list[dict]:
    """Every event of the trace under ``log_dir`` as a plain dict."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for path in paths:
        pd = jax.profiler.ProfileData.from_file(path)
        for plane in pd.planes:
            for line in plane.lines:
                for e in line.events:
                    out.append(dict(plane=plane.name, line=line.name,
                                    name=e.name, start_ns=e.start_ns,
                                    dur_ns=e.duration_ns,
                                    stats={str(k): v for k, v in e.stats
                                           if isinstance(v, (str, int,
                                                             float))}))
    attach_modules(out)
    return out


def attach_modules(evs: list[dict]) -> None:
    """Name each device op's module (``jit_tick``) after the module
    event of its plane that encloses it."""
    mods: dict[str, list] = {}
    for e in evs:
        if e["line"] == MODULES_LINE:
            mods.setdefault(e["plane"], []).append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"],
                 re.sub(r"\(\d+\)$", "", e["name"])))
    for v in mods.values():
        v.sort()
    for e in evs:
        if e["line"] != OPS_LINE or e["plane"] not in mods:
            continue
        v = mods[e["plane"]]
        i = bisect.bisect_right(v, (e["start_ns"], float("inf"), "")) - 1
        if i >= 0 and v[i][0] <= e["start_ns"] <= v[i][1]:
            e["module"] = v[i][2]


def is_device(ev: dict) -> bool:
    return (ev["plane"].startswith("/device:TPU:")
            and ev["line"] == OPS_LINE)


def device_events(evs: list[dict]) -> list[dict]:
    return [e for e in evs if is_device(e) and e["dur_ns"] > 0]


_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?metadata=\{[^}]*?"
    r'op_name="([^"]*)"', re.M)
_HLO_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def op_names_from_hlo(hlo_text: str) -> dict[tuple[str, str], str]:
    """``{(module, instruction): op_name}`` of a compiled module's text."""
    m = _HLO_MODULE.search(hlo_text)
    module = m.group(1) if m else ""
    return {(module, name): op for name, op in _HLO_INSTR.findall(hlo_text)}


_INSTR = re.compile(r"^%?([\w.\-]+)\s*=")


def op_name(ev: dict, hlo_names: dict | None = None) -> str:
    """The op's ``op_name`` metadata: from its stats where the trace
    carries it, else looked up by module and instruction name (a TPU
    trace names an op by its HLO text, ``%fusion.3 = ...``)."""
    for k in _OP_NAME_STATS:
        v = ev["stats"].get(k)
        if isinstance(v, str) and "/" in v:
            return v
    if hlo_names:
        mod = str(ev.get("module", ev["stats"].get("hlo_module", "")))
        m = _INSTR.match(ev["name"])
        op = str(ev["stats"].get("hlo_op", m.group(1) if m else ev["name"]))
        return hlo_names.get((mod, op), "")
    return ""


def union_ns(intervals) -> int:
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return int(total)


def busy_ns(devs: list[dict], device: str | None = None) -> int:
    return union_ns((e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in devs if device in (None, e["plane"]))


def scope_ns(devs: list[dict], scope: str, hlo_names=None,
             device: str | None = None) -> int:
    """Device time under ``jax.named_scope(scope)``, as a union."""
    pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
    return union_ns((e["start_ns"], e["start_ns"] + e["dur_ns"])
                    for e in devs if device in (None, e["plane"])
                    and pat.search(op_name(e, hlo_names)))


def top_ops(devs: list[dict], n: int = 10) -> list[list]:
    """The device ops that took most time: ``[[name, seconds], ...]``,
    an op named by its module and instruction."""
    tot: dict[str, int] = {}
    for e in devs:
        m = _INSTR.match(e["name"])
        k = (e.get("module", "") + ":" if "module" in e else "") + (
            m.group(1) if m else e["name"])
        tot[k] = tot.get(k, 0) + e["dur_ns"]
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(devs: list[dict], host: list[dict], t0: int, t1: int,
              n: int = 10) -> list[list]:
    """The longest gaps between device ops in ``[t0, t1]``, each named by
    the innermost host span that covers its middle."""
    iv = sorted((e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in devs)
    gaps, cur = [], t0
    for s, e in iv:
        if s > cur:
            gaps.append((s - cur, cur, s))
        cur = max(cur, e)
    if t1 > cur:
        gaps.append((t1 - cur, cur, t1))
    gaps.sort(reverse=True)
    out = []
    for length, s, e in gaps[:n]:
        mid = (s + e) // 2
        cover = [h for h in host if h["start_ns"] <= mid
                 <= h["start_ns"] + h["dur_ns"]]
        name = (min(cover, key=lambda h: h["dur_ns"])["name"] if cover
                else "no host span")
        out.append([name, length / 1e9])
    return out
