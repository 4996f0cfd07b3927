"""The trace reduction on events whose answers are counted by hand."""
import reduce_trace as tr

DEV = "/device:TPU:0"


def ev(name, start, dur, op="", plane=DEV, line=tr.OPS_LINE, **stats):
    if op:
        stats["tf_op"] = op
    return dict(plane=plane, line=line, name=name, start_ns=start,
                dur_ns=dur, stats=stats)


EVENTS = [
    # a while loop op that encloses two body ops: busy 100..400
    ev("while.1", 100, 300, "jit(tick)/vmap(serving.period)/while"),
    ev("fusion.2", 120, 50, "jit(tick)/vmap(serving.period)/while/body/add"),
    ev("fusion.3", 200, 100, "jit(tick)/vmap(serving.period)/while/body/mul"),
    ev("fusion.4", 500, 40, "jit(tick)/vmap(serving.admit)/scatter"),
    ev("fusion.5", 560, 20, "jit(tick)/vmap(serving.retire)/select"),
    # a scope whose name is a prefix of another must not match it
    ev("fusion.6", 600, 10, "jit(tick)/serving.periodic/x"),
    # not a device op line, and a host span over the idle gap 400..500
    ev("Steps", 0, 1000, line="Steps"),
    ev("serve_stream", 380, 140, plane="/host:CPU", line="python"),
    ev("take_along_axis", 410, 60, plane="/host:CPU", line="python"),
]


def test_busy_is_the_union_of_device_ops():
    devs = tr.device_events(EVENTS)
    assert len(devs) == 6
    assert tr.busy_ns(devs) == 300 + 40 + 20 + 10


def test_scope_mapping_by_op_name():
    devs = tr.device_events(EVENTS)
    assert tr.scope_ns(devs, "serving.period") == 300
    assert tr.scope_ns(devs, "serving.admit") == 40
    assert tr.scope_ns(devs, "serving.retire") == 20
    assert tr.scope_ns(devs, "serving.telemetry") == 0


def test_scope_from_hlo_text_when_the_trace_has_no_op_name():
    hlo = ('HloModule jit_tick, entry_computation_layout={()}\n'
           '  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, '
           'metadata={op_name="jit(tick)/vmap(serving.admit)/scatter" '
           'source_file="q.py"}\n')
    names = tr.op_names_from_hlo(hlo)
    e = ev("fusion.4", 0, 7, hlo_op="fusion.4", hlo_module="jit_tick")
    assert tr.op_name(e, names) == "jit(tick)/vmap(serving.admit)/scatter"
    assert tr.scope_ns([e], "serving.admit", names) == 7


def test_idle_gaps_are_named_by_the_innermost_host_span():
    devs = tr.device_events(EVENTS)
    host = [e for e in EVENTS if e["plane"].startswith("/host:")]
    gaps = tr.idle_gaps(devs, host, 0, 700)
    assert gaps[0] == ["take_along_axis", 100e-9]
    assert [g[1] for g in gaps] == [100e-9, 100e-9, 90e-9, 20e-9, 20e-9]
    assert gaps[1][0] == "no host span"


def test_top_ops():
    devs = tr.device_events(EVENTS)
    assert tr.top_ops(devs, 2) == [["while.1", 300e-9], ["fusion.3", 100e-9]]


def _recorded():
    """Two ticks of the 96-stream light serving tick, traced on a TPU v5
    lite chip (device ops, module spans, host spans of the window) with
    the tick's ``op_name`` of each instruction that ran."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "serve_tick_trace.json.gz")
    with gzip.open(path, "rt") as fh:
        d = json.load(fh)
    names = {(m, i): o for m, i, o in d["hlo_op_names"]}
    return d["events"], names


def test_recorded_tpu_trace():
    evs, names = _recorded()
    win = [e for e in evs if e["name"] == "chipbench.window"][0]
    devs = tr.device_events(evs)
    assert devs and all(e.get("module") for e in devs)
    busy = tr.busy_ns(devs)
    scopes = {s: tr.scope_ns(devs, s, names)
              for s in ("serving.admit", "serving.period", "serving.retire")}
    # every scope ran; the engine and the actor take most of the tick
    assert all(v > 0 for v in scopes.values()), scopes
    assert scopes["serving.period"] > scopes["serving.admit"] + scopes[
        "serving.retire"]
    assert sum(scopes.values()) <= busy <= win["dur_ns"]
    # the tick is the only module with scopes, so they add up to its time
    tick = tr.union_ns((e["start_ns"], e["start_ns"] + e["dur_ns"])
                       for e in devs if e["module"] == "jit_tick")
    assert sum(scopes.values()) <= tick
    assert tr.top_ops(devs, 1)[0][0].startswith("jit_tick:while")
