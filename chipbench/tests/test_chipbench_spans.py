"""Idle time by program span, the engine's trips on the trace, and the
readers of the env's scopes, on events whose answers are counted by hand
and on a trace recorded on the chip."""
import pytest

import reduce_trace as tr
import run
import span_idle

DEV = "/device:TPU:0"
HOST = "/host:CPU"
ENGINE = "jit(tick)/vmap(serving.period)/vmap(env.engine)/jit(simulate_jax)"
ACT = "jit(tick)/vmap(serving.period)/vmap(env.act)"
SLOTS = "jit(tick)/vmap(serving.period)/vmap(env.slots)"


def dev(name, start, dur, op):
    return dict(plane=DEV, line=tr.OPS_LINE, name=name, start_ns=start,
                dur_ns=dur, stats={"tf_op": op})


def host(name, start, end):
    return dict(plane=HOST, line="python", name=name, start_ns=start,
                dur_ns=end - start, stats={})


# device busy 100..170, 300..350, 600..700 of the window 0..800
DEVS = [dev("fusion.1", 100, 70, ENGINE + "/while/body/gather"),
        dev("fusion.2", 300, 50, ACT + "/while/body/dot_general"),
        dev("fusion.3", 600, 100, SLOTS + "/sort")]
SPANS = [host("serve.session", 50, 790), host("serve.resolve", 60, 150),
         host("serve.setup", 155, 180), host("serve.tick", 185, 650),
         host("serve.stage", 190, 260), host("serve.dispatch", 260, 320),
         host("serve.readback", 320, 500), host("serve.record", 500, 560),
         host("serve.flush", 700, 780)]


def test_idle_intervals_are_the_gaps_in_the_window():
    assert span_idle.idle_intervals(DEVS, 0, 800) == [
        (0, 100), (170, 300), (350, 600), (700, 800)]
    # ops that straddle the window's ends are cut to it
    assert span_idle.idle_intervals(DEVS, 150, 650) == [(170, 300),
                                                        (350, 600)]


def test_idle_by_leaf_span():
    got = span_idle.idle_by_span(DEVS, SPANS, 0, 800, span_idle.LEAF_SPANS)
    want = {"serve.resolve": 40, "serve.setup": 10, "serve.stage": 70,
            "serve.dispatch": 40, "serve.readback": 150, "serve.record": 60,
            "serve.flush": 80, "none": 60 + 10 + 40 + 20}
    assert got == {k: v / 1e9 for k, v in want.items()}
    assert sum(got.values()) == pytest.approx(580e-9)


def test_idle_goes_to_the_innermost_span():
    names = span_idle.LEAF_SPANS + ("serve.tick", "serve.session")
    got = span_idle.idle_by_span(DEVS, SPANS, 0, 800, names)
    assert got["serve.tick"] == pytest.approx(45e-9)     # 185..190, 560..600
    # 50..60, 180..185, 780..790
    assert got["serve.session"] == pytest.approx(25e-9)
    assert got["none"] == pytest.approx(60e-9)           # 0..50, 790..800
    assert got["serve.readback"] == pytest.approx(150e-9)
    # a span named nowhere in `names` takes nothing
    assert "serve.tick" not in span_idle.idle_by_span(
        DEVS, SPANS, 0, 800, ("serve.stage",))


def test_engine_trips_count_one_body_instruction():
    devs = ([dev("fusion.7", 10 * i, 5, ENGINE + "/while/body/gather")
             for i in range(3)]
            + [dev("fusion.8", 10 * i + 5, 2, ENGINE + "/while/body/add")
               for i in range(3)]
            # the loop's condition runs once more than its body
            + [dev("fusion.9", 10 * i + 8, 1, ENGINE + "/while/cond/or")
               for i in range(4)]
            # the actor's recurrence is another loop
            + [dev("fusion.5", 100 + i, 1, ACT + "/while/body/dot_general")
               for i in range(97)])
    assert span_idle.engine_trips(devs) == 3
    assert span_idle.engine_trips(devs[6:]) == 0


class Ctx:
    """What the readers read of ``serve_cell.LayerContext``."""

    def __init__(self, devs, ticks, hlo_names=None):
        self.devs, self.ticks, self.hlo_names = devs, ticks, hlo_names

    def scope_ms(self, scope):
        return tr.scope_ns(self.devs, scope, self.hlo_names) / 1e6


@pytest.mark.parametrize("metric,ns", [("serve.engine_ms", 70),
                                       ("serve.actor_ms", 50),
                                       ("serve.slots_ms", 100)])
def test_scope_readers(metric, ns):
    assert run.read_layer(metric, Ctx(DEVS, 2)) == pytest.approx(
        ns / 1e6 / 2)
    # a program without the scope reads nothing
    assert run.read_layer(metric, Ctx([], 2)) is None


def test_engine_trips_reader():
    devs = [dev("fusion.7", 10 * i, 5, ENGINE + "/while/body/gather")
            for i in range(12)]
    assert run.read_layer("serve.engine_trips", Ctx(devs, 4)) == 3
    assert run.read_layer("serve.engine_trips", Ctx(DEVS[1:], 4)) is None


def _recorded():
    """Three ticks of the 96-stream light serving tick traced on a TPU
    v5 lite chip with the program's spans and scopes (device ops, module
    spans, the ``serve.*`` host spans), the ``op_name`` of each
    instruction that ran, and the device telemetry block's counters of
    the same session."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "serve_tick_spans.json.gz")
    with gzip.open(path, "rt") as fh:
        d = json.load(fh)
    d["hlo_op_names"] = {(m, i): o for m, i, o in d["hlo_op_names"]}
    return d


def test_recorded_spans_and_scopes():
    d = _recorded()
    evs, names = d["events"], d["hlo_op_names"]
    win = [e for e in evs if e["name"] == "chipbench.window"][0]
    t0, t1 = win["start_ns"], win["start_ns"] + win["dur_ns"]
    devs = tr.device_events(evs)
    spans = [e for e in evs if e["name"].startswith("serve.")]
    ticks = [e for e in spans if e["name"] == "serve.tick"]
    assert len(ticks) == d["ticks"]
    # the leaf spans hold nearly all of the idle time
    idle = span_idle.idle_by_span(devs, spans, t0, t1, span_idle.LEAF_SPANS)
    assert idle["none"] < 0.1 * sum(idle.values())
    assert idle["serve.readback"] > 0 and idle["serve.resolve"] > 0
    # the env's scopes lie inside serving.period
    ctx = Ctx(devs, d["ticks"], names)
    parts = [run.read_layer(m, ctx) for m in (
        "serve.engine_ms", "serve.actor_ms", "serve.slots_ms")]
    assert all(p and p > 0 for p in parts)
    assert sum(parts) <= run.read_layer("serve.period_ms", ctx)
    # the trips counted on the trace are the telemetry block's
    assert span_idle.engine_trips(devs, names) == \
        d["counters"]["engine_trips"]
