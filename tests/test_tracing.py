"""The serving path's instrumentation: host spans on the profiler's clock,
the env's named scopes in the compiled tick, the engine's loop counters
in the device telemetry block, and the compile counter.

Nothing here may change what the program computes: the counters ride
the structural ``tele`` gate (telemetry-on and -off ticks leave
bit-equal queues) and a named scope changes only ``op_name`` metadata.
"""
import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.serve import (make_serving_tick, queue_init_batch,
                              specialist_act)
from repro.serving import (MultiTenantService, pack_admissions, queue_admit,
                           trace_to_requests)
from repro.sim.engine import INF
from repro.sim.env import EnvConfig
from repro.telemetry import (ListSink, Telemetry, compile_counts,
                             install_compile_counter)
from repro.workloads import build_registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = EnvConfig(periods=6, max_rq=24, max_jobs=8)
TICK_SPANS = ("serve.stage", "serve.dispatch", "serve.readback",
              "serve.record")


def _svc(policy: str = "relmas") -> MultiTenantService:
    return MultiTenantService(build_registry("light"), policy=policy,
                              env_cfg=CFG, hidden=8)


def _host_events(log_dir: str) -> list[dict]:
    """The host events of a ``jax.profiler`` trace, as plain dicts."""
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return [dict(name=e.name, start=e.start_ns, end=e.start_ns
                 + e.duration_ns, stats={str(k): v for k, v in e.stats})
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _inside(inner: dict, outer: dict) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


def _streams(svc, n: int):
    return [trace_to_requests(svc.env, svc.env.new_episode(
        np.random.default_rng(s))[0]) for s in range(n)]


# ---------------------------------------------------------------------------
# host spans
# ---------------------------------------------------------------------------
def test_serve_stream_spans_nest_on_the_profiler_clock(tmp_path):
    svc = _svc("fcfs")
    reqs = _streams(svc, 2)
    svc.serve_stream(reqs, tick_k=CFG.max_jobs, seed=0)     # compile
    with jax.profiler.trace(str(tmp_path)):
        res = svc.serve_stream(reqs, tick_k=CFG.max_jobs, seed=0)
    evs = [e for e in _host_events(str(tmp_path))
           if e["name"].startswith("serve.")]
    by = {}
    for e in evs:
        by.setdefault(e["name"], []).append(e)
    (session,) = by["serve.session"]
    assert session["stats"]["streams"] == 2
    (resolve,), (setup,), (flush,) = (by["serve.resolve"],
                                      by["serve.setup"], by["serve.flush"])
    ticks = sorted(by["serve.tick"], key=lambda e: e["start"])
    # one step span per tick, numbered in order, all inside the session
    assert len(ticks) == res["stats"]["ticks"] == CFG.periods
    assert [t["stats"]["step_num"] for t in ticks] == list(range(len(ticks)))
    assert all(_inside(e, session) for e in [resolve, setup, flush] + ticks)
    assert resolve["end"] <= setup["start"]
    assert setup["end"] <= ticks[0]["start"]
    assert ticks[-1]["end"] <= flush["start"]
    # every tick holds one stage, dispatch and readback, in that order;
    # a record only where the tick completed jobs, and none outside
    for name in TICK_SPANS[:3]:
        assert len(by[name]) == len(ticks), name
    for t in ticks:
        kids = sorted((e for e in evs if e["name"] in TICK_SPANS
                       and _inside(e, t)), key=lambda e: e["start"])
        assert [k["name"] for k in kids][:3] == list(TICK_SPANS[:3])
        assert all(k["name"] == "serve.record" for k in kids[3:])
    assert all(any(_inside(r, t) for t in ticks)
               for r in by.get("serve.record", []))


def test_telemetry_span_emits_its_record_and_opens_an_annotation(tmp_path):
    sink = ListSink()
    tele = Telemetry([sink])
    with jax.profiler.trace(str(tmp_path)):
        with tele.span("collect", episodes=3):
            pass
    (rec,) = sink.records
    assert rec["kind"] == "span" and rec["name"] == "collect"
    assert rec["episodes"] == 3 and rec["secs"] >= 0
    (ev,) = [e for e in _host_events(str(tmp_path))
             if e["name"] == "collect"]
    assert ev["stats"]["episodes"] == 3


def test_serve_driver_profile_holds_its_spans_and_run_end_its_compiles(
        tmp_path):
    """``launch/serve.py --profile-dir``: the driver's ``serve`` span and
    the serving loop's spans land in the trace; ``run_end`` carries the
    compile counter."""
    log, prof = tmp_path / "serve.jsonl", tmp_path / "prof"
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--workload", "light",
         "--batched", "--streams", "2", "--requests", "4", "--periods", "4",
         "--max-rq", "24", "--max-jobs", "8", "--hidden", "8",
         "--log-jsonl", str(log), "--profile-dir", str(prof)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    end = [json.loads(line) for line in log.read_text().splitlines()][-1]
    assert end["kind"] == "run_end" and end["compile"]["compile_n"] > 0
    names = [e["name"] for e in _host_events(str(prof))]
    assert "serve" in names and names.count("serve.tick") == 4


# ---------------------------------------------------------------------------
# device scopes
# ---------------------------------------------------------------------------
def _tick_text(svc, streams: int = 2) -> str:
    env, K = svc.env, 3
    tick = make_serving_tick(env, kind=svc.policy_kind, pcfg=svc.pcfg,
                             streams=streams)
    adm = dict(model=np.zeros((streams, K), np.int32),
               arrival=np.zeros((streams, K), np.float32),
               deadline=np.zeros((streams, K), np.float32),
               q=np.ones((streams, K), np.float32),
               rid=np.zeros((streams, K), np.int32),
               valid=np.zeros((streams, K), bool))
    return tick.lower(svc.params, queue_init_batch(env, streams), adm,
                      jax.random.PRNGKey(0)).compile().as_text()


def _strip_metadata(text: str) -> str:
    """The module's text without its source locations: each
    instruction's ``metadata={...}`` and the stack-frame tables."""
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", text,
                  flags=re.S)
    return re.sub(r",? metadata=\{[^}]*\}", "", text)


def test_compiled_tick_names_the_env_scopes():
    names = re.findall(r'op_name="([^"]*)"', _tick_text(_svc()))
    for scope in ("env.slots", "env.act", "env.engine"):
        pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
        assert any(pat.search(n) for n in names), scope
    # the engine's event loop lies under env.engine, the actor's
    # recurrence under env.act
    assert any("env.engine" in n and "/while" in n for n in names)
    assert any("env.act" in n and "dot_general" in n for n in names)


def test_named_scopes_change_only_metadata(monkeypatch):
    """The telemetry-off tick compiles to the same program with the
    env's scopes as without them, once metadata is stripped."""
    with_scopes = _strip_metadata(_tick_text(_svc()))
    import repro.sim.env as env_mod

    class NoScope:
        @staticmethod
        def named_scope(name):
            import contextlib
            return contextlib.nullcontext()

        def __getattr__(self, k):
            return getattr(jax, k)

    monkeypatch.setattr(env_mod, "jax", NoScope())
    without = _strip_metadata(_tick_text(_svc()))
    assert with_scopes == without


# ---------------------------------------------------------------------------
# engine counters in the device telemetry block
# ---------------------------------------------------------------------------
def _admissions(svc, streams: int) -> dict:
    """Every job of one drawn trace per stream, staged at tick 0."""
    rows = []
    for s in range(streams):
        tr, _ = svc.env.new_episode(np.random.default_rng(10 + s))
        arr = np.asarray(tr["arrival"])
        rows.append([(j, int(tr["model"][j]), float(arr[j]),
                      float(tr["deadline"][j]), float(tr["q"][j]))
                     for j in range(arr.shape[0]) if arr[j] < INF / 2])
    packs = [pack_admissions(r, CFG.max_jobs) for r in rows]
    return {k: np.stack([p[k] for p in packs]) for k in packs[0]}


def _no_admissions(adm: dict) -> dict:
    return {**adm, "valid": np.zeros_like(adm["valid"])}


@pytest.mark.parametrize("streams", [2, 4])
def test_engine_counters_equal_each_streams_own_loop(streams):
    svc = _svc()
    env = svc.env
    tick = make_serving_tick(env, kind="specialist", pcfg=svc.pcfg,
                             streams=streams)
    act = specialist_act(svc.pcfg)
    queues = queue_init_batch(env, streams, telemetry=True)
    adm0 = _admissions(svc, streams)
    iters = np.zeros(streams, np.int64)
    trips = 0
    for i in range(CFG.periods):
        adm = adm0 if i == 0 else _no_admissions(adm0)
        pre = jax.device_get(queues)
        key = jax.random.PRNGKey(i)
        queues, _ = tick(svc.params, queues, adm, key)
        # each stream's engine run on its own inputs, outside the vmap
        per = []
        for s in range(streams):
            qs = jax.tree.map(lambda x: jnp.asarray(x[s]), pre)
            qs.pop("tele")
            qs, _ = queue_admit(env, qs, {k: v[s] for k, v in adm.items()})
            _, _, info = env.period(
                qs["state"], qs["trace"],
                lambda f, m, sl, st: act(svc.params, f, m, sl, st, None),
                commit_only=True, engine_iters=True)
            per.append(int(info["engine_iters"]))
        iters += per
        trips += max(per)
    tele = jax.device_get(queues["tele"])
    assert iters.sum() > 0
    assert np.array_equal(tele["engine_iters"], iters)
    assert np.all(tele["engine_trips"] == trips)
    assert np.all(tele["ticks"] == CFG.periods)


def test_telemetry_on_and_off_ticks_leave_bit_equal_queues():
    svc = _svc()
    S = 3
    tick = make_serving_tick(svc.env, kind="specialist", pcfg=svc.pcfg,
                             streams=S)
    q_off = queue_init_batch(svc.env, S)
    q_on = queue_init_batch(svc.env, S, telemetry=True)
    adm0 = _admissions(svc, S)
    for i in range(CFG.periods):
        adm = adm0 if i == 0 else _no_admissions(adm0)
        key = jax.random.PRNGKey(i)
        q_off, o_off = tick(svc.params, q_off, adm, key)
        q_on, o_on = tick(svc.params, q_on, adm, key)
        q_on_h = jax.device_get(q_on)
        tele = q_on_h.pop("tele")
        for a, b in zip(jax.tree.leaves((jax.device_get(q_off), o_off)),
                        jax.tree.leaves((q_on_h, o_on))):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        # the per-tick record carries no counter: only the block does
        assert set(o_on) == set(o_off)
    assert int(tele["ticks"][0]) == CFG.periods


def test_serve_stream_surfaces_the_engine_counters():
    svc = _svc()
    reqs = _streams(svc, 2)
    res = svc.serve_stream(reqs, tick_k=CFG.max_jobs, seed=0,
                           telemetry=Telemetry([ListSink()]))
    dt = res["stats"]["device_tele"]
    assert 0 < dt["engine_trips"] and dt["engine_iters"] > 0
    # each stream's own iterations never exceed the batched loop's trips
    assert dt["engine_iters"] <= 2 * dt["engine_trips"]
    assert "device_tele" not in svc.serve_stream(
        reqs, tick_k=CFG.max_jobs, seed=0)["stats"]


# ---------------------------------------------------------------------------
# compile counter
# ---------------------------------------------------------------------------
def test_compile_counter_counts_a_fresh_jit_once():
    install_compile_counter()
    install_compile_counter()              # idempotent: one listener
    x = np.arange(5, dtype=np.float32)
    f = jax.jit(lambda v: v * 3.0 + 1.0)
    c0 = compile_counts()
    np.asarray(f(x))
    c1 = compile_counts(since=c0)
    assert c1["compile_n"] == 1 and c1["lower_n"] == 1
    assert c1["trace_n"] >= 1 and c1["compile_s"] > 0
    np.asarray(f(x))
    c2 = compile_counts(since=c0)
    assert c2 == c1
