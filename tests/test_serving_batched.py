"""Batched serving path: bit-parity with the host-loop reference,
queue mechanics, admission validation, and the engine's serving-only
early exit.

The load-bearing property is *parity*: a request stream replayed into
the device-resident queue (``serve_stream``, one dispatch per tick)
must retire the exact SLA / energy / per-tenant numbers of the same
workload run through ``serve_trace_host`` (one dispatch per period,
trace known upfront).  Everything the tick path does differently —
masked-scatter admission, cumulative accumulators, ``commit_only``
engine early exit — is pinned bit-for-bit here for several streams
and for the generalist on every fleet preset; the specialist and the
heuristic baselines on every fleet are pinned in
``test_serving_parity_specialist.py`` and
``test_serving_parity_baselines.py``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import generalist as G
from repro.core import policy as P
from repro.core.serve import (host_layout, host_unpack, make_serving_tick,
                              queue_init_batch)
from repro.costmodel.fleets import fleet_names
from repro.serving import (LoadGenConfig, MultiTenantService, Request,
                           pack_admissions, per_tenant_metrics, queue_admit,
                           queue_init, queue_retire, request_stream,
                           resolve_request, trace_to_requests)
from repro.serving.loadgen import requests_to_trace
from repro.sim.engine import INF, simulate_jax
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.telemetry import ListSink, Telemetry
from repro.workloads import build_registry

CFG = EnvConfig(periods=10, max_rq=32, max_jobs=12)


# ---------------------------------------------------------------------------
# host-loop vs single-dispatch tick: bit-identical on the same workload
# ---------------------------------------------------------------------------
def test_multi_stream_parity_each_stream_matches_its_reference(assert_parity):
    """The stream vmap axis must not couple queues: each of S streams
    retires exactly its own single-stream reference numbers."""
    svc = MultiTenantService(build_registry("light"), policy="relmas",
                             env_cfg=CFG)
    traces = [svc.env.new_episode(np.random.default_rng(s))[0]
              for s in (5, 6)]
    refs = [svc.serve_trace_host(tr, seed=9) for tr in traces]
    out = svc.serve_stream([trace_to_requests(svc.env, tr) for tr in traces],
                           tick_k=CFG.max_jobs, seed=9)
    for ref, m in zip(refs, out["metrics"]):
        assert_parity(ref, m)


def _generalist_service(m_max: int = 6, hidden: int = 8,
                        fleet: str = "paper6"):
    """A generalist-serving service without a checkpoint on disk: the
    exact attribute set ``__init__``'s generalist branch produces, with
    freshly-initialized weights (parity only needs determinism)."""
    cfg = EnvConfig(periods=6, max_rq=16, max_jobs=8)
    svc = MultiTenantService.__new__(MultiTenantService)
    svc.env = G.PaddedEnv(build_registry("light", mas=fleet), cfg, m_max)
    spec = G.GeneralistSpec(m_max=m_max)
    svc.pcfg = spec.pcfg(hidden=hidden)
    svc.params = P.init_actor(jax.random.PRNGKey(7), svc.pcfg)
    svc.policy_name = "relmas"
    svc.policy_kind = "generalist"
    svc._baseline_fn = None
    svc._period = G.make_generalist_period(svc.env, svc.pcfg)
    return svc


@pytest.mark.parametrize("fleet", fleet_names())
def test_generalist_parity_host_vs_batched(fleet, assert_parity):
    svc = _generalist_service(m_max=8, fleet=fleet)
    trace, _ = svc.env.new_episode(np.random.default_rng(2))
    ref = svc.serve_trace_host(trace, seed=2)
    out = svc.serve_stream(trace_to_requests(svc.env, trace),
                           tick_k=svc.env.cfg.max_jobs, seed=2)
    assert_parity(ref, out["metrics"][0])


def test_requests_to_trace_roundtrip_is_identity_on_real_rows():
    """Padding rows (arrival = INF) are rebuilt with neutral fill values
    — they are invisible to the sim — but every *real* row must survive
    the trace -> requests -> trace roundtrip bit-for-bit."""
    env = SchedulingEnv(build_registry("light"), CFG)
    trace, _ = env.new_episode(np.random.default_rng(4))
    tr2 = requests_to_trace(env, trace_to_requests(env, trace))
    real = np.asarray(trace["arrival"]) < INF / 2
    np.testing.assert_array_equal(np.asarray(trace["arrival"]),
                                  np.asarray(tr2["arrival"]))
    for k in ("deadline", "q", "model", "njl"):
        np.testing.assert_array_equal(np.asarray(trace[k])[real],
                                      np.asarray(tr2[k])[real])


# ---------------------------------------------------------------------------
# queue mechanics: rejection when full, deferral, retire frees slots
# ---------------------------------------------------------------------------
def _tiny_env(max_jobs=4):
    return SchedulingEnv(build_registry("light"),
                         EnvConfig(periods=4, max_rq=16, max_jobs=max_jobs))


def test_queue_admit_rejects_overflow_rows():
    env = _tiny_env(max_jobs=4)
    qs = queue_init(env)
    rows = [(i, 0, 0.0, 1000.0, 1000.0) for i in range(6)]
    qs, n_adm = queue_admit(env, qs, pack_admissions(rows, 6))
    assert int(n_adm) == 4                      # capacity, not staged count
    assert bool(jnp.all(qs["occupied"]))
    assert int(qs["acc"]["admitted"]) == 4
    assert int(qs["acc"]["rejected"]) == 2
    # the four admitted rows landed in arrival order at slots 0..3
    np.testing.assert_array_equal(np.asarray(qs["rid"]), [0, 1, 2, 3])


def test_queue_retire_frees_slots_and_accumulates():
    env = _tiny_env(max_jobs=4)
    qs = queue_init(env)
    rows = [(i, 0, 0.0, 1000.0, 1000.0) for i in range(4)]
    qs, _ = queue_admit(env, qs, pack_admissions(rows, 4))
    done = jnp.array([True, False, True, False])
    hit = jnp.array([True, False, False, False])
    qs, out = queue_retire(env, {**qs, "state": {**qs["state"],
                                                 "done": done, "hit": hit}})
    np.testing.assert_array_equal(np.asarray(out["completed"]),
                                  [True, False, True, False])
    np.testing.assert_array_equal(np.asarray(qs["occupied"]),
                                  [False, True, False, True])
    # freed slots become invisible to build_slots/mark_drops
    assert np.all(np.asarray(qs["trace"]["arrival"])[[0, 2]] >= INF / 2)
    assert int(qs["acc"]["counted"]) == 2
    assert int(qs["acc"]["hits"]) == 1
    assert int(qs["acc"]["ten_counted"][0]) == 2


def test_pack_admissions_overflow_raises():
    with pytest.raises(ValueError, match="> tick_k"):
        pack_admissions([(i, 0, 0.0, 1.0, 1.0) for i in range(3)], 2)


def test_serve_stream_defers_then_serves_oversubscribed_burst():
    """More simultaneous arrivals than queue slots: the surplus must be
    deferred (re-staged next tick), never dropped — every request is
    eventually admitted once drops/completions free slots."""
    cfg = EnvConfig(periods=20, max_rq=24, max_jobs=8)
    svc = MultiTenantService(build_registry("light"), policy="relmas",
                             env_cfg=cfg)
    name = svc.env.registry.model_names[0]
    reqs = [Request(rid=i, tenant=name, arrival_us=0.0, deadline_us=2000.0)
            for i in range(16)]
    out = svc.serve_stream(reqs, tick_k=8, seed=0)
    assert out["stats"]["deferred"] > 0
    assert out["stats"]["unserved"] == 0
    assert out["aggregate"]["arrived"] == 16
    assert out["aggregate"]["counted"] == 16


# ---------------------------------------------------------------------------
# engine early exit: committed-prefix results are bit-identical
# ---------------------------------------------------------------------------
def test_simulate_jax_stop_start_after_prefix_equality():
    rng = np.random.default_rng(0)
    n, M = 12, 3
    valid = np.ones((n,), bool)
    assign = rng.integers(0, M, size=n)
    prio = rng.uniform(0, 1, size=n).astype(np.float32)
    cost = rng.uniform(50, 200, size=n).astype(np.float32)
    bw = rng.uniform(0, 2, size=n).astype(np.float32)
    dep = np.full((n,), -1, np.int32)
    dep[5], dep[9] = 1, 4                       # a couple of chains
    ready = np.zeros((n,), np.float32)
    sa_free = np.zeros((M,), np.float32)
    args = (valid, assign, prio, cost, bw, dep, ready, sa_free,
            jnp.float32(4.0))
    s_full, f_full = simulate_jax(*args, num_sas=M)
    stop = float(np.median(np.asarray(s_full)))
    s_cut, f_cut = simulate_jax(*args, num_sas=M, stop_start_after=stop)
    early = np.asarray(s_full) < stop
    assert early.any() and not early.all()
    # every SJ starting before the horizon: exact start AND finish
    np.testing.assert_array_equal(np.asarray(s_cut)[early],
                                  np.asarray(s_full)[early])
    np.testing.assert_array_equal(np.asarray(f_cut)[early],
                                  np.asarray(f_full)[early])
    # stop_start_after=None is the unhorizoned loop, bit-for-bit
    s_none, f_none = simulate_jax(*args, num_sas=M, stop_start_after=None)
    np.testing.assert_array_equal(np.asarray(s_none), np.asarray(s_full))
    np.testing.assert_array_equal(np.asarray(f_none), np.asarray(f_full))


# ---------------------------------------------------------------------------
# admission validation: malformed requests are rejected with clear errors
# ---------------------------------------------------------------------------
def test_resolve_request_unknown_model_id():
    with pytest.raises(ValueError, match="unknown model id"):
        resolve_request(Request(rid=0, tenant="nonexistent_model",
                                arrival_us=0.0, deadline_us=100.0),
                        ["squeezenet", "yolo_lite"])


def test_resolve_request_non_positive_sla_budget():
    with pytest.raises(ValueError, match="non-positive SLA budget"):
        resolve_request(Request(rid=1, tenant="squeezenet",
                                arrival_us=100.0, deadline_us=100.0),
                        ["squeezenet"])
    with pytest.raises(ValueError, match="non-positive SLA budget"):
        resolve_request(Request(rid=2, tenant="squeezenet",
                                arrival_us=0.0, deadline_us=50.0,
                                q_us=-1.0),
                        ["squeezenet"])


def test_serve_stream_rejects_malformed_request_upfront():
    svc = MultiTenantService(build_registry("light"), policy="fcfs",
                             env_cfg=CFG)
    bad = [Request(rid=0, tenant="not_served", arrival_us=0.0,
                   deadline_us=100.0)]
    with pytest.raises(ValueError, match="unknown model id"):
        svc.serve_stream(bad)


def test_loadgen_config_validation():
    with pytest.raises(ValueError, match="unknown scenario"):
        LoadGenConfig(scenario="flash_crowd")
    with pytest.raises(ValueError, match="rate_scale"):
        LoadGenConfig(rate_scale=0.0)
    with pytest.raises(ValueError, match="n_requests"):
        LoadGenConfig(n_requests=0)


def test_request_stream_rejects_non_positive_sla_multiplier():
    env = SchedulingEnv(build_registry("light"), CFG)
    with pytest.raises(ValueError, match="non-positive SLA multiplier"):
        request_stream(env, LoadGenConfig(scenario="steady", qos_factor=0.0),
                       np.random.default_rng(0))


# ---------------------------------------------------------------------------
# per-tenant metrics
# ---------------------------------------------------------------------------
def test_per_tenant_metrics_zero_arrival_tenant_is_none():
    env = SchedulingEnv(build_registry("light"), CFG)
    names = env.registry.model_names
    J = 4
    trace = dict(arrival=np.array([0.0, 0.0, 0.0, INF], np.float32),
                 model=np.array([1, 1, 2, 0], np.int32))
    state = dict(hit=np.array([True, False, True, False]),
                 done=np.array([True, True, True, False]),
                 missed=np.zeros((J,), bool))
    out = per_tenant_metrics(env, state, trace)
    assert out[names[0]] == {"jobs": 0, "sla_rate": None}
    assert out[names[1]] == {"jobs": 2, "sla_rate": 0.5}
    assert out[names[2]] == {"jobs": 1, "sla_rate": 1.0}


def test_per_tenant_jobs_sum_to_counted_on_real_episode():
    svc = MultiTenantService(build_registry("light"), policy="relmas",
                             env_cfg=CFG)
    m = svc.serve_episode_host(seed=11)
    assert sum(t["jobs"] for t in m["per_tenant"].values()) == m["counted"]
    # and the batched path's table obeys the same invariant
    trace, _ = svc.env.new_episode(np.random.default_rng(11))
    out = svc.serve_stream(trace_to_requests(svc.env, trace),
                           tick_k=CFG.max_jobs, seed=11)
    bm = out["metrics"][0]
    assert sum(t["jobs"] for t in bm["per_tenant"].values()) == bm["counted"]


# ---------------------------------------------------------------------------
# the tick's packed host record: one read a tick, the same answers
# ---------------------------------------------------------------------------
CNN_HOST_FIELDS = {"n_admitted", "depth", "completed", "hit", "missed",
                   "rid", "finish_us"}


def _staged_traces(env, streams: int, seed: int) -> dict:
    """Every real job of one drawn trace per stream, staged at once."""
    packs = []
    for s in range(streams):
        tr, _ = env.new_episode(np.random.default_rng(seed + s))
        arr = np.asarray(tr["arrival"])
        packs.append(pack_admissions(
            [(j, int(tr["model"][j]), float(arr[j]),
              float(tr["deadline"][j]), float(tr["q"][j]))
             for j in range(arr.shape[0]) if arr[j] < INF / 2],
            env.cfg.max_jobs))
    return {k: np.stack([p[k] for p in packs]) for k in packs[0]}


def test_host_record_unpacks_to_the_ticks_own_fields():
    svc = MultiTenantService(build_registry("light"), policy="relmas",
                             env_cfg=CFG)
    env, S = svc.env, 3
    tick = make_serving_tick(env, kind="specialist", pcfg=svc.pcfg,
                             streams=S)
    layout = host_layout(env)
    queues = queue_init_batch(env, S)
    adm0 = _staged_traces(env, S, seed=20)
    seen = dict(completed=0, inf=0, finite=0)
    for i in range(CFG.periods):
        adm = adm0 if i == 0 else {**adm0,
                                   "valid": np.zeros_like(adm0["valid"])}
        queues, out = tick(svc.params, queues, adm, jax.random.PRNGKey(i))
        got = host_unpack(layout, np.asarray(out["host"]))
        assert set(got) == CNN_HOST_FIELDS
        for k, v in got.items():
            want = np.asarray(out[k])
            assert (v.dtype, v.shape) == (want.dtype, want.shape), k
            assert v.tobytes() == want.tobytes(), (i, k)
        fin = got["finish_us"]
        seen["completed"] += int(got["completed"].sum())
        seen["inf"] += int((fin == INF).sum())
        seen["finite"] += int((fin < INF).sum())
    assert all(seen.values()), seen


def _same_session(res: dict, ref: dict):
    stats = {k: v for k, v in res["stats"].items()
             if k not in ("tick_wall_us", "readbacks")}
    assert stats == ref["stats"]
    for k in ("completions", "metrics", "aggregate"):
        assert res[k] == ref[k], k


@pytest.mark.parametrize("policy,tick_k", [("relmas", CFG.max_jobs),
                                           ("fcfs", 3), ("generalist", 4)])
def test_serve_stream_equals_the_field_by_field_loop(serve_fieldwise, policy,
                                                     tick_k):
    """Three streams, backlogs deferred where ``tick_k`` is small: the
    loop over the packed record serves what a loop reading each field
    with its own transfer serves."""
    if policy == "generalist":
        svc = _generalist_service()
    else:
        svc = MultiTenantService(build_registry("light"), policy=policy,
                                 env_cfg=CFG)
    reqs = [trace_to_requests(svc.env, svc.env.new_episode(
        np.random.default_rng(30 + s))[0]) for s in range(3)]
    res = svc.serve_stream(reqs, tick_k=tick_k, seed=4)
    ref = serve_fieldwise(svc, reqs, tick_k=tick_k, seed=4)
    assert res["aggregate"]["completed"] > 0
    _same_session(res, ref)


@pytest.mark.parametrize("telemetry", [False, True])
def test_serve_stream_reads_once_a_tick_and_once_at_the_flush(telemetry):
    svc = MultiTenantService(build_registry("light"), policy="relmas",
                             env_cfg=CFG)
    reqs = [trace_to_requests(svc.env, svc.env.new_episode(
        np.random.default_rng(s))[0]) for s in range(2)]
    sink = ListSink()
    res = svc.serve_stream(reqs, tick_k=CFG.max_jobs, ticks=7, seed=0,
                           telemetry=Telemetry([sink]) if telemetry
                           else None, window=3)
    assert res["stats"]["readbacks"] == res["stats"]["ticks"] + 1 == 8
    if telemetry:
        (summ,) = [r for r in sink.records if r["kind"] == "serve_summary"]
        assert summ["readbacks"] == 8
