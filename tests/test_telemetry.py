"""Telemetry plane tests: in-graph reducers vs numpy oracles, scan
reducer identities, JSONL schema round-trips, and the load-bearing
bit-neutrality contract — enabling telemetry must not change a single
bit of the fused training round's or the batched serving path's
outputs, and must add zero device dispatches per period."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ddpg as D
from repro.core import policy as P
from repro.core.replay import replay_init
from repro.core.train import make_train_round, make_train_rounds, round_keys
from repro.serving import MultiTenantService, queue_admit, queue_init, \
    queue_retire, trace_to_requests
from repro.sim.arrivals import ArrivalConfig
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.telemetry import (ConsoleSink, JsonlSink, ListSink, SchemaError,
                             Telemetry, counter_add, counter_init,
                             hist_add, hist_init, make_telemetry,
                             null_telemetry, validate_record)
from repro.telemetry.metrics import (ROUND_TELE_KEYS, round_telemetry)
from repro.workloads import build_registry

ECFG = EnvConfig(t_s_us=500.0, periods=6, max_rq=16, max_jobs=8)


@pytest.fixture(scope="module")
def env():
    reg = build_registry("light")
    arr = ArrivalConfig(max_jobs=ECFG.max_jobs, horizon_us=ECFG.horizon_us,
                        slack_us=2 * ECFG.t_s_us)
    return SchedulingEnv(reg, ECFG, arr)


@pytest.fixture(scope="module")
def dcfg(env):
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    return D.DDPGConfig(policy=pcfg)


TRAIN_KW = dict(batch_episodes=2, num_updates=3, batch_size=8,
                sigma_min=0.05, sigma_decay=0.97)


# ---------------------------------------------------------------------------
# histogram vs numpy oracle
# ---------------------------------------------------------------------------
EDGES = (-1.0, 0.0, 0.5, 1.0, 2.0)


def _np_hist(values, edges):
    bins = np.concatenate([[-np.inf], np.asarray(edges, np.float64),
                           [np.inf]])
    return np.histogram(np.asarray(values, np.float64), bins=bins)[0]


def test_hist_add_matches_numpy():
    rng = np.random.default_rng(0)
    v = rng.normal(0.3, 1.2, size=257).astype(np.float32)
    h = hist_add(hist_init(EDGES), v)
    assert np.array_equal(np.asarray(h["counts"]), _np_hist(v, EDGES))
    assert int(np.asarray(h["counts"]).sum()) == v.size


def test_hist_add_edge_values_go_to_upper_bucket():
    # v == edges[k] lands in the bucket spanning [edges[k], edges[k+1])
    h = hist_add(hist_init(EDGES), np.asarray(EDGES, np.float32))
    assert np.array_equal(np.asarray(h["counts"]),
                          _np_hist(np.asarray(EDGES), EDGES))


def test_hist_add_weighted():
    v = np.array([-5.0, 0.25, 0.25, 3.0], np.float32)
    w = np.array([2, 1, 1, 7], np.int32)
    h = hist_add(hist_init(EDGES), v, weights=w)
    oracle = np.histogram(
        v, bins=np.concatenate([[-np.inf], EDGES, [np.inf]]), weights=w)[0]
    assert np.array_equal(np.asarray(h["counts"]), oracle)


def test_hist_init_rejects_bad_edges():
    with pytest.raises(ValueError):
        hist_init([])
    with pytest.raises(ValueError):
        hist_init([[0.0, 1.0]])


# ---------------------------------------------------------------------------
# reducer identities under lax.scan (the form the fused round uses)
# ---------------------------------------------------------------------------
def test_counter_scan_equals_bulk_add():
    xs = jnp.arange(1, 11, dtype=jnp.int32)

    def step(c, x):
        return counter_add(c, x), None

    scanned, _ = jax.lax.scan(step, counter_init(), xs)
    assert int(scanned) == int(counter_add(counter_init(), xs.sum()))


def test_hist_scan_equals_bulk_add():
    rng = np.random.default_rng(2)
    v = jnp.asarray(rng.normal(0.5, 1.0, size=(16, 4)), jnp.float32)

    def step(h, row):
        return hist_add(h, row), None

    scanned, _ = jax.lax.scan(step, hist_init(EDGES), v)
    bulk = hist_add(hist_init(EDGES), v)
    assert np.array_equal(np.asarray(scanned["counts"]),
                          np.asarray(bulk["counts"]))


# ---------------------------------------------------------------------------
# schema validation
# ---------------------------------------------------------------------------
def _round_rec(**over):
    rec = {"kind": "train_round", "v": 1, "episode": 3, "sla": 0.9,
           "sigma": 0.2, "periods_per_sec": 100.0}
    rec.update(over)
    return rec


def test_validate_accepts_valid_and_extra_fields():
    validate_record(_round_rec())
    validate_record(_round_rec(replay_fill=0.5, fleet="paper6"))
    # tenant sla_rate may be null (zero counted jobs)
    validate_record({"kind": "tenant", "v": 1, "tenant": "resnet",
                     "jobs": 0, "sla_rate": None})


def test_validate_rejects_missing_field():
    bad = _round_rec()
    del bad["sigma"]
    with pytest.raises(SchemaError, match="missing field"):
        validate_record(bad)


def test_validate_rejects_bool_where_number_expected():
    with pytest.raises(SchemaError, match="bool"):
        validate_record(_round_rec(sla=True))


def test_validate_rejects_unknown_kind_and_envelope():
    with pytest.raises(SchemaError, match="unknown record kind"):
        validate_record({"kind": "nope", "v": 1})
    with pytest.raises(SchemaError, match="kind"):
        validate_record({"v": 1})
    with pytest.raises(SchemaError, match="schema version"):
        validate_record({"kind": "note", "msg": "x"})


# ---------------------------------------------------------------------------
# sinks + the Telemetry session
# ---------------------------------------------------------------------------
def test_jsonl_sink_round_trip(tmp_path):
    path = tmp_path / "nested" / "metrics.jsonl"   # parent dir created
    tele = Telemetry([JsonlSink(str(path))], run_id="t1")
    tele.run_header("train", {"episodes": 4})
    tele.emit("train_round", episode=1, sla=0.8, sigma=0.3,
              periods_per_sec=50.0)
    tele.note("hello")
    tele.emit("run_end")
    tele.close()
    recs = [validate_record(json.loads(l))
            for l in path.read_text().splitlines()]
    assert [r["kind"] for r in recs] == \
        ["run_header", "train_round", "note", "run_end"]
    hdr = recs[0]
    assert hdr["run_id"] == "t1" and hdr["config"] == {"episodes": 4}
    assert hdr["git_sha"] and hdr["created_at"].endswith("Z")


def test_invalid_emit_never_reaches_sinks():
    sink = ListSink()
    tele = Telemetry([sink])
    with pytest.raises(SchemaError):
        tele.emit("train_round", episode=1)        # missing fields
    assert sink.records == []


def test_console_sink_renders_known_kinds_and_skips_spans():
    lines = []
    tele = Telemetry([ConsoleSink(log_fn=lines.append)])
    tele.emit("train_round", episode=7, sla=0.875, sigma=0.25,
              periods_per_sec=10.0)
    with tele.span("collect"):
        pass
    tele.note("plain context")
    assert any("sla=0.875" in l for l in lines)
    assert "plain context" in lines
    assert not any("collect" in l for l in lines)  # spans stay JSONL-only


def test_make_telemetry_stacks(tmp_path):
    lines = []
    tele = make_telemetry(log_fn=lines.append,
                          jsonl_path=str(tmp_path / "m.jsonl"))
    tele.emit("baseline", name="fcfs", sla_rate=0.5)
    tele.close()
    assert lines and "fcfs" in lines[0]
    rec = json.loads((tmp_path / "m.jsonl").read_text())
    assert rec["kind"] == "baseline"
    # closing twice is fine; emitting after close is not
    tele.close()
    with pytest.raises(ValueError, match="closed"):
        tele.emit("baseline", name="fcfs", sla_rate=0.5)


# ---------------------------------------------------------------------------
# fused round: telemetry-on == telemetry-off, bit for bit
# ---------------------------------------------------------------------------
def _run_rounds(env, dcfg, telemetry: bool):
    state = D.init_ddpg(jax.random.PRNGKey(1), dcfg)
    buf = replay_init(64, env.seq_len, env.feat_dim, env.act_dim)
    fn = make_train_rounds(env, dcfg, telemetry=telemetry, **TRAIN_KW)
    keys = round_keys(7, 0, 3)
    flags = jnp.array([False, True, True])
    state, buf, sigma, mets = fn(state, buf, keys, jnp.float32(0.4), flags)
    return state, sigma, jax.tree.map(np.asarray, mets)


def test_fused_round_bit_parity_telemetry_on_off(env, dcfg):
    """The load-bearing contract: the telemetry block only READS values
    the round already computes — params, sigma, and every shared metric
    must be bitwise identical with telemetry on vs off."""
    st_off, sg_off, m_off = _run_rounds(env, dcfg, telemetry=False)
    st_on, sg_on, m_on = _run_rounds(env, dcfg, telemetry=True)
    for a, b in zip(jax.tree.leaves(st_off.actor),
                    jax.tree.leaves(st_on.actor)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    for a, b in zip(jax.tree.leaves(st_off.critic),
                    jax.tree.leaves(st_on.critic)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert np.asarray(sg_off).tobytes() == np.asarray(sg_on).tobytes()
    for k in m_off:
        assert m_off[k].tobytes() == m_on[k].tobytes(), k
    # the tele leaves exist ONLY when asked, and ride the same metrics
    # dict the chunk already transfers (zero added host syncs)
    assert not any(k in m_off for k in ROUND_TELE_KEYS)
    assert all(k in m_on for k in ROUND_TELE_KEYS)


def test_round_telemetry_leaves_are_consistent(env, dcfg):
    state = D.init_ddpg(jax.random.PRNGKey(1), dcfg)
    buf = replay_init(64, env.seq_len, env.feat_dim, env.act_dim)
    fn = make_train_round(env, dcfg, telemetry=True, **TRAIN_KW)
    state, buf, sigma, mets = fn(state, buf, jax.random.PRNGKey(0),
                                 jnp.float32(0.4), True)
    n_eps = TRAIN_KW["batch_episodes"]
    assert int(np.asarray(mets["tele_sla_hist"]).sum()) == n_eps
    # reward histogram folds every (episode, period) reward
    assert int(np.asarray(mets["tele_reward_hist"]).sum()) == \
        n_eps * ECFG.periods
    assert float(mets["tele_replay_fill"]) == pytest.approx(
        int(buf["size"]) / buf["r"].shape[0])
    assert int(mets["tele_committed"]) >= 0


def test_round_telemetry_pure_fn():
    sla = jnp.array([0.5, 1.0])
    rew = jnp.ones((2, 4))
    tele = round_telemetry(sla, rew, jnp.array([3, 4]), 10, 40)
    assert set(tele) == set(ROUND_TELE_KEYS)
    assert int(tele["tele_committed"]) == 7
    assert float(tele["tele_replay_fill"]) == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# batched serving: telemetry session changes no outputs, adds no
# dispatches, and emits the window / tenant / summary stream
# ---------------------------------------------------------------------------
SCFG = EnvConfig(periods=10, max_rq=32, max_jobs=12)


def _counting_svc():
    svc = MultiTenantService(build_registry("light"), policy="fcfs",
                             env_cfg=SCFG)
    calls = dict(tick=0, flush=0)
    orig = svc._tick_fns

    def counting(streams, device_telemetry=False):
        tick, flush, queues = orig(streams, device_telemetry)

        def tick2(*a):
            calls["tick"] += 1
            return tick(*a)

        def flush2(*a):
            calls["flush"] += 1
            return flush(*a)

        return tick2, flush2, queues

    svc._tick_fns = counting
    return svc, calls


def test_serving_telemetry_parity_and_zero_added_dispatches(env):
    svc, calls = _counting_svc()
    trace, _ = svc.env.new_episode(np.random.default_rng(0))
    reqs = trace_to_requests(svc.env, trace)

    off = svc.serve_stream(reqs, tick_k=SCFG.max_jobs, seed=0)
    off_calls = dict(calls)
    calls.update(tick=0, flush=0)

    sink = ListSink()
    on = svc.serve_stream(reqs, tick_k=SCFG.max_jobs, seed=0,
                          telemetry=Telemetry([sink]), window=4)

    # bit-neutral: identical per-stream metrics and aggregate
    assert off["metrics"] == on["metrics"]
    assert off["aggregate"] == on["aggregate"]
    assert off["completions"] == on["completions"]
    # zero added device dispatches: same tick/flush counts either way
    assert calls == off_calls
    assert calls["tick"] == SCFG.periods and calls["flush"] == 1

    # the device-accumulated block appears only with telemetry, read
    # back at the flush the path already pays for
    assert "device_tele" not in off["stats"]
    dt = on["stats"]["device_tele"]
    assert dt["ticks"] == SCFG.periods
    # depth histogram folded one depth sample per (tick, stream)
    assert sum(dt["depth_hist"]) == SCFG.periods * on["stats"]["streams"]
    assert dt["committed"] >= 0

    kinds = [r["kind"] for r in sink.records]
    assert kinds.count("serve_window") >= 2      # 10 ticks / window=4
    assert kinds[-1] == "serve_summary"
    assert "tenant" in kinds
    wins = [r for r in sink.records if r["kind"] == "serve_window"]
    assert wins[0]["tick_first"] == 0 and wins[-1]["tick_last"] == \
        SCFG.periods - 1
    assert sum(w["admitted"] for w in wins) == on["stats"]["admitted"]
    summ = sink.records[-1]
    assert summ["sla_rate"] == pytest.approx(on["aggregate"]["sla_rate"])
    for r in sink.records:
        validate_record(r)


def test_queue_tele_block_survives_admit_retire(env):
    """The structural gate: the 'tele' subdict threads through
    queue_admit / queue_retire untouched (same {**qs, ...} spread the
    tick relies on)."""
    qs = queue_init(env, telemetry=True)
    assert "tele" in qs
    adm = dict(model=jnp.zeros((2,), jnp.int32),
               arrival=jnp.zeros((2,), jnp.float32),
               deadline=jnp.full((2,), 1e4, jnp.float32),
               q=jnp.ones((2,), jnp.float32),
               rid=jnp.arange(2, dtype=jnp.int32),
               valid=jnp.ones((2,), bool))
    qs2, n_adm = queue_admit(env, qs, adm)
    assert "tele" in qs2 and int(n_adm) == 2
    qs3, _ = queue_retire(env, qs2)
    assert "tele" in qs3
    assert "tele" not in queue_init(env)          # off by default


def test_null_telemetry_validates_but_writes_nothing(capsys):
    tele = null_telemetry()
    tele.run_header("train", {})
    tele.emit("run_end")
    tele.close()
    assert capsys.readouterr().out == ""
    with pytest.raises(SchemaError):
        null_telemetry().emit("train_round", episode=0)
