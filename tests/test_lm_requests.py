"""Whole LM requests on the serving path: tables with a decode pass, jobs
that re-enter the queue once per output token under two limits, and the
one-pass (CNN) program left as it was.

``data/cnn_tick_parent.json.gz`` holds what the program traced before
re-entering jobs existed, recorded with the same shapes as below: the
light-set tick's StableHLO (telemetry off and on; recorded again when
the tick gained its packed host record ``out["host"]``, which adds the
pack and that output and nothing else; and again when slot packing
gave up its sort, search loop and gathers for bit-identical comparisons,
selects and a 0/1 contraction) and one episode of
``SchedulingEnv.episode`` (every leaf's bytes). A change that means to
alter the one-pass program records it again.
"""
import gzip
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import TENANT_ARCHS
from repro.core import policy as P
from repro.core.serve import (host_layout, host_unpack, make_serving_tick,
                              queue_init_batch, specialist_act)
from repro.serving import (LoadGenConfig, MultiTenantService, Request,
                           pack_admissions, queue_admit, queue_init,
                           resolve_request)
from repro.serving.loadgen import request_streams, requests_to_trace
from repro.sim.engine import INF
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.workloads import build_llm_registry, build_registry
from repro.workloads.llm_zoo import llm_layer_specs, llm_request_specs

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "cnn_tick_parent.json.gz")
CNN_CFG = EnvConfig(periods=6, max_rq=24, max_jobs=8)
LM_CFG = EnvConfig(periods=60, max_rq=32, max_jobs=8)
LM_SETS = ["lm_dsv2lite", "lm_light", "lm_heavy", "lm_mixed", "lm_all"]


@pytest.fixture(scope="module")
def golden():
    with gzip.open(GOLDEN, "rt") as fh:
        return json.load(fh)


def _cnn():
    env = SchedulingEnv(build_registry("light"), CNN_CFG)
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    return env, pcfg, P.init_actor(jax.random.PRNGKey(0), pcfg)


def _tick_text(env, pcfg, params, telemetry: bool) -> str:
    S, K = 2, 3
    tick = make_serving_tick(env, kind="specialist", pcfg=pcfg, streams=S)
    adm = dict(model=np.zeros((S, K), np.int32),
               arrival=np.zeros((S, K), np.float32),
               deadline=np.zeros((S, K), np.float32),
               q=np.ones((S, K), np.float32),
               rid=np.zeros((S, K), np.int32),
               valid=np.zeros((S, K), bool))
    return tick.lower(params, queue_init_batch(env, S, telemetry=telemetry),
                      adm, jax.random.PRNGKey(0)).as_text()


# ---------------------------------------------------------------------------
# the one-pass program is the parent's
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("telemetry", [False, True])
def test_cnn_tick_traces_to_the_one_pass_program(golden, telemetry):
    env, pcfg, params = _cnn()
    assert not env.reenters
    want = golden["tick_telemetry" if telemetry else "tick"]
    assert _tick_text(env, pcfg, params, telemetry) == want


def test_cnn_episode_is_bit_identical(golden):
    env, pcfg, params = _cnn()
    act = specialist_act(pcfg)
    trace, state = env.new_episode(np.random.default_rng(3))
    final, _, infos, metrics = jax.jit(lambda s, t: env.episode(
        s, t, lambda f, m, sl, st, k, a: act(params, f, m, sl, st, k),
        collect=False))(state, trace)
    got = {"final": final, "infos": infos, "metrics": metrics}
    for group, leaves in golden["episode"].items():
        assert set(got[group]) == set(leaves), group
        for k, rec in leaves.items():
            x = np.asarray(got[group][k])
            assert (str(x.dtype), list(x.shape)) == (rec["dtype"],
                                                     rec["shape"]), k
            assert x.tobytes().hex() == rec["hex"], (group, k)


# ---------------------------------------------------------------------------
# tables: a whole request is the prefill pass, then one decode pass
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", sorted(TENANT_ARCHS))
def test_request_table_is_prefill_then_decode_pass(arch):
    cfg = TENANT_ARCHS[arch]
    rows, ds = llm_request_specs(cfg, prompt=64, ctx=128)
    pre = llm_layer_specs(cfg, phase="prefill", seq=64, ctx=64)
    dec = llm_layer_specs(cfg, phase="decode", ctx=128)
    if cfg.family == "encdec":          # the encoder runs once, in prefill
        dec = dec[:1] + dec[1 + cfg.enc_layers:]
    assert ds == len(pre) and rows == pre + dec
    assert all(r.gemm_m == 1 for r in rows[ds + 1:])


def test_registry_states_which_tenants_reenter():
    assert not build_registry("light").reenters
    reg = build_llm_registry("lm_dsv2lite")
    assert reg.reenters and reg.model_names == ["dsv2lite-p512",
                                                "dsv2lite-p2048"]
    d = reg.dense()
    np.testing.assert_array_equal(d["decode_start"], [29, 29])
    np.testing.assert_array_equal(d["n_layers"], [58, 58])
    best = d["lat"].min(axis=2)
    np.testing.assert_allclose(d["min_first"], best[:, :29].sum(axis=1))
    np.testing.assert_allclose(d["min_pass"], best[:, 29:].sum(axis=1))
    np.testing.assert_allclose(d["min_lat"], d["min_first"] + d["min_pass"])
    # the longer prompt's prefill costs more; its decode pass reads a
    # longer cache
    assert d["min_first"][1] > 4 * d["min_first"][0]
    assert d["min_pass"][1] > d["min_pass"][0]


def test_decode_pass_takes_the_shared_bus_prefill_leaves_it():
    """Prefill layers are bound by compute, decode layers by the bus."""
    reg = build_llm_registry("lm_dsv2lite")
    d = reg.dense()
    cap = reg.mas.dram_gbps
    for m in range(2):
        bw = d["bw"][m]
        assert np.all(bw[1:28] < 0.5 * cap)           # prefill layers
        assert np.allclose(bw[30:57], cap)             # decode layers


def test_moe_ffn_uses_expert_width_and_shared_experts():
    """The MoE branch streams the experts a pass touches at the expert
    width: the router, every shared expert, and at most S x top_k
    routed ones; OLMoE (no shared experts, d_ff is its expert width)
    keeps its expert MACs."""
    dsv2 = TENANT_ARCHS["deepseek-v2-lite"]
    d, w = dsv2.d_model, dsv2.moe_d_ff
    dec = llm_layer_specs(dsv2, phase="decode", ctx=576)
    pre = llm_layer_specs(dsv2, phase="prefill", seq=512, ctx=512)
    moe_w = 2 * (3 * d * w * (2 + 6) + d * 64)
    attn_w = 2 * (d * 16 * 192 + d * 576 + 512 * 16 * 256 + 16 * 128 * d)
    assert dec[1].w_bytes == attn_w + 2 * 3 * d * dsv2.d_ff  # layer 0 dense
    assert dec[2].w_bytes == attn_w + moe_w
    assert pre[2].w_bytes == attn_w + 2 * (3 * d * w * (2 + 64) + d * 64)
    olmoe = TENANT_ARCHS["olmoe-1b-7b"]
    o = llm_layer_specs(olmoe, phase="decode", ctx=128)[1]
    dense = llm_layer_specs(olmoe.__class__(**{
        **olmoe.__dict__, "n_experts": 0, "top_k": 0,
        "d_ff": olmoe.d_ff * olmoe.top_k}), phase="decode", ctx=128)[1]
    assert o.macs == dense.macs + olmoe.d_model * olmoe.n_experts


# ---------------------------------------------------------------------------
# requests: n_out and two limits, validated
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("kw", [
    dict(n_out=0, tpot_us=10.0),
    dict(n_out=-3, tpot_us=10.0),
    dict(n_out=4, tpot_us=0.0),
    dict(n_out=4, tpot_us=-1.0),
    dict(n_out=4, tpot_us=None),
    dict(n_out=1, tpot_us=0.0),
    dict(n_out=4, tpot_us=10.0, deadline_us=5.0),    # TTFT limit 0
])
def test_resolve_request_rejects_bad_passes_and_limits(kw):
    kw = {"deadline_us": 100.0, **kw}
    with pytest.raises(ValueError):
        resolve_request(Request(rid=0, tenant="dsv2lite-p512",
                                arrival_us=5.0, **kw),
                        ["dsv2lite-p512"])


def test_resolve_request_rows_and_one_pass_registries():
    names = ["dsv2lite-p512"]
    row = resolve_request(Request(rid=0, tenant=names[0], arrival_us=1.0,
                                  deadline_us=11.0, n_out=3, tpot_us=2.0),
                          names)
    assert tuple(row) == (0, 1.0, 11.0, 10.0, 3, 2.0)
    one = Request(rid=1, tenant=names[0], arrival_us=0.0, deadline_us=4.0)
    assert tuple(resolve_request(one, names))[4:] == (1, 0.0)
    # a registry whose tenants run one pass refuses several tokens, on
    # the serving path and in a replayed trace
    cnn, _, _ = _cnn()
    many = Request(rid=2, tenant="squeezenet", arrival_us=0.0,
                   deadline_us=4.0, n_out=2, tpot_us=1.0)
    with pytest.raises(ValueError, match="re-enter"):
        requests_to_trace(cnn, [many])
    svc = MultiTenantService(build_registry("light"), policy="fcfs",
                             env_cfg=CNN_CFG)
    with pytest.raises(ValueError, match="re-enter"):
        svc.serve_stream([[many]], ticks=2)
    _lm_env().check_passes(np.array([1, 2, 256]))


# ---------------------------------------------------------------------------
# jobs that re-enter
# ---------------------------------------------------------------------------
def _lm_env():
    return SchedulingEnv(build_llm_registry("lm_dsv2lite"), LM_CFG)


def _admit_one(env, n_out=3, tpot=1e6, ttft=1e7):
    qs = queue_init(env)
    rows = [(7, 0, 0.0, ttft, ttft, n_out, tpot)]
    qs, _ = queue_admit(env, qs, pack_admissions(rows, 2))
    return qs


def test_slots_offer_only_the_current_pass():
    env = _lm_env()
    qs = _admit_one(env)
    st, tr = qs["state"], qs["trace"]
    assert int(st["passes_left"][0]) == 2 and int(tr["ds"][0]) == 29
    s = env.build_slots(st, tr, cutoff=0.0)
    assert int(jnp.sum(s["valid"])) == 29               # the prefill rows
    np.testing.assert_array_equal(np.asarray(s["layer"][:29]),
                                  np.arange(29))
    # mid decode pass: the rest of that pass only, never the next one
    st = {**st, "nls": st["nls"].at[0].set(40),
          "t_first": st["t_first"].at[0].set(5.0)}
    s = env.build_slots(st, tr, cutoff=0.0)
    assert int(jnp.sum(s["valid"])) == 58 - 40
    assert int(s["layer"][57 - 40]) == 57


def test_decode_pass_is_ordered_by_its_token_deadline():
    env = _lm_env()
    qs = queue_init(env)
    # job 0 asks for 64 tokens and has its first at t = 0 (final deadline
    # 63 ms away); job 1 is a fresh prefill due in 5 ms
    rows = [(0, 0, 0.0, 1e4, 1e4, 64, 1e3), (1, 0, 0.0, 5e3, 5e3, 4, 1e3)]
    qs, _ = queue_admit(env, qs, pack_admissions(rows, 2))
    st, tr = qs["state"], qs["trace"]
    ds = int(tr["ds"][0])
    st = {**st, "nls": st["nls"].at[0].set(ds),
          "t_first": st["t_first"].at[0].set(0.0),
          "passes_left": st["passes_left"].at[0].set(62),
          "dl": st["dl"].at[0].set(63e3)}
    s = env.build_slots(st, tr, cutoff=0.0)
    # its second token is due tpot after the first: ahead of the prefill
    assert int(s["job"][0]) == 0 and float(s["deadline"][0]) == 1e3
    assert int(s["job"][29]) == 1 and float(s["deadline"][29]) == 5e3
    # its 60th token is due at 59 ms: behind it
    st = {**st, "passes_left": st["passes_left"].at[0].set(4)}
    s = env.build_slots(st, tr, cutoff=0.0)
    assert int(s["job"][0]) == 1 and float(s["deadline"][29]) == 59e3
    # drops still count from the final deadline
    assert not bool(env.mark_drops(st, tr, 6e4)["missed"][0])


def _first_fit(env):
    """Every valid slot on SA 0 in slot order: a pass runs as a chain."""
    def act(feats, mask, slots, st):
        R = slots["valid"].shape[0]
        a = jnp.zeros((R, env.act_dim))
        return a, -jnp.arange(R, dtype=jnp.float32), jnp.zeros((R,), jnp.int32)
    return act


def test_job_reenters_per_token_and_hits_both_limits():
    env = _lm_env()
    d = env.registry.dense()
    tpot = 3.0 * float(d["min_pass"][0])
    qs = _admit_one(env, n_out=3, tpot=tpot,
                    ttft=3.0 * float(d["min_first"][0]))
    st, tr = qs["state"], qs["trace"]
    period = jax.jit(lambda st, tr: env.period(st, tr, _first_fit(env)))
    ends, firsts = [], 0
    for _ in range(400):
        new, _, info = period(st, tr)
        if int(info["passes"]) or int(info["first_tokens"]):
            ends.append((int(new["nls"][0]), int(new["passes_left"][0])))
        firsts += int(info["first_tokens"])
        st = new
        if bool(st["done"][0]):
            break
    # prefill end -> back to decode_start with 1 pass left, then two
    # decode passes, the last one done
    assert ends == [(29, 1), (29, 0), (58, 0)] and firsts == 1
    assert bool(st["done"][0]) and bool(st["hit"][0])
    t_first, fin = float(st["t_first"][0]), float(st["fjob"][0])
    # not sooner than in isolation, to the float32 clock's rounding
    assert t_first >= float(d["min_first"][0]) * (1 - 1e-5)
    assert fin >= (t_first + 2 * float(d["min_pass"][0])) * (1 - 1e-5)
    np.testing.assert_allclose(float(st["dl"][0]), t_first + 2 * tpot,
                               rtol=1e-6)


def test_drops_use_the_current_limit():
    env = _lm_env()
    qs = _admit_one(env, n_out=3, tpot=1.0, ttft=100.0)
    st, tr = qs["state"], qs["trace"]
    assert bool(env.mark_drops(st, tr, 101.0)["missed"][0])
    # after the first token the final deadline counts, not the TTFT one
    st = {**st, "t_first": st["t_first"].at[0].set(50.0),
          "dl": st["dl"].at[0].set(500.0)}
    assert not bool(env.mark_drops(st, tr, 101.0)["missed"][0])
    assert bool(env.mark_drops(st, tr, 501.0)["missed"][0])


# lm_all's seed-5 streams hold no request done before tick ~2,600 (its
# llama3-405b prefill alone takes ~7,900 ticks): a longer session
@pytest.mark.parametrize("workload,ticks", [
    *((w, 700) for w in LM_SETS[:-1]), ("lm_all", 3000)])
def test_lm_service_counts_both_limits_and_passes(workload, ticks):
    env_cfg = EnvConfig(periods=60, max_rq=32, max_jobs=8)
    svc = MultiTenantService(build_llm_registry(workload),
                             policy="fcfs", env_cfg=env_cfg)
    lg = LoadGenConfig(scenario="burst", rate_scale=2.0, n_requests=3,
                       out_median=8.0)
    reqs = request_streams(svc.env, lg, 2, seed=5)
    assert all(r.n_out >= 8 and r.tpot_us > 0 for s in reqs for r in s)
    from repro.telemetry import ListSink, Telemetry
    res = svc.serve_stream(reqs, ticks=ticks,
                           telemetry=Telemetry([ListSink()]))
    done = [c for s in res["completions"] for c in s if not c["missed"]]
    assert done, "no request finished inside the session"
    n_out = {(s, r.rid): r.n_out for s, st in enumerate(reqs) for r in st}
    for c in done:
        assert c["passes_left"] == 0 and c["t_first_us"] < c["finish_us"]
    tele = res["stats"]["device_tele"]
    # every first token and every decode pass of the done requests was
    # counted on the device
    want = sum(n_out[(s, c["rid"])] - 1 for s, st in
               enumerate(res["completions"]) for c in st if not c["missed"])
    assert tele["passes"] >= want and tele["first_tokens"] >= len(done)
    agg = res["aggregate"]
    assert 0.0 <= agg["sla_rate"] <= min(agg["ttft_rate"], agg["tpot_rate"])
    for m in res["metrics"]:
        assert m["hits"] <= min(m["ttft_hits"], m["tpot_hits"])


# ---------------------------------------------------------------------------
# the tick's packed host record carries the LM fields
# ---------------------------------------------------------------------------
def test_lm_host_record_unpacks_to_the_ticks_own_fields():
    env = _lm_env()
    S = 2
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    params = P.init_actor(jax.random.PRNGKey(0), pcfg)
    tick = make_serving_tick(env, kind="specialist", pcfg=pcfg, streams=S)
    layout = host_layout(env)
    # stream 0: a request dropped at its 1 ms TTFT deadline and one with
    # room; stream 1: one request of the other class
    rows = [[(0, 0, 0.0, 1e3, 1e3, 4, 1e3), (1, 0, 0.0, 1e7, 1e7, 3, 1e6)],
            [(2, 1, 0.0, 1e7, 1e7, 5, 1e6)]]
    packs = [pack_admissions(r, 2) for r in rows]
    adm0 = {k: np.stack([p[k] for p in packs]) for k in packs[0]}
    queues = queue_init_batch(env, S)
    seen = dict(completed=0, inf=0, finite=0, passes=0)
    for i in range(6):
        adm = adm0 if i == 0 else {**adm0,
                                   "valid": np.zeros_like(adm0["valid"])}
        if i == 1:
            # request 1 has its first token: its t_first is finite
            st = queues["state"]
            queues = {**queues, "state": {
                **st, "t_first": st["t_first"].at[0, 1].set(250.0)}}
        queues, out = tick(params, queues, adm, jax.random.PRNGKey(i))
        got = host_unpack(layout, np.asarray(out["host"]))
        assert set(got) == {"n_admitted", "depth", "completed", "hit",
                            "missed", "rid", "finish_us", "t_first",
                            "passes_left"}
        for k, v in got.items():
            want = np.asarray(out[k])
            assert (v.dtype, v.shape) == (want.dtype, want.shape), k
            assert v.tobytes() == want.tobytes(), (i, k)
        seen["completed"] += int(got["completed"].sum())
        seen["inf"] += int((got["t_first"] == INF).sum()
                           + (got["finish_us"] == INF).sum())
        seen["finite"] += int((got["t_first"] < INF).sum())
        seen["passes"] += int(got["passes_left"].sum())
    assert all(seen.values()), seen


@pytest.mark.parametrize("workload,ticks", [(w, 500) for w in LM_SETS])
def test_lm_serve_stream_equals_the_field_by_field_loop(serve_fieldwise,
                                                        workload, ticks):
    svc = MultiTenantService(build_llm_registry(workload),
                             policy="fcfs", env_cfg=LM_CFG)
    lg = LoadGenConfig(scenario="burst", rate_scale=2.0, n_requests=4,
                       out_median=8.0)
    reqs = request_streams(svc.env, lg, 3, seed=7)
    res = svc.serve_stream(reqs, tick_k=2, ticks=ticks, seed=1)
    ref = serve_fieldwise(svc, reqs, tick_k=2, ticks=ticks, seed=1)
    assert res["stats"]["readbacks"] == ticks + 1
    done = [c for st in res["completions"] for c in st]
    assert any(c["t_first_us"] < INF for c in done), done
    stats = {k: v for k, v in res["stats"].items()
             if k not in ("tick_wall_us", "readbacks")}
    assert stats == ref["stats"]
    for k in ("completions", "metrics", "aggregate"):
        assert res[k] == ref[k], k
