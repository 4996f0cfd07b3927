"""A serving cell: many streams through ``MultiTenantService.serve_stream``.

Set-up builds the service for the configuration, draws the actor's
weights on the device from the seed, draws a pool of sessions (one
request stream per fleet) from the seed, and serves one whole session
of the same length to compile and warm every program the window uses.

The window serves whole sessions back to back, closed loop in wall time
(each tick starts when the last decision is back), the load offered in
simulated time by the traffic file's ``rate_scale``:

- ``tick_p95_us``: the 95th percentile of every tick of the window, as
  ``serve_stream`` times it (dispatch to the decision on the host);
- ``serve_periods_per_s``: streams x ticks over the window's wall time,
  request resolution, staging and flushes included.

After the window, one of its sessions, drawn from the seed, is served
again through the same compiled tick with a host copy of the queues
taken before and after a sample of ticks; the replay has to return
what the window returned. Each sampled tick is run once more through
the tick built the same way with the actor's outputs returned beside
the queues (it has to leave the same queues), and the reference
(``serve_check``) then judges those outputs and the queues they led to
on a sample of the streams.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

import flops
import loadgen
import peaks
import reduce_trace as tr
import reference as ref
import serve_check as chk

POOL = 4              # sessions drawn in set-up; the window cycles them
CHECK_TICKS = 24      # ticks of the replayed session that are compared
CHECK_STREAMS = 16    # streams compared at each of those ticks
TRACE_TICKS = 100     # ticks of the traced session (trace runs only)

# limits of the numbers compared (PERF.md gives the readings they were
# set from)
LIMITS = {"replay_mismatches": 0, "accounting_errors": 0,
          "tick_mismatches": 0, "actor_gap": 1.5e-2}


def build_service(cfg: dict, traffic: dict):
    """The program's service for the configuration, after checking that
    the program's cost tables are the configuration's."""
    from repro.serving import MultiTenantService
    from repro.sim.arrivals import ArrivalConfig
    from repro.sim.env import EnvConfig
    from repro.workloads.cnn_zoo import build_registry
    reg = build_registry(cfg["workload"], mas=cfg["fleet"])
    check_tables(cfg, reg)
    ecfg = EnvConfig(t_s_us=cfg["t_s_us"], periods=60, max_rq=cfg["max_rq"],
                     max_jobs=cfg["max_jobs"])
    arr = ArrivalConfig(max_jobs=cfg["max_jobs"], load=cfg["load"],
                        eff_parallelism=cfg["eff_parallelism"],
                        qos_factor=cfg["qos_factor"],
                        qos_level=cfg["qos_level"],
                        horizon_us=ecfg.horizon_us, slack_us=cfg["slack_us"],
                        scenario=traffic["scenario"])
    svc = MultiTenantService(reg, policy=cfg["policy"], hidden=cfg["hidden"],
                             env_cfg=ecfg, arrivals=arr)
    if svc.policy_kind != cfg["policy_kind"]:
        raise RuntimeError(f"service built a {svc.policy_kind} policy, the "
                           f"configuration states {cfg['policy_kind']}")
    return svc


def check_tables(cfg: dict, reg) -> None:
    d = reg.dense()
    t = cfg["tables"]
    pairs = [("lat", "lat_us"), ("bw", "bw_gbps"), ("en", "en_uj"),
             ("min_lat", "min_lat_us")]
    bad = [k for k, c in pairs if not np.array_equal(
        np.asarray(d[k], np.float32), np.asarray(t[c], np.float32))]
    if list(reg.model_names) != cfg["tenants"]:
        bad.append("tenants")
    if list(np.asarray(d["n_layers"])) != t["n_layers"]:
        bad.append("n_layers")
    if float(reg.mas.dram_gbps) != cfg["bandwidth_gbps"]:
        bad.append("bandwidth_gbps")
    if bad:
        raise RuntimeError(f"the program's {cfg['fleet']}/{cfg['workload']} "
                           f"tables differ from the configuration in {bad}")


def init_actor(seed: int, feat_dim: int, act_dim: int, hidden: int,
               base_key: int = 0):
    """The actor's weights, drawn on the device in one jitted call.

    One untrained set of weights is drawn from the fixed key
    ``base_key`` and the seed permutes its hidden units: the same
    function (to rounding) in another order, so that every seed asks the
    scheduler for the same work (the actor's decisions set how deep the
    queues run) while the arrays, and so what the check compares, differ
    from seed to seed."""
    import jax
    import jax.numpy as jnp
    H = hidden

    def uni(k, shape):
        s = (6.0 / (shape[0] + shape[1])) ** 0.5
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    def init(key):
        k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(base_key), 4)
        perm = jax.random.permutation(key, H)
        gates = (jnp.arange(4)[:, None] * H + perm[None, :]).reshape(-1)
        b = jnp.zeros((4 * H,), jnp.float32).at[H:2 * H].set(1.0)
        wh = uni(k2, (H, 4 * H))
        return {"lstm": {"wx": uni(k1, (feat_dim, 4 * H))[:, gates],
                         "wh": wh[perm][:, gates], "b": b[gates]},
                "fc1": {"w": uni(k3, (H, H // 2))[perm],
                        "b": jnp.zeros((H // 2,), jnp.float32)},
                "fc2": {"w": uni(k4, (H // 2, act_dim)),
                        "b": jnp.zeros((act_dim,), jnp.float32)}}

    key = jax.random.PRNGKey(np.random.default_rng([seed, 1]).integers(2**31))
    return jax.block_until_ready(jax.jit(init)(key))


def to_requests(cfg: dict, cols: list[dict]):
    """Loadgen columns -> the program's ``Request`` lists."""
    from repro.serving.request import Request
    names = cfg["tenants"]
    return [[Request(rid=i, tenant=names[int(m)], arrival_us=float(a),
                     deadline_us=float(d), q_us=float(q))
             for i, (m, a, d, q) in enumerate(zip(c["model"], c["arrival"],
                                                  c["deadline"], c["q"]))]
            for c in cols]


@contextlib.contextmanager
def record_ticks(wanted: set, store: dict):
    """Serve through the program's own compiled tick, keeping a host copy
    of the queues before and after each tick whose index is ``wanted``."""
    import jax
    import repro.core.serve as cs
    orig = cs.make_serving_tick

    def make(*a, **k):
        tick = orig(*a, **k)
        count = [0]

        def rec(params, queues, adm, key):
            i = count[0]
            count[0] += 1
            if i not in wanted:
                return tick(params, queues, adm, key)
            pre = jax.device_get(queues)
            queues, out = tick(params, queues, adm, key)
            store[i] = (pre, {k2: np.asarray(v) for k2, v in adm.items()},
                        np.asarray(key), jax.device_get(queues),
                        jax.device_get(out))
            return queues, out
        return rec

    cs.make_serving_tick = make
    try:
        yield
    finally:
        cs.make_serving_tick = orig


@contextlib.contextmanager
def actions_out(env):
    """Ticks built inside are the program's tick as ``make_serving_tick``
    builds it, with the actor's outputs of the period (the transition's
    ``a``, which the tick otherwise drops) returned in its record as
    ``a``; they are kept apart from the ticks the window uses."""
    import repro.core.serve as cs
    period, retire, cache = env.period, cs.queue_retire, cs._runner_cache
    own: dict = {}

    def period_a(state, trace, act_fn, **kw):
        state, trans, info = period(state, trace, act_fn, **kw)
        return {**state, "_a": trans["a"]}, trans, info

    def retire_a(env_, qs):
        state = dict(qs["state"])
        a = state.pop("_a")
        qs, out = retire(env_, {**qs, "state": state})
        return qs, {**out, "a": a}

    env.period = period_a
    cs.queue_retire, cs._runner_cache = retire_a, lambda e: own
    try:
        yield
    finally:
        del env.period
        cs.queue_retire, cs._runner_cache = retire, cache


def differing_fields(a, b) -> int:
    """Leaves of two pytrees of host arrays that are not bit-equal."""
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return abs(len(la) - len(lb)) + sum(
        x.shape != y.shape or x.dtype != y.dtype or x.tobytes() != y.tobytes()
        for x, y in zip(map(np.asarray, la), map(np.asarray, lb)))


def replay_mismatches(a: dict, b: dict) -> int:
    """Fields in which two results of ``serve_stream`` differ."""
    n = 0
    for ma, mb in zip(a["metrics"], b["metrics"]):
        n += sum(ma[k] != mb[k] for k in ma)
    for ca, cb in zip(a["completions"], b["completions"]):
        n += int(ca != cb)
    n += sum(a["aggregate"][k] != b["aggregate"][k] for k in a["aggregate"])
    return n + abs(len(a["metrics"]) - len(b["metrics"]))


def accounting_errors(cfg: dict, cols: list[dict], res: dict) -> int:
    """Requests of a session whose counting breaks a guarantee: a rid
    served twice or unknown, a hit that finished after its deadline or a
    miss that finished in time, a request that finished sooner than its
    model's minimum isolated latency, and per-stream and per-tenant
    counts that do not add up."""
    min_lat = np.asarray(cfg["tables"]["min_lat_us"], np.float64)
    names = cfg["tenants"]
    bad = 0
    for c, comp, m in zip(cols, res["completions"], res["metrics"]):
        rids = [x["rid"] for x in comp]
        bad += len(rids) - len(set(rids))
        hits = 0
        per_tenant = np.zeros(len(names), np.int64)
        for x in comp:
            r = x["rid"]
            if not 0 <= r < len(c["arrival"]):
                bad += 1
                continue
            dl = float(np.float32(c["deadline"][r]))
            arr = float(np.float32(c["arrival"][r]))
            model = int(c["model"][r])
            per_tenant[model] += 1
            hits += x["hit"]
            if x["hit"] and (x["missed"] or x["finish_us"] > dl):
                bad += 1
            if not x["missed"] and not x["hit"] and x["finish_us"] <= dl:
                bad += 1
            if (not x["missed"] and x["finish_us"]
                    < arr + min_lat[model] * (1 - 1e-5) - 1e-2):
                bad += 1
        bad += int(m["counted"] != len(comp)) + int(m["hits"] != hits)
        bad += sum(int(m["per_tenant"][n]["jobs"] != per_tenant[i])
                   for i, n in enumerate(names))
    return bad


class LayerContext:
    """What a per-layer reader (``layers/<metric>.py``) reads from a
    traced window of a serving cell."""

    def __init__(self, evs: list[dict], results: list, cfg: dict,
                 streams: int, device_kind: str, hlo_names: dict):
        win = [e for e in evs if e["name"] == "chipbench.window"]
        t0 = win[0]["start_ns"]
        t1 = t0 + win[0]["dur_ns"]
        self.devs = [e for e in tr.device_events(evs)
                     if t0 <= e["start_ns"] <= t1]
        host = [e for e in evs if e["plane"].startswith("/host:")
                and e["dur_ns"] > 0 and e is not win[0]]
        self.window_s = (t1 - t0) / 1e9
        self.busy_s = tr.busy_ns(self.devs) / 1e9
        self.ticks = sum(r["stats"]["ticks"] for r in results)
        self.stream_ticks = streams * self.ticks
        self.flops_per_stream_tick = flops.serve_flops_per_stream_tick(cfg)
        self.peak = peaks.peak(device_kind)
        self.hlo_names = hlo_names
        self.breakdown = {"device_ops": tr.top_ops(self.devs),
                          "idle_gaps": tr.idle_gaps(self.devs, host, t0, t1)}

    def scope_ms(self, scope: str) -> float:
        return tr.scope_ns(self.devs, scope, self.hlo_names) / 1e6


class Cell:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 base_key: int = 0):
        import jax
        self.devices = jax.devices()[:1]
        self.cfg, self.traffic = cfg, traffic
        self.base_key = base_key
        self.S = int(cfg["streams"])
        self.K = int(traffic["tick_k"])
        self.T = int(traffic["session_ticks"])
        self.svc = build_service(cfg, traffic)
        self.reseed(seed)

    def reseed(self, seed: int) -> None:
        """Weights and the session pool of ``seed``, on the same service
        (and so through the same compiled programs)."""
        import jax
        env = self.svc.env
        self.seed = seed
        self.svc.params = init_actor(seed, env.feat_dim, env.act_dim,
                                     self.cfg["hidden"], self.base_key)
        self.params_host = jax.device_get(self.svc.params)
        self.cols = [loadgen.streams(self.cfg, self.traffic, [seed, i],
                                     self.S) for i in range(POOL)]
        self.pool = [to_requests(self.cfg, c) for c in self.cols]

    def serve(self, i: int, ticks: int):
        return self.svc.serve_stream(self.pool[i % POOL], tick_k=self.K,
                                     ticks=ticks, seed=i)

    def warm(self, trace: bool) -> None:
        """Compile every program the window and the check use: one
        session of each length served, with one request per stream (the
        shapes do not depend on the traffic)."""
        warm = to_requests(self.cfg, [{k: v[:1] for k, v in c.items()}
                                      for c in self.cols[0]])
        self.svc.serve_stream(warm, tick_k=self.K, ticks=self.T, seed=POOL)
        if trace:
            self.svc.serve_stream(warm, tick_k=self.K, ticks=TRACE_TICKS,
                                  seed=POOL)

    def window(self, seconds: float, ticks: int | None = None):
        """Whole sessions back to back until ``seconds`` have passed."""
        results = []
        t0 = time.perf_counter()
        while True:
            results.append(self.serve(len(results), ticks or self.T))
            if time.perf_counter() - t0 >= seconds:
                break
        return results, time.perf_counter() - t0

    def traced_window(self):
        """One session of ``TRACE_TICKS`` ticks, for the profiler."""
        return self.window(0.0, TRACE_TICKS)

    def layer_context(self, evs: list[dict], results) -> LayerContext:
        return LayerContext(evs, results, self.cfg, self.S,
                            self.devices[0].device_kind, self.hlo_op_names())

    def hlo_op_names(self) -> dict:
        """``op_name`` of every instruction of the compiled tick (the
        trace names device ops by instruction only)."""
        import jax
        from repro.core.serve import make_serving_tick, queue_init_batch
        env, S, K = self.svc.env, self.S, self.K
        tick = make_serving_tick(env, kind=self.svc.policy_kind,
                                 pcfg=self.svc.pcfg, streams=S)
        adm = dict(model=np.zeros((S, K), np.int32),
                   arrival=np.zeros((S, K), np.float32),
                   deadline=np.zeros((S, K), np.float32),
                   q=np.ones((S, K), np.float32),
                   rid=np.zeros((S, K), np.int32),
                   valid=np.zeros((S, K), bool))
        text = tick.lower(self.svc.params, queue_init_batch(env, S), adm,
                          jax.random.PRNGKey(0)).compile().as_text()
        return tr.op_names_from_hlo(text)

    def end_to_end(self, results, wall: float) -> dict:
        ticks = np.concatenate([r["stats"]["tick_wall_us"] for r in results])
        n = sum(r["stats"]["ticks"] for r in results)
        return {"tick_p95_us": float(np.percentile(ticks, 95)),
                "serve_periods_per_s": self.S * n / wall}

    def load(self, results) -> tuple[int, int]:
        """(attempted, failed): requests that arrived by a session's last
        tick, and those of them still waiting for a queue slot then."""
        att = fail = 0
        for i, r in enumerate(results):
            last = (r["stats"]["ticks"] - 1) * self.cfg["t_s_us"]
            arrived = sum(int(np.sum(c["arrival"] <= last))
                          for c in self.cols[i % POOL])
            att += arrived
            fail += arrived - r["stats"]["admitted"]
        return att, fail

    def actions(self, store: dict) -> tuple[dict, int]:
        """The actor's outputs at each recorded tick (tick -> (S, R, G)),
        and the fields in which the tick that returns them leaves other
        queues or another record than the window's tick did."""
        import jax
        import repro.core.serve as cs
        acts, off = {}, 0
        with actions_out(self.svc.env):
            tick = cs.make_serving_tick(self.svc.env,
                                        kind=self.svc.policy_kind,
                                        pcfg=self.svc.pcfg, streams=self.S)
            for i, (pre, adm, key, post, out) in sorted(store.items()):
                q2, out2 = jax.device_get(tick(self.svc.params,
                                               jax.device_put(pre), adm, key))
                acts[i] = np.asarray(out2.pop("a"))
                off += differing_fields((q2, out2), (post, out))
        return acts, off

    def check(self, results, control: str | None = None,
              diag: list | None = None) -> dict:
        """Replay one session of the window and compare it.

        ``control`` (an operand rounding, the configuration's
        ``control``) puts the reference at that precision in the
        program's place, to read what the control reads; ``diag``
        collects what :func:`serve_check.check_stream` reads of every
        sampled stream-tick."""
        rng = np.random.default_rng([self.seed, 2])
        ticks = results[0]["stats"]["ticks"]
        c = int(rng.integers(len(results)))
        wanted = sorted(int(x) for x in rng.choice(
            ticks, size=min(CHECK_TICKS, ticks), replace=False))
        streams = {i: rng.choice(self.S, size=min(CHECK_STREAMS, self.S),
                                 replace=False) for i in wanted}
        store: dict = {}
        with record_ticks(set(wanted), store):
            res = self.serve(c, ticks)
        acts, off = self.actions(store)
        nums = {"replay_mismatches": replay_mismatches(res, results[c]) + off
                + len(set(wanted) - set(store)),
                "accounting_errors": accounting_errors(
                    self.cfg, self.cols[c % POOL], res)}
        tb = ref.Tables(self.cfg)
        gaps, bad = [0.0], 0
        for i in sorted(store):
            pre, adm, _, post, out = store[i]
            for s in streams[i]:
                r = chk.check_stream(
                    tb, self.params_host, chk.flat_queue(pre, s),
                    chk.flat_adm(adm, s), chk.flat_queue(post, s, out),
                    acts[i][s], self.cfg["operands"], control)
                gaps.append(r["gap"])
                bad += not r["ok"]
                if diag is not None:
                    diag.append(dict(tick=i, stream=int(s), **r))
        nums["tick_mismatches"] = bad
        nums["actor_gap"] = float(max(gaps))
        return nums
