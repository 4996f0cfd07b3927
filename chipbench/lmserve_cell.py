"""An LM serving cell: whole LM requests through ``serve_stream``.

The serving cell of ``serve_cell.py`` for tenants whose requests
re-enter the queue: each asks for ``n_out`` tokens, one prefill pass and
then ``n_out - 1`` decode passes, under a time-to-first-token and a
time-per-output-token limit. Set-up, the window, the end-to-end metrics
and the replay are ``serve_cell``'s; what differs:

- the configuration's tables are the program's whole-request tables
  (``workloads/llm_zoo``) with each tenant's ``decode_start``, and its
  tenants are request classes of one architecture;
- the streams are ``loadgen_lm``'s and carry each request's output
  length and TPOT limit;
- the check's reference tick is ``reference_lm``'s (``lmserve_check``),
  and its accounting holds every completed request to its passes and
  both limits;
- set-up serves a short session (``WARM_TICKS``): the tick and the
  flush compile once whatever a session's length, and only the per-tick
  keys are shaped by it;
- the traced session is longer (``TRACE_TICKS``); it runs the timed
  program, and the decode passes and first tokens ``lm.passes_per_tick``
  reads come from the same session served again after the trace with
  the device telemetry block, where it returns what the traced one did.
"""
from __future__ import annotations

import numpy as np

import lmserve_check as lchk
import loadgen_lm
import reference_lm as rl
import serve_cell as sc
from serve_cell import CHECK_STREAMS, CHECK_TICKS, POOL

# ticks of the traced session (trace runs only): a 512-token prefill
# alone takes 197 periods, so a session must run several times that
# before first tokens, and so re-entries, are part of its work
TRACE_TICKS = 800
# ticks of the warm session: enough to admit, tick, retire and flush
WARM_TICKS = 8

# limits of the numbers compared (PERF.md gives the readings they were
# set from)
LIMITS = {"replay_mismatches": 0, "accounting_errors": 0,
          "tick_mismatches": 0, "actor_gap": 1.5e-2}


def build_service(cfg: dict, traffic: dict):
    """The program's service for the configuration, after checking that
    the program's whole-request tables are the configuration's."""
    from repro.serving import MultiTenantService
    from repro.sim.arrivals import ArrivalConfig
    from repro.sim.env import EnvConfig
    from repro.workloads.llm_zoo import build_llm_registry
    reg = build_llm_registry(cfg["workload"], mas=cfg["fleet"])
    check_tables(cfg, reg)
    ecfg = EnvConfig(t_s_us=cfg["t_s_us"], periods=60, max_rq=cfg["max_rq"],
                     max_jobs=cfg["max_jobs"])
    arr = ArrivalConfig(max_jobs=cfg["max_jobs"], load=cfg["load"],
                        eff_parallelism=cfg["eff_parallelism"],
                        qos_factor=cfg["qos_factor"],
                        qos_level=cfg["qos_level"],
                        horizon_us=ecfg.horizon_us,
                        scenario=traffic["scenario"],
                        burst_size=traffic.get("burst_size", 4))
    svc = MultiTenantService(reg, policy=cfg["policy"], hidden=cfg["hidden"],
                             env_cfg=ecfg, arrivals=arr)
    if svc.policy_kind != cfg["policy_kind"] or not svc.env.reenters:
        raise RuntimeError(f"service built a {svc.policy_kind} policy over "
                           f"tenants that re-enter: {svc.env.reenters}; the "
                           f"configuration states {cfg['policy_kind']} over "
                           f"whole LM requests")
    return svc


def check_tables(cfg: dict, reg) -> None:
    sc.check_tables(cfg, reg)
    d, t = reg.dense(), cfg["tables"]
    bad = [c for k, c in (("decode_start", "decode_start"),
                          ("min_first", "min_first_us"),
                          ("min_pass", "min_pass_us"))
           if not np.array_equal(np.asarray(d[k], np.float32),
                                 np.asarray(t[c], np.float32))]
    if bad:
        raise RuntimeError(f"the program's {cfg['workload']} tables differ "
                           f"from the configuration in {bad}")


def to_requests(cfg: dict, cols: list[dict]):
    """Loadgen columns -> the program's ``Request`` lists."""
    from repro.serving.request import Request
    names = cfg["tenants"]
    return [[Request(rid=i, tenant=names[int(c["model"][i])],
                     arrival_us=float(c["arrival"][i]),
                     deadline_us=float(c["deadline"][i]),
                     q_us=float(c["q"][i]), n_out=int(c["n_out"][i]),
                     tpot_us=float(c["tpot"][i]))
             for i in range(len(c["arrival"]))]
            for c in cols]


def accounting_errors(cfg: dict, cols: list[dict], res: dict) -> int:
    """Requests of a session whose counting breaks a guarantee: a rid
    served twice or unknown; a done request that did not get exactly its
    ``n_out`` passes (passes left, no first token, or a last token
    sooner than its decode passes take in isolation after its first); a
    first token sooner than the isolated prefill after arrival; a hit
    that is not a done request meeting both limits; and per-stream and
    per-tenant counts, hits and each limit's hits, that do not add up.
    The final deadline is float32 on the device: a last token within
    rounding of it may count either way."""
    first = np.asarray(cfg["tables"]["min_first_us"], np.float64)
    step = np.asarray(cfg["tables"]["min_pass_us"], np.float64)
    names = cfg["tenants"]
    soon = lambda t, bound: t < bound * (1 - 1e-5) - 1e-2
    bad = 0
    for c, comp, m in zip(cols, res["completions"], res["metrics"]):
        rids = [x["rid"] for x in comp]
        bad += len(rids) - len(set(rids))
        hits = ttft = tpot = edge = 0
        per_tenant = np.zeros(len(names), np.int64)
        for x in comp:
            r = x["rid"]
            if not 0 <= r < len(c["arrival"]):
                bad += 1
                continue
            model, n_out = int(c["model"][r]), int(c["n_out"][r])
            arr = float(np.float32(c["arrival"][r]))
            dl = float(np.float32(c["deadline"][r]))
            tf, fin, done = x["t_first_us"], x["finish_us"], not x["missed"]
            per_tenant[model] += 1
            got_first = tf < rl.INF / 2
            final = tf + float(np.float32(c["tpot"][r])) * (n_out - 1)
            near = done and abs(fin - final) <= 1e-2 + 1e-6 * abs(final)
            met = done and fin <= final
            hits += x["hit"]
            ttft += got_first and tf <= dl
            tpot += met and not near
            edge += near
            if got_first and soon(tf, arr + first[model]):
                bad += 1
            if done and (x["passes_left"] != 0 or not got_first
                         or soon(fin, tf + (n_out - 1) * step[model])):
                bad += 1
            if x["hit"] != (done and tf <= dl and met) and not near:
                bad += 1
        bad += int(m["counted"] != len(comp)) + int(m["hits"] != hits)
        bad += int(m["ttft_hits"] != ttft)
        bad += int(not tpot <= m["tpot_hits"] <= tpot + edge)
        bad += sum(int(m["per_tenant"][n]["jobs"] != per_tenant[i])
                   for i, n in enumerate(names))
    return bad


class LayerContext(sc.LayerContext):
    """What a per-layer reader reads from a traced window of an LM
    serving cell: ``serve_cell``'s, and the decode passes and first
    tokens of the window's session, from the device counters of
    ``counted`` (the session served again with the telemetry block);
    None where the program keeps no such counter, or where ``counted``
    did not return what the window did."""

    def __init__(self, evs, results, counted, cfg, streams, device_kind,
                 hlo_names):
        super().__init__(evs, results, cfg, streams, device_kind, hlo_names)
        tele = counted["stats"].get("device_tele", {})
        same = (len(results) == 1
                and sc.replay_mismatches(counted, results[0]) == 0)
        self.passes = tele.get("passes") if same else None
        self.first_tokens = tele.get("first_tokens") if same else None


class Cell(sc.Cell):
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
        import jax
        env = self.svc.env
        self.seed = seed
        self.svc.params = sc.init_actor(seed, env.feat_dim, env.act_dim,
                                        self.cfg["hidden"], self.base_key)
        self.params_host = jax.device_get(self.svc.params)
        self.cols = [loadgen_lm.streams(self.cfg, self.traffic, [seed, i],
                                        self.S) for i in range(POOL)]
        self.pool = [to_requests(self.cfg, c) for c in self.cols]

    def warm(self, trace: bool) -> None:
        """Compile every program the window uses: the tick and the flush,
        through a short session with one request per stream, and the
        per-tick keys of each session length the run serves."""
        import jax
        warm = to_requests(self.cfg, [{k: v[:1] for k, v in c.items()}
                                      for c in self.cols[0]])
        self.svc.serve_stream(warm, tick_k=self.K, ticks=WARM_TICKS,
                              seed=POOL)
        for ticks in {self.T, TRACE_TICKS} if trace else {self.T}:
            jax.block_until_ready(
                jax.random.split(jax.random.PRNGKey(POOL), ticks))

    def traced_window(self):
        """One session of ``TRACE_TICKS`` ticks of the timed program, for
        the profiler."""
        return self.window(0.0, TRACE_TICKS)

    def layer_context(self, evs, results) -> LayerContext:
        counted = self.svc.serve_stream(self.pool[0], tick_k=self.K,
                                        ticks=TRACE_TICKS, seed=0,
                                        telemetry=_telemetry())
        return LayerContext(evs, results, counted, self.cfg, self.S,
                            self.devices[0].device_kind, self.hlo_op_names())

    def hlo_op_names(self) -> dict:
        """``op_name`` of every instruction of the timed tick."""
        import jax
        import reduce_trace as tr
        from repro.core.serve import make_serving_tick, queue_init_batch
        from repro.serving.queue import pack_admissions
        env, S, K = self.svc.env, self.S, self.K
        tick = make_serving_tick(env, kind=self.svc.policy_kind,
                                 pcfg=self.svc.pcfg, streams=S)
        one = pack_admissions([], K)
        adm = {k: np.stack([v] * S) for k, v in one.items()}
        text = tick.lower(self.svc.params,
                          queue_init_batch(env, S), adm,
                          jax.random.PRNGKey(0)).compile().as_text()
        return tr.op_names_from_hlo(text)

    def check(self, results, control: str | None = None,
              diag: list | None = None) -> dict:
        """``serve_cell.Cell.check`` with the LM reference tick and the
        LM accounting."""
        rng = np.random.default_rng([self.seed, 2])
        ticks = results[0]["stats"]["ticks"]
        c = int(rng.integers(len(results)))
        wanted = sorted(int(x) for x in rng.choice(
            ticks, size=min(CHECK_TICKS, ticks), replace=False))
        streams = {i: rng.choice(self.S, size=min(CHECK_STREAMS, self.S),
                                 replace=False) for i in wanted}
        store: dict = {}
        with sc.record_ticks(set(wanted), store):
            res = self.serve(c, ticks)
        acts, off = self.actions(store)
        nums = {"replay_mismatches": sc.replay_mismatches(res, results[c])
                + off + len(set(wanted) - set(store)),
                "accounting_errors": accounting_errors(
                    self.cfg, self.cols[c % POOL], res)}
        tb = rl.Tables(self.cfg)
        gaps, bad = [0.0], 0
        for i in sorted(store):
            pre, adm, _, post, out = store[i]
            for s in streams[i]:
                r = lchk.check_stream(
                    tb, self.params_host, lchk.flat_queue(pre, s),
                    lchk.chk.flat_adm(adm, s),
                    lchk.flat_queue(post, s, out), acts[i][s],
                    self.cfg["operands"], control)
                gaps.append(r["gap"])
                bad += not r["ok"]
                if diag is not None:
                    diag.append(dict(tick=i, stream=int(s), **r))
        nums["tick_mismatches"] = bad
        nums["actor_gap"] = float(max(gaps))
        return nums


def _telemetry():
    from repro.telemetry import ListSink, Telemetry
    return Telemetry([ListSink()])
