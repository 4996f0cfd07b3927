"""Multi-tenant scheduling service: policy x registry x environment.

Deployment wrapper over ``sim.SchedulingEnv`` with two serving paths:

- :meth:`MultiTenantService.serve_stream` — the device-resident batched
  path: ``streams`` independent request queues live on device
  (``serving.queue``), and ONE jitted, donated scheduling tick
  (``repro.core.serve.make_serving_tick``) per period admits staged
  requests (masked scatter), runs batched policy inference over every
  pending sub-job of every tenant, advances the contention sim, and
  retires completed jobs — the host crosses the device boundary once
  per tick, staging ``(S, K)`` admission buffers in and reading one
  packed completion record out.  Fed by ``serving.loadgen`` streams.

- :meth:`MultiTenantService.serve_episode_host` — the per-period
  host-loop reference (one dispatch per period, full trace known
  upfront): kept as the numerical parity oracle (the batched path is
  bit-identical on a replayed trace — ``tests/test_serving_batched.py``)
  and as the "before" arm of ``benchmarks/serving_bench.py``.

Checkpoint policy: *generalist* checkpoints (``policy_kind:
"generalist"`` in meta — the fleet-conditioned M-agnostic policy of
``repro.core.generalist``) restore on ANY fleet whose ``num_sas`` fits
the checkpoint's ``m_max`` (the env is padded, descriptors condition
the weights); legacy per-fleet *specialist* checkpoints keep the
shape-/fleet-aware refusal — a same-width fleet restores shape-clean
but carries another platform's policy.
"""
from __future__ import annotations

import os
import time

import jax
import numpy as np

from repro.ckpt import restore_checkpoint
from repro.core import baselines as BL
from repro.core import policy as P
from repro.core.generalist import (PaddedEnv, load_generalist_checkpoint,
                                   make_generalist_period)
from repro.core.rollout import make_baseline_period, make_policy_period, \
    run_episode
from repro.costmodel.registry import Registry
from repro.serving.queue import admission_fields
from repro.serving.request import Request, resolve_request
from repro.sim.arrivals import ArrivalConfig
from repro.sim.engine import INF
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.telemetry.console import console_line
from repro.telemetry.profiler import trace_span


def per_tenant_metrics(env: SchedulingEnv, state, trace) -> dict[str, dict]:
    """SLA breakdown by tenant (model id) for one finished episode.

    Tenants with zero counted jobs report ``sla_rate: None`` (no data —
    distinct from 0.0, which means "all jobs missed"); the per-tenant
    ``jobs`` counts sum to the episode's counted total.
    """
    model = np.asarray(trace["model"])
    arrived = np.asarray(trace["arrival"]) < 1e29
    hit = np.asarray(state["hit"])
    counted = np.asarray(state["done"] | state["missed"]) & arrived
    out = {}
    for mid, name in enumerate(env.registry.model_names):
        sel = counted & (model == mid)
        n = int(sel.sum())
        out[name] = {"jobs": n,
                     "sla_rate": float(hit[sel].sum() / n) if n else None}
    return out


def _tenant_table(model_names, ten_counted, ten_hit) -> dict[str, dict]:
    """Per-tenant table from the queue accumulators — same int-ratio
    arithmetic as :func:`per_tenant_metrics` (bit-identical floats)."""
    out = {}
    for mid, name in enumerate(model_names):
        n = int(ten_counted[mid])
        out[name] = {"jobs": n,
                     "sla_rate": float(int(ten_hit[mid]) / n) if n else None}
    return out


class MultiTenantService:
    def __init__(self, registry: Registry, *, policy: str = "relmas",
                 ckpt_dir: str | None = None, hidden: int = 64,
                 env_cfg: EnvConfig | None = None,
                 arrivals: ArrivalConfig | None = None):
        env_cfg = env_cfg or EnvConfig()
        self.policy_name = policy
        self.policy_kind = "heuristic" if policy != "relmas" else "specialist"
        self.pcfg = None
        self._baseline_fn = None
        gen = (load_generalist_checkpoint(
                   ckpt_dir, min_num_sas=registry.mas.num_sas,
                   default_hidden=hidden)
               if policy == "relmas" else None)
        if gen is not None:
            # fleet-conditioned generalist: pad this fleet's env to the
            # checkpoint's m_max and serve it on ANY platform — the
            # descriptors in the features carry the fleet identity (a
            # failed weight restore only leaves the architecture
            # untrained; load_generalist_checkpoint already warned)
            params, pcfg, spec, _ = gen
            self.env = PaddedEnv(registry, env_cfg, spec.m_max, arrivals)
            self.policy_kind = "generalist"
            self.params = params
            self.pcfg = pcfg
            self._period = make_generalist_period(self.env, pcfg)
            return
        self.env = SchedulingEnv(registry, env_cfg, arrivals)
        if policy == "relmas":
            pcfg = P.PolicyConfig(feat_dim=self.env.feat_dim,
                                  act_dim=self.env.act_dim, hidden=hidden)
            params = P.init_actor(jax.random.PRNGKey(0), pcfg)
            # attempt the restore whenever a directory was given (even
            # an empty one: the FileNotFoundError path must still warn)
            if ckpt_dir and os.path.isdir(ckpt_dir):
                try:
                    restored, _, meta = restore_checkpoint(ckpt_dir, params)
                    # legacy specialist checkpoints stay fleet-locked:
                    # same-width fleets restore shape-clean but carry
                    # another platform's policy — only accept a fleet
                    # match when both sides are named (checkpoints from
                    # before the fleet axis carry no meta["fleet"])
                    ck_fleet = meta.get("fleet")
                    fleet = getattr(registry.mas, "name", None)
                    if ck_fleet and fleet and ck_fleet != fleet:
                        console_line(f"[service] checkpoint trained on fleet "
                                     f"{ck_fleet!r}, serving {fleet!r}; "
                                     f"using untrained policy")
                    else:
                        params = restored
                except (ValueError, KeyError, FileNotFoundError) as e:
                    # checkpoint trained for a different MAS shape (M
                    # changes feat/act dims) — serve with a fresh policy
                    console_line(f"[service] checkpoint incompatible ({e}); "
                                 f"using untrained policy")
            self.params = params
            self.pcfg = pcfg
            self._period = make_policy_period(self.env, pcfg)
        else:
            self._baseline_fn = BL.BASELINES[policy]
            self.params = None
            self._period = make_baseline_period(self.env, self._baseline_fn)

    # ------------------------------------------------------------------
    # host-loop reference path (one dispatch per period, trace upfront)
    # ------------------------------------------------------------------
    def serve_episode_host(self, seed: int = 0) -> dict:
        """Run one freshly-drawn full-trace episode through the
        per-period host loop (draws the trace, then
        :meth:`serve_trace_host`)."""
        rng = np.random.default_rng(seed)
        trace, state = self.env.new_episode(rng)
        return self.serve_trace_host(trace, state, seed=seed)

    def serve_trace_host(self, trace, state=None, *, seed: int = 0) -> dict:
        """Serve one episode trace through the per-period host loop.

        One dispatch per period, the whole trace known upfront — the
        numerical reference for :meth:`serve_stream` (bit-identical SLA
        + per-tenant metrics on the same workload, see
        ``loadgen.requests_to_trace``) and the "before" arm of
        ``benchmarks/serving_bench.py``.
        """
        if state is None:
            state = self.env.init_state(trace)
        key = jax.random.PRNGKey(seed)
        for _ in range(self.env.cfg.periods):
            if self.params is not None:
                key, sub = jax.random.split(key)
                state, _, _ = self._period(self.params, state, trace, sub,
                                           sigma=0.0)
            else:
                state, _, _ = self._period(state, trace)
        state = self.env.mark_drops(state, trace, state["t"])
        metrics = {k: float(v) for k, v in
                   self.env.metrics(state, trace).items()}
        metrics["per_tenant"] = per_tenant_metrics(self.env, state, trace)
        return metrics

    # kept name: external callers/tests predate the batched path
    run_episode = serve_episode_host

    # ------------------------------------------------------------------
    # device-resident batched path (one dispatch per tick, all streams)
    # ------------------------------------------------------------------
    def _tick_fns(self, streams: int, device_telemetry: bool = False):
        # deferred import: repro.core.serve imports serving.queue, which
        # initializes this package — a module-level import here would
        # close the cycle during interpreter bootstrap
        from repro.core.serve import (make_serving_flush, make_serving_tick,
                                      queue_init_batch)
        tick = make_serving_tick(self.env, kind=self.policy_kind,
                                 pcfg=self.pcfg,
                                 baseline_fn=self._baseline_fn,
                                 streams=streams)
        flush = make_serving_flush(self.env, streams)
        return tick, flush, queue_init_batch(self.env, streams,
                                             telemetry=device_telemetry)

    def serve_stream(self, request_streams, *, tick_k: int = 8,
                     ticks: int | None = None, seed: int = 0,
                     telemetry=None, window: int = 0) -> dict:
        """Serve request streams through the batched single-dispatch tick.

        ``request_streams``: a list of per-stream ``Request`` lists (or
        one flat ``Request`` list for a single stream).  Every request
        is validated up front (:func:`~repro.serving.request.
        resolve_request`: unknown model ids and non-positive SLA budgets
        raise).  Each tick stages up to ``tick_k`` arrived requests per
        stream; rows that find no free slot are *deferred* (re-staged
        next tick — under saturation they admit late and age into SLA
        misses rather than vanishing).  Runs ``ticks`` scheduling
        periods (default ``env.cfg.periods``) and then flushes: final
        drop pass + drain, exactly the reference path's closing pass.

        Where the registry's tenants re-enter (LM requests), each
        request asks for ``n_out`` tokens under a TTFT deadline and a
        TPOT limit: its completion record carries ``t_first_us`` and
        ``passes_left``, ``metrics`` and ``aggregate`` TTFT and TPOT
        attainment beside ``sla_rate`` (both limits), and the device
        telemetry block the decode passes and first tokens.

        Returns ``dict(metrics, aggregate, completions, stats)``:
        ``metrics`` is the per-stream list of
        :meth:`serve_episode_host`-schema dicts, ``completions`` the
        per-stream completion records, ``stats`` the serving telemetry
        (per-tick wall times, admitted/deferred counts, queue depths,
        and ``readbacks``: the device-to-host reads the call issued, one
        packed read a tick and the flush's).

        ``telemetry``: an optional :class:`repro.telemetry.Telemetry`
        session.  When given, the queues carry the device-resident
        telemetry block (depth histogram, committed/tick counters —
        accumulated in-graph, read back only at the flush the path
        already pays for) and the host emits ``serve_window`` records
        every ``window`` ticks (0 disables windows), the per-tenant
        ``tenant`` table aggregated across streams, and a
        ``serve_summary`` (with ``readbacks``) — all computed from values
        the loop already transfers, so the telemetry session adds zero
        device syncs.

        Host spans on the profiler's clock (:func:`repro.telemetry.
        trace_span`; one context manager each when no profiler runs):
        ``serve.session`` over the call, holding ``serve.resolve``
        (request resolution), ``serve.setup`` (queues and keys), one
        ``serve.tick`` step per tick (its ``serve.stage``,
        ``serve.dispatch``, ``serve.readback`` — the tick's one read, of
        its packed ``out["host"]`` — and ``serve.record``, which reads
        nothing from the device) and ``serve.flush``.
        """
        if request_streams and isinstance(request_streams[0], Request):
            request_streams = [request_streams]
        S = len(request_streams)
        if S == 0:
            raise ValueError("no request streams given")
        with trace_span("serve.session", streams=S):
            return self._serve_stream(request_streams, tick_k, ticks, seed,
                                      telemetry, window)

    def _serve_stream(self, request_streams, tick_k, ticks, seed,
                      telemetry, window) -> dict:
        from repro.core.serve import host_layout, host_unpack  # see _tick_fns
        S = len(request_streams)
        names = self.env.registry.model_names
        # resolve every request up front into per-stream column arrays,
        # arrival-sorted.  Admission consumes staged rows FIFO in this
        # order, so each stream's backlog is always the contiguous
        # window [head, avail) of its columns — per-tick staging is pure
        # array slicing, no per-request Python in the hot loop.
        K = tick_k
        n_req = np.array([len(st) for st in request_streams], np.int64)
        N = max(int(n_req.max()), 1)
        cols = dict(rid=np.full((S, N), -1, np.int32),
                    model=np.zeros((S, N), np.int32),
                    arrival=np.full((S, N), np.float32(INF), np.float32),
                    deadline=np.full((S, N), np.float32(INF), np.float32),
                    q=np.ones((S, N), np.float32),
                    n_out=np.ones((S, N), np.int32),
                    tpot=np.zeros((S, N), np.float32))
        with trace_span("serve.resolve"):
            for s, stream in enumerate(request_streams):
                for j, r in enumerate(sorted(stream,
                                             key=lambda r: r.arrival_us)):
                    row = resolve_request(r, names)
                    cols["rid"][s, j] = r.rid
                    cols["model"][s, j] = row.model
                    cols["arrival"][s, j] = row.arrival_us
                    cols["deadline"][s, j] = row.deadline_us
                    cols["q"][s, j] = row.q_us
                    cols["n_out"][s, j] = row.n_out
                    cols["tpot"][s, j] = row.tpot_us
            self.env.check_passes(cols["n_out"])
            cols = {k: cols[k] for k in admission_fields(self.env)}
        n_ticks = ticks if ticks is not None else self.env.cfg.periods
        with trace_span("serve.setup"):
            tick, flush, queues = self._tick_fns(
                S, device_telemetry=telemetry is not None)
            # all per-tick keys drawn up front: a host-side split per
            # tick would cost two extra dispatches inside the serving loop
            keys = np.asarray(jax.random.split(jax.random.PRNGKey(seed),
                                               n_ticks))
        t_s = float(self.env.cfg.t_s_us)
        layout = host_layout(self.env)
        head = np.zeros((S,), np.int64)    # first not-yet-admitted row
        completions: list[list[dict]] = [[] for _ in range(S)]
        tick_wall_us: list[float] = []
        depth_sum = 0
        admitted = deferred = readbacks = 0
        win = int(window) if telemetry is not None else 0
        w_first, w_adm, w_def, w_comp, w_depth = 0, 0, 0, 0, 0
        lane = np.arange(K)
        for i in range(n_ticks):
            with trace_span("serve.tick", step_num=i):
                t_now = i * t_s
                # each stream's backlog is cols[:, head:avail]; window the
                # first K rows with one gather per column — no per-stream
                # Python in the hot loop
                with trace_span("serve.stage"):
                    avail = (cols["arrival"] <= t_now).sum(axis=1)
                    n_stage = np.minimum(avail - head, K)
                    idx = np.minimum(head[:, None] + lane[None, :], N - 1)
                    valid = lane[None, :] < n_stage[:, None]
                    adm = {k: np.take_along_axis(v, idx, axis=1)
                           for k, v in cols.items()}
                    adm["valid"] = valid
                # tick_wall_us times the dispatch and the tick's one
                # read: the decision back on the host.  Every field the
                # host needs comes in that read, packed as out["host"]
                with trace_span("serve.dispatch"):
                    t0 = time.perf_counter()
                    queues, out = tick(self.params, queues, adm, keys[i])
                    out["host"].copy_to_host_async()
                with trace_span("serve.readback"):
                    buf = np.asarray(out["host"])
                    readbacks += 1
                    tick_wall_us.append((time.perf_counter() - t0) * 1e6)
                    got = host_unpack(layout, buf)
                n_adm, comp = got["n_admitted"], got["completed"]
                depth = int(got["depth"].sum())
                head += n_adm
                admitted += int(n_adm.sum())
                deferred += int((n_stage - n_adm).sum())
                depth_sum += depth
                if win:
                    w_adm += int(n_adm.sum())
                    w_def += int((n_stage - n_adm).sum())
                    w_comp += int(comp.sum())
                    w_depth += depth
                    if i + 1 - w_first >= win or i == n_ticks - 1:
                        w_wall = tick_wall_us[w_first:i + 1]
                        telemetry.emit(
                            "serve_window", tick_first=w_first, tick_last=i,
                            tick_p50_us=float(np.percentile(w_wall, 50)),
                            tick_p99_us=float(np.percentile(w_wall, 99)),
                            admitted=w_adm, deferred=w_def, completed=w_comp,
                            mean_depth=w_depth / max(len(w_wall) * S, 1))
                        w_first, w_adm, w_def, w_comp, w_depth = \
                            i + 1, 0, 0, 0, 0
                if comp.any():
                    with trace_span("serve.record"):
                        self._record(got, completions)
        with trace_span("serve.flush"):
            queues, fout = flush(queues)
            final = jax.tree.map(np.asarray, fout)
            readbacks += 1
            self._record(final, completions)
        metrics = []
        for s in range(S):
            m = dict(hits=float(final["hits"][s]),
                     counted=float(final["counted"][s]),
                     arrived=float(final["arrived"][s]),
                     sla_rate=float(final["sla_rate"][s]),
                     energy_uj=float(final["energy_uj"][s]))
            if "ttft_hits" in final:
                m.update({k: float(final[k][s]) for k in (
                    "ttft_hits", "tpot_hits", "ttft_rate", "tpot_rate")})
            m["per_tenant"] = _tenant_table(names, final["ten_counted"][s],
                                            final["ten_hit"][s])
            metrics.append(m)
        tot_c = int(final["counted"].sum())
        tot_h = int(final["hits"].sum())
        unserved = int((n_req - head).sum())
        aggregate = dict(
            sla_rate=tot_h / max(tot_c, 1), counted=tot_c, hits=tot_h,
            arrived=int(final["arrived"].sum()),
            energy_uj=float(final["energy_uj"].sum()),
            completed=sum(len(c) for c in completions))
        if "ttft_hits" in final:
            for k in ("ttft", "tpot"):
                aggregate[k + "_rate"] = (int(final[k + "_hits"].sum())
                                          / max(tot_c, 1))
        stats = dict(streams=S, ticks=n_ticks, tick_k=tick_k,
                     tick_wall_us=tick_wall_us, admitted=admitted,
                     deferred=deferred, unserved=unserved,
                     mean_depth=depth_sum / max(n_ticks, 1),
                     readbacks=readbacks)
        if "tele_depth_hist" in final:
            # the device-accumulated block, read back at the flush
            stats["device_tele"] = dict(
                depth_hist=final["tele_depth_hist"].sum(axis=0).tolist(),
                depth_edges=final["tele_depth_edges"][0].tolist(),
                committed=int(final["tele_committed"].sum()),
                ticks=int(final["tele_ticks"][0]),
                engine_iters=int(final["tele_engine_iters"].sum()),
                engine_trips=int(final["tele_engine_trips"][0]))
            if "tele_passes" in final:
                stats["device_tele"].update(
                    passes=int(final["tele_passes"].sum()),
                    first_tokens=int(final["tele_first_tokens"].sum()))
        if telemetry is not None:
            ten_counted = final["ten_counted"].sum(axis=0)
            ten_hit = final["ten_hit"].sum(axis=0)
            for name, row in _tenant_table(names, ten_counted,
                                           ten_hit).items():
                telemetry.emit("tenant", tenant=name, jobs=row["jobs"],
                               sla_rate=row["sla_rate"])
            telemetry.emit("serve_summary",
                           sla_rate=aggregate["sla_rate"],
                           counted=tot_c, ticks=n_ticks,
                           readbacks=readbacks)
        return dict(metrics=metrics, aggregate=aggregate,
                    completions=completions, stats=stats)

    @staticmethod
    def _record(out, completions) -> None:
        """Append one tick's completed jobs to the per-stream logs (an LM
        request's with its first-token time and the passes it had left).
        ``out`` holds host arrays: nothing here reads the device."""
        at = np.nonzero(out["completed"])
        fields = dict(rid="rid", hit="hit", missed="missed",
                      finish_us="finish_us")
        if "t_first" in out:
            fields.update(t_first_us="t_first", passes_left="passes_left")
        cols = [out[k][at].tolist() for k in fields.values()]
        for s, *vals in zip(at[0].tolist(), *cols):
            completions[s].append(dict(zip(fields, vals)))
