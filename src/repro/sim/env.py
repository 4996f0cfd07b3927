"""Periodic-scheduling environment (paper Sec. 4.1, Fig. 2a).

Fixed-shape, jit-friendly formulation: instead of a slot-based mutable
ready queue, per-job state (next layer to schedule, ready time, flags)
is kept and the RQ is *derived* each period by packing the uncommitted
layers of active jobs — sorted by absolute deadline, exactly the order
the paper feeds the LSTM — into ``max_rq`` slots.  Because a job's
layers occupy contiguous ascending slots, precedence reduces to
``dep[i] = i-1`` within a job, which is what the contention engine
consumes.

Each period:
  1. deadline-passed jobs are dropped (whole remaining job = SLA miss);
  2. the RQ is built from jobs arrived by ``t`` + residuals;
  3. the policy (or a baseline) emits (priority, SA) per slot;
  4. the engine simulates the full horizon; SJs *started* before
     ``t + T_s`` commit (non-preemptive), the rest become residuals;
  5. the paper reward is computed from the projected finish times;
  6. the transition's next state encodes the residual RQ only.

Jobs that re-enter (an LM request: one prefill pass, then one decode
pass per further output token; the registry's ``decode_start``): a
job's table is the prefill rows then the decode-pass rows, the RQ
offers only the current pass, and a job whose pass ends with passes
left goes back to ``decode_start``.  Such a job carries two limits: the
TTFT deadline (``trace["deadline"]``) before its first token, then the
final deadline ``t_first + tpot * (n_out - 1)``; the current one
(``state["dl"]``) drives drops, and a job hits iff it meets both.  The
RQ orders a pass by the deadline of the token it yields (the TTFT
deadline, then ``t_first + tpot * k`` for the ``k + 1``-th token), so
a decode pass is due ``tpot`` after the last and does not wait behind
every prefill whose TTFT deadline lies before the request's final one.  Whether any tenant re-enters is read from the
registry once: without one, every method traces the one-pass program.

Whole episodes are traceable too: :meth:`SchedulingEnv.episode` runs
all periods in one ``jax.lax.scan`` (final drop pass + metrics inside
the trace) and is ``vmap``-able over the stacked traces/states built by
:meth:`SchedulingEnv.new_episodes` — the device-resident batched
rollout pipeline in ``repro.core.rollout`` is built on exactly this.
"""
from __future__ import annotations

import copy
import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.costmodel.registry import Registry
from repro.sim.arrivals import (ArrivalConfig, generate_trace,
                                generate_traces, generate_traces_jax)
from repro.sim.engine import simulate_jax, INF

State = dict[str, Any]
Trace = dict[str, Any]
Slots = dict[str, Any]

# advertised cost of an SA that is invalid this period (failed, or not
# yet joined — see repro.sim.churn): large enough that selecting it is
# an unmissable SLA catastrophe, finite so the `* zero` slot masking in
# build_slots stays NaN-free (INF * 0 = NaN).  Mirrors the padding
# poison PAD_LAT_US of repro.core.generalist.env.
CHURN_POISON_US = 1.0e7

# state keys injected by `period` when a churn row is threaded; they are
# visible to build_slots / act_fns and stripped before the state is
# returned (the scan carry keeps its static structure)
_CHURN_KEYS = ("sa_valid", "lat_mult", "bw_mult")

# the primer row (feats[0]) carries each SA's busy time, clip((sa_free -
# t) / t_s, 0, BUSY_CAP) / BUSY_CAP, in the M columns from BUSY_COL (the
# slot rows' per-SA cost columns); see SchedulingEnv.primer_sa_busy
BUSY_COL = 4
BUSY_CAP = 4.0


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    t_s_us: float = 500.0        # scheduling period T_S
    periods: int = 60            # episode length (last ~40% drains arrivals)
    max_rq: int = 96             # R: RQ slot capacity presented to the policy
    max_jobs: int = 64           # J
    # shared DRAM bandwidth (fig.4 sweeps this); 0 = take the fleet's
    # dram_gbps from the registry's MASConfig (repro.costmodel.fleets)
    bandwidth_gbps: float = 0.0
    # reward coefficients (paper Sec. 5)
    alpha: float = 0.10
    beta: float = 0.11
    gamma_r: float = 0.05
    delta: float = 0.01
    # feature normalization
    ttd_norm_periods: float = 8.0

    @property
    def horizon_us(self) -> float:
        return 0.6 * self.t_s_us * self.periods


class SchedulingEnv:
    """Binds a model Registry (tables) + EnvConfig into pure step functions."""

    def __init__(self, registry: Registry, cfg: EnvConfig,
                 arrivals: ArrivalConfig | None = None):
        if cfg.bandwidth_gbps <= 0:  # resolve "fleet default" once, here
            cfg = dataclasses.replace(cfg,
                                      bandwidth_gbps=registry.mas.dram_gbps)
        self.cfg = cfg
        self.registry = registry
        d = registry.dense()
        self.num_models = d["num_models"]
        self.lmax = d["lmax"]
        self.num_sas = d["num_sas"]
        self.lat = jnp.asarray(d["lat"], jnp.float32)      # (n, Lmax, M)
        self.bw = jnp.asarray(d["bw"], jnp.float32)
        self.en = jnp.asarray(d["en"], jnp.float32)
        self.n_layers = jnp.asarray(d["n_layers"], jnp.int32)
        self.min_lat = jnp.asarray(d["min_lat"], jnp.float32)
        self.reenters = registry.reenters
        if self.reenters:
            self.decode_start = jnp.asarray(d["decode_start"], jnp.int32)
            # isolated first pass and decode pass per tenant, on the
            # host: the LM load generator's limits
            self.min_first = np.asarray(d["min_first"], np.float32)
            self.min_pass = np.asarray(d["min_pass"], np.float32)
        self.arrivals = arrivals or ArrivalConfig(
            max_jobs=cfg.max_jobs, horizon_us=cfg.horizon_us,
            slack_us=2.0 * cfg.t_s_us)
        self.feat_dim = 4 + 2 * self.num_sas
        self.act_dim = 1 + self.num_sas
        self.seq_len = cfg.max_rq + 1          # + primer

    @property
    def primer_sa_busy(self) -> tuple[tuple, float]:
        """``(index, us_per_unit)``: where :meth:`encode` puts the SAs'
        busy times in ``feats`` (row, columns) and how many µs one
        feature unit stands for.  These features are an absolute float32
        time less the clock, so they keep that time's rounding."""
        return ((0, slice(BUSY_COL, BUSY_COL + self.num_sas)),
                BUSY_CAP * self.cfg.t_s_us)

    # ---------------- fleet tables as data ----------------
    def bind_tables(self, *, lat=None, bw=None, en=None, min_lat=None,
                    bandwidth_gbps=None) -> "SchedulingEnv":
        """Functional shallow copy with characterization tables replaced.

        The replacements may be **traced** arrays: every env method only
        ever indexes/broadcasts the tables, so a jitted program can bind
        per-round fleet tensors gathered from a stacked ``(K, ...)``
        axis and run :meth:`episode` with the platform as *data* — one
        compiled trace serves every fleet of equal padded shape (the
        multi-fleet generalist trainer in ``repro.core.generalist``).
        Shapes must match the originals; ``bandwidth_gbps`` rebinds the
        resolved ``cfg.bandwidth_gbps`` (a scalar, traceable too).
        """
        env = copy.copy(self)
        env._runner_cache = {}     # compiled-runner cache is per-binding
        for name, val in (("lat", lat), ("bw", bw), ("en", en),
                          ("min_lat", min_lat)):
            if val is not None:
                if val.shape != getattr(self, name).shape:
                    raise ValueError(f"{name}: bound shape {val.shape} != "
                                     f"{getattr(self, name).shape}")
                setattr(env, name, val)
        if bandwidth_gbps is not None:
            env.cfg = dataclasses.replace(self.cfg,
                                          bandwidth_gbps=bandwidth_gbps)
        return env

    # ---------------- episode setup ----------------
    def init_state(self, trace: Trace) -> State:
        """Fresh per-episode state for one trace (traceable, vmap-able)."""
        J, M = self.cfg.max_jobs, self.num_sas
        state = dict(
            nls=jnp.zeros((J,), jnp.int32),
            jready=trace["arrival"],
            missed=jnp.zeros((J,), bool),
            done=jnp.zeros((J,), bool),
            hit=jnp.zeros((J,), bool),
            fjob=jnp.full((J,), INF, jnp.float32),
            sa_free=jnp.zeros((M,), jnp.float32),
            t=jnp.zeros((), jnp.float32),
            energy=jnp.zeros((), jnp.float32),
        )
        if self.reenters:
            state.update(passes_left=trace["n_out"] - 1,
                         t_first=jnp.full((J,), INF, jnp.float32),
                         dl=trace["deadline"])
        return state

    def _finish_trace(self, tr: dict) -> Trace:
        trace = {k: jnp.asarray(v) for k, v in tr.items()}
        trace["njl"] = self.n_layers[trace["model"]]
        if self.reenters:
            # a drawn trace carries no output lengths: one pass each
            # (the first token), unless the rows say otherwise
            ones = jnp.ones_like(trace["model"])
            trace.setdefault("n_out", ones)
            trace.setdefault("tpot", jnp.zeros_like(trace["arrival"]))
            trace["ds"] = self.decode_start[trace["model"]]
        else:
            self.check_passes(tr.get("n_out", 1))
            trace.pop("n_out", None)
            trace.pop("tpot", None)
        return trace

    def check_passes(self, n_out) -> None:
        """Refuse requests for several output tokens where no tenant
        re-enters (each job is one pass)."""
        if not self.reenters and np.any(np.asarray(n_out) > 1):
            raise ValueError(
                f"requests for up to {int(np.max(n_out))} output tokens; "
                f"this registry's tenants run one pass and do not re-enter")

    def new_episode(self, rng: np.random.Generator,
                    arrivals: ArrivalConfig | None = None
                    ) -> tuple[Trace, State]:
        """Fresh trace+state; ``arrivals`` overrides the env's arrival
        process (e.g. a scenario preset) without recompiling anything —
        trace generation is host-side, the jitted episode is shared."""
        trace = self._finish_trace(
            generate_trace(np.asarray(self.min_lat),
                           arrivals or self.arrivals, rng))
        return trace, self.init_state(trace)

    def new_episodes(self, rng: np.random.Generator, batch: int,
                     arrivals: ArrivalConfig | None = None
                     ) -> tuple[Trace, State]:
        """Batched :meth:`new_episode`: all arrays gain a (batch,) axis."""
        traces = self._finish_trace(
            generate_traces(np.asarray(self.min_lat),
                            arrivals or self.arrivals, rng, batch))
        return traces, jax.vmap(self.init_state)(traces)

    def new_episodes_jax(self, key, batch: int,
                         arrivals: ArrivalConfig | None = None
                         ) -> tuple[Trace, State]:
        """Fully traceable :meth:`new_episodes`: traces drawn via
        ``jax.random`` (``generate_traces_jax``, vmapped over per-episode
        key splits), so a jitted training round can generate its own
        episodes on device — no per-round host trace loop.  ``batch``
        and ``arrivals`` must be static under jit; the NumPy path stays
        the oracle for the arrival-process semantics."""
        traces = self._finish_trace(
            generate_traces_jax(self.min_lat, arrivals or self.arrivals,
                                key, batch))
        return traces, jax.vmap(self.init_state)(traces)

    # ---------------- pure helpers (traceable) ----------------
    def deadline(self, state: State, trace: Trace):
        """Each job's current deadline: the trace's, or for re-entering
        jobs the TTFT deadline until the first token, the final one
        after it."""
        return state["dl"] if self.reenters else trace["deadline"]

    def pass_deadline(self, state: State, trace: Trace):
        """Each job's order key: the deadline of the token its current
        pass yields (the trace's deadline for a one-pass job)."""
        if not self.reenters:
            return trace["deadline"]
        k = (trace["n_out"] - 1 - state["passes_left"]).astype(jnp.float32)
        return jnp.where(state["t_first"] < INF / 2,
                         state["t_first"] + trace["tpot"] * k, state["dl"])

    def pass_end(self, state: State, trace: Trace):
        """One past the last row of each job's current pass."""
        if not self.reenters:
            return trace["njl"]
        return jnp.where(state["nls"] < trace["ds"], trace["ds"],
                         trace["njl"])

    def mark_drops(self, state: State, trace: Trace, now) -> State:
        overdue = ((trace["arrival"] <= now) & ~state["done"]
                   & ~state["missed"] & (self.deadline(state, trace) < now))
        return {**state, "missed": state["missed"] | overdue}

    def build_slots(self, state: State, trace: Trace, cutoff) -> Slots:
        """Pack uncommitted layers of active jobs into R slots by deadline
        (of a re-entering job, the layers of its current pass only, by
        the deadline of the token it yields)."""
        cfg, R, J = self.cfg, self.cfg.max_rq, self.cfg.max_jobs
        deadline = self.pass_deadline(state, trace)
        active = ((trace["arrival"] <= cutoff) & ~state["done"]
                  & ~state["missed"])
        rem = jnp.where(active, self.pass_end(state, trace) - state["nls"], 0)
        key = jnp.where(active & (rem > 0), deadline, INF)
        order = jnp.argsort(key)                       # (J,)
        rem_o = rem[order]
        cum = jnp.cumsum(rem_o)
        starts = cum - rem_o
        total = cum[-1]
        i = jnp.arange(R)
        k = jnp.searchsorted(cum, i, side="right")
        k = jnp.clip(k, 0, J - 1)
        valid = i < jnp.minimum(total, R)
        job = jnp.where(valid, order[k], 0)
        layer = jnp.where(valid, state["nls"][job] + (i - starts[k]), 0)
        layer = jnp.clip(layer, 0, self.lmax - 1)
        prev_same = jnp.concatenate(
            [jnp.array([False]), (job[1:] == job[:-1]) & valid[1:] & valid[:-1]])
        dep = jnp.where(prev_same, i - 1, -1)
        model = trace["model"][job]
        ready_rel = jnp.where(
            dep < 0, jnp.maximum(0.0, state["jready"][job] - state["t"]), 0.0)
        cost_all = self.lat[model, layer]              # (R, M)
        bw_all = self.bw[model, layer]
        en_all = self.en[model, layer]
        # in-episode churn (rows injected by `period` when a schedule is
        # threaded — repro.sim.churn): a slowed SA advertises scaled
        # busy-times, a throttled SA scaled bus demand, an invalid SA a
        # saturated poison cost.  All three are bit-exact identities at
        # the no-op row (x * 1.0 / where(True, x, _)), so the zero-churn
        # program reproduces the static path bit-for-bit.
        lat_mult = state.get("lat_mult")
        if lat_mult is not None:
            cost_all = cost_all * lat_mult[None, :]
        bw_mult = state.get("bw_mult")
        if bw_mult is not None:
            bw_all = bw_all * bw_mult[None, :]
        sa_valid = state.get("sa_valid")
        if sa_valid is not None:
            cost_all = jnp.where(sa_valid[None, :], cost_all,
                                 CHURN_POISON_US)
        zero = jnp.where(valid[:, None], 1.0, 0.0)
        return dict(job=job, layer=layer, valid=valid, dep=dep,
                    ready_rel=ready_rel * valid,
                    cost_all=cost_all * zero, bw_all=bw_all * zero,
                    en_all=en_all * zero, model=model,
                    deadline=deadline[job], q=trace["q"][job],
                    arrival=trace["arrival"][job])

    def encode(self, slots: Slots, state: State):
        """-> (feats (R+1, F), mask (R+1,)) with the primer at t=0."""
        cfg = self.cfg
        tsn = cfg.t_s_us * cfg.ttd_norm_periods
        t = state["t"]
        model_n = (slots["model"] + 1.0) / self.num_models
        layer_n = (slots["layer"] + 1.0) / self.lmax
        ttd = jnp.clip((slots["deadline"] - t) / tsn, -1.0, 1.0)
        wait = jnp.clip((t - slots["arrival"]) / tsn, 0.0, 1.0)
        c_n = jnp.clip(slots["cost_all"] / cfg.t_s_us, 0.0, 2.0) / 2.0
        b_n = slots["bw_all"] / cfg.bandwidth_gbps
        v = slots["valid"].astype(jnp.float32)
        rows = jnp.concatenate(
            [model_n[:, None] * v[:, None], layer_n[:, None] * v[:, None],
             ttd[:, None] * v[:, None], wait[:, None] * v[:, None],
             c_n * v[:, None], b_n * v[:, None]], axis=-1)
        sa_busy = jnp.maximum(0.0, state["sa_free"] - t) / cfg.t_s_us
        primer = jnp.concatenate(
            [jnp.zeros((BUSY_COL,)),
             jnp.clip(sa_busy, 0.0, BUSY_CAP) / BUSY_CAP,
             jnp.zeros((self.num_sas,))])[None, :]
        feats = jnp.concatenate([primer, rows], axis=0)
        mask = jnp.concatenate([jnp.array([True]), slots["valid"]])
        return feats.astype(jnp.float32), mask

    def simulate(self, state: State, slots: Slots, prio, sa_choice,
                 commit_only: bool = False, return_iters: bool = False):
        """Engine run for the current RQ: ``(start, finish, cost, bw, en,
        sa)``, times relative to t, and the engine's loop iterations
        last when ``return_iters``.

        ``commit_only=True`` stops the event loop once every SJ starting
        inside the period has finished (``stop_start_after=T_s``) — the
        committed-path results are bit-identical, late starters keep
        ``finish = INF``.  Only valid for consumers that ignore
        uncommitted SJs (the serving tick; the training path needs every
        finish for the reward).
        """
        sa = jnp.clip(sa_choice.astype(jnp.int32), 0, self.num_sas - 1)
        # one-hot contraction instead of take_along_axis: batched gathers
        # serialize on XLA CPU (see sim/engine.py), (R, M) selects don't
        sahot = sa[:, None] == jnp.arange(self.num_sas)[None, :]
        take = lambda x: jnp.sum(jnp.where(sahot, x, 0.0), axis=1)
        cost = take(slots["cost_all"])
        bw = take(slots["bw_all"])
        sa_free_rel = jnp.maximum(0.0, state["sa_free"] - state["t"])
        start, fin, *iters = simulate_jax(
            slots["valid"], sa, prio, cost, bw, slots["dep"],
            slots["ready_rel"], sa_free_rel,
            jnp.float32(self.cfg.bandwidth_gbps), num_sas=self.num_sas,
            stop_start_after=(self.cfg.t_s_us if commit_only else None),
            return_iters=return_iters)
        return (start, fin, cost, bw, take(slots["en_all"]), sa, *iters)

    def reward(self, state: State, slots: Slots, fin):
        cfg = self.cfg
        t = state["t"]
        ran = slots["valid"] & (fin < INF / 2)
        abs_f = t + fin
        delta = jnp.where(fin < cfg.t_s_us, 1.0, cfg.delta)
        hit = abs_f <= slots["deadline"]
        A = jnp.where(hit, cfg.alpha, -cfg.beta)
        slack = jnp.clip((slots["deadline"] - abs_f)
                         / jnp.maximum(slots["q"], 1e-3), -3.0, 3.0)
        r_slot = delta * (A + cfg.gamma_r * slack)
        r_unran = cfg.delta * (-cfg.beta - 3.0 * cfg.gamma_r)
        return jnp.sum(jnp.where(slots["valid"],
                                 jnp.where(ran, r_slot, r_unran), 0.0))

    def commit(self, state: State, trace: Trace, slots: Slots,
               start, fin, en, sa) -> State:
        cfg, J, M = self.cfg, self.cfg.max_jobs, self.num_sas
        t = state["t"]
        # an SJ commits iff it *started* inside the period; the finite-fin
        # guard protects state from a (bounded-iteration) engine anomaly
        committed = (slots["valid"] & (start < cfg.t_s_us - 1e-6)
                     & (fin < INF / 2))
        job = slots["job"]
        # per-job / per-SA reductions via one-hot masked max/sum instead
        # of segment_* (XLA CPU scatters serialize under vmap — see
        # sim/engine.py); R x J = 96 x 64 bools is tiny.
        jobhot = job[:, None] == jnp.arange(J)[None, :]          # (R, J)
        ncom = jnp.sum(committed[:, None] & jobhot, axis=0,
                       dtype=jnp.int32)
        fin_c = jnp.where(committed, fin, -INF)
        jlast = jnp.max(jnp.where(jobhot, fin_c[:, None], -INF), axis=0)
        nls = state["nls"] + ncom
        jready = jnp.where(ncom > 0, t + jlast, state["jready"])
        arrived = trace["arrival"] <= t
        if self.reenters:
            with jax.named_scope("env.reenter"):
                nls, newly_done, fjob, hit, lm = self._reenter(
                    state, trace, nls, ncom, jready, arrived)
        else:
            lm = {}
            newly_done = arrived & ~state["done"] & ~state["missed"] \
                & (nls >= trace["njl"]) & (ncom > 0)
            fjob = jnp.where(newly_done, jready, state["fjob"])
            hit = state["hit"] | (newly_done & (fjob <= trace["deadline"]))
        done = state["done"] | newly_done
        energy = state["energy"] + jnp.sum(jnp.where(committed, en, 0.0))
        sahot = sa[:, None] == jnp.arange(M)[None, :]            # (R, M)
        fin_sa = jnp.max(jnp.where(sahot, fin_c[:, None], -INF), axis=0)
        sa_free = jnp.where(fin_sa > -INF / 2,
                            jnp.maximum(state["sa_free"], t + fin_sa),
                            state["sa_free"])
        return {**state, "nls": nls, "jready": jready, "done": done,
                "hit": hit, "fjob": fjob, "energy": energy,
                "sa_free": sa_free, "t": t + cfg.t_s_us, **lm}

    def _reenter(self, state: State, trace: Trace, nls, ncom, jready,
                 arrived):
        """Pass ends of re-entering jobs at commit: ``(nls, newly_done,
        fjob, hit, updates)``.  A job whose current pass committed its
        last row goes back to ``decode_start`` while it has passes left,
        and is done after its last; the first pass's end is its first
        token (``t_first``), from which its final deadline counts.  A
        done job hits iff its first token met the TTFT deadline and its
        last the final one.  ``updates``: the new ``passes_left``,
        ``t_first`` and ``dl``."""
        ds = trace["ds"]
        ended = (arrived & ~state["done"] & ~state["missed"] & (ncom > 0)
                 & (nls >= self.pass_end(state, trace)))
        first = ended & (state["nls"] < ds)
        t_first = jnp.where(first, jready, state["t_first"])
        final = jready + trace["tpot"] * (trace["n_out"] - 1).astype(
            jnp.float32)
        dl = jnp.where(first, final, state["dl"])
        again = ended & (state["passes_left"] > 0)
        newly_done = ended & ~again
        fjob = jnp.where(newly_done, jready, state["fjob"])
        hit = state["hit"] | (newly_done & (t_first <= trace["deadline"])
                              & (fjob <= dl))
        passes_left = jnp.where(again, state["passes_left"] - 1,
                                state["passes_left"])
        return (jnp.where(again, ds, nls), newly_done, fjob, hit,
                dict(passes_left=passes_left, t_first=t_first, dl=dl))

    def pass_counts(self, old: State, new: State, trace: Trace):
        """``(decode passes, first tokens)`` that ended between two
        states of the same jobs (a period's commit)."""
        first = (old["t_first"] >= INF / 2) & (new["t_first"] < INF / 2)
        ended = ((new["passes_left"] < old["passes_left"])
                 | (new["done"] & ~old["done"]))
        decode = ended & (old["nls"] >= trace["ds"])
        return (jnp.sum(decode, dtype=jnp.int32),
                jnp.sum(first, dtype=jnp.int32))

    # ---------------- one full period (traceable) ----------------
    def period(self, state: State, trace: Trace, act_fn,
               commit_only: bool = False, churn=None,
               engine_iters: bool = False):
        """act_fn(feats, mask, slots, state) -> (a (R,G), prio (R,), sa (R,)).

        Returns (new_state, transition dict, info dict).
        ``commit_only=True`` runs the engine with the period-boundary
        start horizon (see :meth:`simulate`) — valid only when the
        caller discards the transition (its reward/``s2`` need every
        finish time); ``new_state`` and ``info["committed"]`` are
        bit-identical either way.  ``engine_iters=True`` adds the
        engine's loop iterations to ``info`` (the serving tick's
        telemetry block counts them).

        The device work is named for the profiler: ``env.slots``
        (:meth:`build_slots`), ``env.act`` (``act_fn``),
        ``env.engine`` (:meth:`simulate`) and, where jobs re-enter,
        ``env.reenter`` (their pass ends at commit, and ``info``'s
        ``passes``/``first_tokens``); a named scope changes only the
        instructions' ``op_name`` metadata.

        ``churn``: optional per-period churn row ``dict(valid (M,),
        lat_mult (M,), bw_mult (M,))`` (one slice of a compiled
        ``repro.sim.churn`` schedule).  Injected into the state seen by
        :meth:`build_slots` and ``act_fn`` as ``sa_valid`` /
        ``lat_mult`` / ``bw_mult`` — policies read ``state.get(
        "sa_valid")`` to mask allocation — and stripped from the
        returned state so the scan carry keeps its static structure.
        """
        if churn is not None:
            state = {**state, "sa_valid": churn["valid"],
                     "lat_mult": churn["lat_mult"],
                     "bw_mult": churn["bw_mult"]}
        t = state["t"]
        state = self.mark_drops(state, trace, t)
        with jax.named_scope("env.slots"):
            slots = self.build_slots(state, trace, cutoff=t)
        feats, mask = self.encode(slots, state)
        with jax.named_scope("env.act"):
            a, prio, sa_choice = act_fn(feats, mask, slots, state)
        with jax.named_scope("env.engine"):
            start, fin, cost, bw, en, sa, *iters = self.simulate(
                state, slots, prio, sa_choice, commit_only=commit_only,
                return_iters=engine_iters)
        r = self.reward(state, slots, fin)
        new_state = self.commit(state, trace, slots, start, fin, en, sa)
        # residual-RQ-only next state (paper Sec. 4.2): cutoff at *old* t
        ns = self.mark_drops(new_state, trace, new_state["t"])
        with jax.named_scope("env.slots"):
            rslots = self.build_slots(ns, trace, cutoff=t)
        feats2, mask2 = self.encode(rslots, ns)
        trans = dict(s=feats, mask=mask, a=a, r=r, s2=feats2, mask2=mask2)
        info = dict(reward=r,
                    committed=jnp.sum(slots["valid"] & (start < self.cfg.t_s_us)))
        if engine_iters:
            info["engine_iters"] = iters[0]
        if self.reenters:
            with jax.named_scope("env.reenter"):
                info["passes"], info["first_tokens"] = self.pass_counts(
                    state, new_state, trace)
        if churn is not None:
            new_state = {k: v for k, v in new_state.items()
                         if k not in _CHURN_KEYS}
        return new_state, trans, info

    # ---------------- whole episode (traceable, vmap-able) ----------------
    def episode(self, state: State, trace: Trace, act_fn, aux=None,
                key=None, collect: bool = True, churn=None):
        """Run all ``cfg.periods`` periods inside one ``jax.lax.scan``.

        act_fn(feats, mask, slots, state, key, aux) -> (a, prio, sa):

        - ``key`` is that period's PRNG key — ``key`` (one key per
          episode) is split into ``periods`` per-period keys inside the
          trace, so stochastic searchers (MAGMA's in-period GA) draw
          fresh randomness every period with zero host syncs.  When the
          episode ``key`` is None a constant dummy is threaded instead
          (deterministic policies and heuristics ignore it).
        - ``aux`` is that period's slice of the ``aux`` scan input with
          leading dim ``periods`` (the policy path's pre-drawn
          exploration noise — RNG inside the period scan costs real
          time on CPU, so the whole episode block is drawn up front).

        - ``churn`` is an optional compiled churn schedule
          ``dict(valid (periods, M) bool, lat_mult / bw_mult
          (periods, M) f32)`` from ``repro.sim.churn`` — pure trace
          data scanned alongside ``keys``/``aux`` (the ``bind_tables``
          no-recompile trick applied to fleet health), sliced into the
          per-period rows :meth:`period` injects.  ``None`` leaves the
          static-fleet program untouched.

        Entirely traceable: jit it once and ``vmap`` over stacked
        (state, trace, key, aux) for device-resident batched rollouts.
        The final drop pass and episode metrics run inside the trace.

        Returns (final_state, transitions, infos, metrics) where
        transitions/infos are stacked over the leading periods axis
        (transitions is ``{}`` when ``collect=False``).
        """
        periods = self.cfg.periods
        if aux is None:
            aux = jnp.zeros((periods,))
        keys = (jax.random.split(key, periods) if key is not None
                else jnp.zeros((periods, 2), jnp.uint32))

        def step(st, xs):
            k, a, c = xs if churn is not None else (*xs, None)
            new_st, trans, info = self.period(
                st, trace,
                lambda feats, mask, slots, s: act_fn(feats, mask, slots,
                                                     s, k, a),
                churn=c)
            return new_st, ((trans if collect else {}), info)

        xs = (keys, aux) if churn is None else (keys, aux, churn)
        final, (transitions, infos) = jax.lax.scan(step, state, xs)
        final = self.mark_drops(final, trace, final["t"])
        return final, transitions, infos, self.metrics(final, trace)

    # ---------------- episode metrics ----------------
    def metrics(self, state: State, trace: Trace) -> dict[str, jnp.ndarray]:
        counted = state["done"] | state["missed"]
        hits = jnp.sum(state["hit"])
        arrived = jnp.sum(trace["arrival"] < INF / 2)
        return dict(
            hits=hits, counted=jnp.sum(counted), arrived=arrived,
            sla_rate=hits / jnp.maximum(jnp.sum(counted), 1),
            energy_uj=state["energy"],
        )
