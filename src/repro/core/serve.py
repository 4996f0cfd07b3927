"""Single-dispatch serving ticks: the scheduler as a batched service.

The serving analogue of ``repro.core.train``'s fused training rounds:
where ``serving/service.py``'s host loop used to pay one dispatch per
period per stream (plus host-side request bookkeeping between), ONE
jitted, donated call now advances ``streams`` independent serving
queues a full scheduling period each:

    admit (masked scatter of up to K staged requests per stream)
      -> batched policy inference + contention sim (``env.period``:
         every pending sub-job of every tenant in one actor pass)
      -> retire (drain completed jobs into cumulative SLA accumulators,
         free their slots)

vmapped over the stream axis inside a single ``jax.jit`` with the queue
pytree donated — the device boundary is crossed once per tick: the
``(S, K)`` staging buffers go in, a compact fixed-shape completion
record comes out, packed for the host into one int32 array
(:func:`host_layout`) that the serving loop reads in one transfer.
Episode transitions are never materialized (the tick returns no
``trans``, XLA dead-code-eliminates the collection).

Act adapters reproduce the per-period reference paths *bit-for-bit* at
``sigma = 0``: the specialist matches ``rollout.make_policy_period``,
the generalist matches ``generalist.make_generalist_period`` (zero
noise through the same clip/mask pipeline), heuristics call the
``baselines`` functions unchanged — so a queue fed a replayed trace
retires the exact SLA numbers of ``MultiTenantService.
serve_episode_host`` on that trace (``tests/test_serving_batched.py``).

Works on any :class:`~repro.sim.env.SchedulingEnv`, including
:class:`~repro.core.generalist.env.PaddedEnv` (the generalist adapter
reads the env's ``descriptors``/``sa_mask``) and table-bound envs
(``bind_tables`` — tables are data to the tick like everywhere else).

Compiled ticks are cached per env instance, keyed on (kind, pcfg,
streams, K) exactly like the rollout runners.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import policy as P
from repro.core.rollout import _runner_cache
from repro.serving.queue import (queue_admit, queue_init, queue_metrics,
                                 queue_retire)
from repro.sim.env import SchedulingEnv
from repro.telemetry.metrics import counter_add, hist_add


def queue_init_batch(env: SchedulingEnv, streams: int,
                     telemetry: bool = False) -> dict:
    """``streams`` empty queues, tree-stacked over a leading (S,) axis.
    ``telemetry=True`` attaches the per-stream device telemetry block
    (see ``repro.serving.queue.queue_telemetry_init``)."""
    one = queue_init(env, telemetry=telemetry)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x, (streams,) + x.shape), one)


def specialist_act(pcfg: P.PolicyConfig):
    """Deterministic RELMAS actor — bit-identical to
    ``make_policy_period``'s act_fn at ``sigma = 0`` (no clip)."""
    def act(params, feats, mask, slots, st, key):
        a = P.actor_apply(params, pcfg, feats, mask)
        return a, a[:, 0], jnp.argmax(a[:, 1:], axis=-1).astype(jnp.int32)
    return act


def generalist_act(env, pcfg: P.PolicyConfig):
    """Descriptor-conditioned actor — bit-identical to
    ``make_generalist_period`` at ``sigma = 0`` (zero noise through the
    same clip + channel mask)."""
    from repro.core.generalist.features import generalist_act_fn
    desc, sa_mask = env.descriptors, env.sa_mask
    zero = jnp.zeros((env.cfg.max_rq, pcfg.act_dim))

    def act(params, feats, mask, slots, st, key):
        return generalist_act_fn(params, pcfg, desc, sa_mask)(
            feats, mask, slots, st, key, zero)
    return act


def baseline_act(env, baseline_fn):
    """Heuristic baselines act on raw slot data; ``params`` unused."""
    def act(params, feats, mask, slots, st, key):
        return baseline_fn(slots, st, env, key)
    return act


def _build_act(env, kind: str, pcfg, baseline_fn):
    if kind == "specialist":
        return specialist_act(pcfg)
    if kind == "generalist":
        return generalist_act(env, pcfg)
    if kind == "heuristic":
        if baseline_fn is None:
            raise ValueError("kind='heuristic' needs baseline_fn")
        return baseline_act(env, baseline_fn)
    raise ValueError(f"unknown serving policy kind {kind!r}")


def host_layout(env: SchedulingEnv) -> dict:
    """Columns of the tick's packed host record ``out["host"]``.

    ``out["host"]`` is one ``(S, C)`` int32 array holding every field the
    serving loop reads of a tick; this maps each name to its column (one
    value a stream) or its slice of ``max_jobs`` columns (one value a
    slot).  ``flags`` holds ``completed | hit << 1 | missed << 2``;
    ``finish_us`` and ``t_first`` hold float32 bit patterns.  The LM
    fields ``t_first`` and ``passes_left`` are packed only where the
    env's tenants re-enter.
    """
    J = env.cfg.max_jobs
    widths = [("n_admitted", None), ("depth", None), ("flags", J),
              ("rid", J), ("finish_us", J)]
    if env.reenters:
        widths += [("t_first", J), ("passes_left", J)]
    layout, c = {}, 0
    for name, w in widths:
        layout[name] = c if w is None else slice(c, c + w)
        c += 1 if w is None else w
    return layout


def _host_pack(env: SchedulingEnv, out: dict) -> jnp.ndarray:
    """The ``(S, C)`` int32 host record of a vmapped tick's ``out``."""
    i32 = lambda x: x.astype(jnp.int32)
    bits = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    cols = dict(n_admitted=out["n_admitted"], depth=out["depth"],
                flags=i32(out["completed"]) | i32(out["hit"]) << 1
                | i32(out["missed"]) << 2,
                rid=out["rid"], finish_us=bits(out["finish_us"]))
    if env.reenters:
        cols.update(t_first=bits(out["t_first"]),
                    passes_left=out["passes_left"])
    return jnp.concatenate(
        [i32(cols[k]).reshape(cols[k].shape[0], -1)
         for k in host_layout(env)], axis=1)


def host_unpack(layout: dict, buf: np.ndarray) -> dict:
    """The fields of one host copy of ``out["host"]`` under ``out``'s own
    names, dtypes and values, as numpy views of ``buf`` (the flags
    decoded into the three bool masks)."""
    got = {k: buf[:, ix] for k, ix in layout.items()}
    flags = got.pop("flags")
    got.update(completed=(flags & 1) != 0, hit=(flags & 2) != 0,
               missed=(flags & 4) != 0,
               finish_us=got["finish_us"].view(np.float32))
    if "t_first" in got:
        got["t_first"] = got["t_first"].view(np.float32)
    return got


def make_serving_tick(env: SchedulingEnv, *, kind: str = "specialist",
                      pcfg: P.PolicyConfig | None = None,
                      baseline_fn=None, streams: int = 1):
    """Build the jitted single-dispatch scheduling tick.

    Returns ``tick(params, queues, adm, key) -> (queues, out)`` where
    ``queues`` is a :func:`queue_init_batch` pytree (DONATED — rebind to
    the return value), ``adm`` stacks per-stream ``pack_admissions``
    buffers over the leading (S,) axis, and ``out`` carries per-stream
    fixed-shape results: the retire record (``completed``/``rid``/
    ``hit``/``missed``/``finish_us``/``depth``, and ``t_first``/
    ``passes_left`` where the env's tenants re-enter), ``n_admitted``,
    the period's committed-SJ count, the post-tick sim clock ``t_us``,
    and ``host``: the fields the serving loop reads, packed into one
    int32 array (:func:`host_layout`, :func:`host_unpack`).
    ``params`` is the actor pytree (``None``-like empty for heuristics).
    """
    key_ = ("serving_tick", kind, pcfg, baseline_fn, streams)
    cache = _runner_cache(env)
    if key_ in cache:
        return cache[key_]
    act = _build_act(env, kind, pcfg, baseline_fn)

    def one(params, qs, adm, key):
        with jax.named_scope("serving.admit"):
            qs, n_adm = queue_admit(env, qs, adm)
        # commit_only: the tick discards the transition, so the engine
        # may stop at the period-boundary start horizon — committed
        # results (and therefore all queue state) stay bit-identical
        tele = "tele" in qs
        with jax.named_scope("serving.period"):
            state, _, info = env.period(
                qs["state"], qs["trace"],
                lambda feats, mask, slots, st: act(params, feats, mask,
                                                   slots, st, key),
                commit_only=True, engine_iters=tele)
        with jax.named_scope("serving.retire"):
            qs, out = queue_retire(env, {**qs, "state": state})
        out.update(n_admitted=n_adm, committed=info["committed"],
                   t_us=state["t"])
        if tele:
            # across-tick device aggregates: trace-time structural gate
            # (a queue without the block compiles the identical program,
            # so telemetry-off ticks stay bit-for-bit unchanged)
            with jax.named_scope("serving.telemetry"):
                t = qs["tele"]
                qs = {**qs, "tele": dict(
                    t, depth_hist=hist_add(t["depth_hist"], out["depth"]),
                    committed=counter_add(t["committed"],
                                          info["committed"]),
                    ticks=counter_add(t["ticks"], 1),
                    engine_iters=counter_add(t["engine_iters"],
                                             info["engine_iters"]))}
                if env.reenters:
                    qs["tele"].update(
                        passes=counter_add(t["passes"], info["passes"]),
                        first_tokens=counter_add(t["first_tokens"],
                                                 info["first_tokens"]))
            out["engine_iters"] = info["engine_iters"]
        return qs, out

    @functools.partial(jax.jit, donate_argnums=(1,))
    def tick(params, queues, adm, key):
        queues, out = jax.vmap(one, in_axes=(None, 0, 0, 0))(
            params, queues, adm, jax.random.split(key, streams))
        if "tele" in queues:
            # the batched engine loop runs until its slowest stream is
            # done: its trip count is the maximum over the streams
            with jax.named_scope("serving.telemetry"):
                t = queues["tele"]
                trips = jnp.max(out.pop("engine_iters"))
                queues = {**queues, "tele": dict(
                    t, engine_trips=counter_add(t["engine_trips"], trips))}
        with jax.named_scope("serving.host_pack"):
            # behind a barrier the pack reads the tick's results and
            # leaves the fusions that make them as they were
            out["host"] = _host_pack(env, jax.lax.optimization_barrier(out))
        return queues, out

    cache[key_] = tick
    return tick


def make_serving_flush(env: SchedulingEnv, streams: int = 1):
    """Jitted end-of-stream drain: a final drop pass at the current sim
    time (the batched twin of the reference path's closing
    ``mark_drops``), one last retire, and the cumulative metrics.

    Returns ``flush(queues) -> (queues, out)``; ``out`` is the retire
    record plus :func:`queue_metrics` fields, everything stacked over
    the stream axis.  Queues are donated like the tick's.
    """
    key_ = ("serving_flush", streams)
    cache = _runner_cache(env)
    if key_ in cache:
        return cache[key_]

    def one(qs):
        state = env.mark_drops(qs["state"], qs["trace"], qs["state"]["t"])
        qs, out = queue_retire(env, {**qs, "state": state})
        out.update(queue_metrics(qs))
        if "tele" in qs:
            # surface the device telemetry block as flat leaves the
            # host can serialize (same tele_* convention as training)
            out.update(
                tele_depth_hist=qs["tele"]["depth_hist"]["counts"],
                tele_depth_edges=qs["tele"]["depth_hist"]["edges"],
                tele_committed=qs["tele"]["committed"],
                tele_ticks=qs["tele"]["ticks"],
                tele_engine_iters=qs["tele"]["engine_iters"],
                tele_engine_trips=qs["tele"]["engine_trips"])
            if env.reenters:
                out.update(tele_passes=qs["tele"]["passes"],
                           tele_first_tokens=qs["tele"]["first_tokens"])
        return qs, out

    @functools.partial(jax.jit, donate_argnums=(0,))
    def flush(queues):
        return jax.vmap(one)(queues)

    cache[key_] = flush
    return flush
