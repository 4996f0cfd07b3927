"""Episode rollout runners: batched device-resident collection + eval.

Architecture (device-resident pipeline PR): the hot path is
``make_rollout_batch`` / ``make_evaluate_batch`` — one jitted call runs
``batch`` episodes end-to-end on device: ``jax.lax.scan`` over periods
(``SchedulingEnv.episode``) inside ``jax.vmap`` over stacked
traces/states, with the final drop pass and metrics computed inside the
trace.  Collection returns stacked transitions shaped
``(batch, periods, ...)``, ready for the device replay buffer's
``add_batch`` — no per-period host round-trips, no Python loop.

The legacy per-period runners (``make_policy_period`` /
``make_baseline_period`` / ``run_episode`` / ``evaluate``) are kept as
thin compatibility wrappers; ``benchmarks/rollout_throughput.py``
measures the two paths against each other.

Compiled runners are cached per environment instance (the jit cache is
keyed on the closed-over env/policy config), so repeated calls from
training loops and benchmarks do not re-trace.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from repro.core import policy as P
from repro.sim.env import SchedulingEnv

Metrics = dict[str, jnp.ndarray]


# --------------------------------------------------------------------------
# batched device-resident runners (the new hot path)
# --------------------------------------------------------------------------
def _policy_act_fn(params, pcfg: P.PolicyConfig):
    """Per-period actor; ``noise`` (the per-period ``aux`` scan input)
    is the pre-drawn exploration noise — RNG inside the period scan
    costs real time on CPU, so the whole episode block is drawn in one
    call.  The per-period ``key`` is ignored (deterministic actor).

    Under in-episode churn (``repro.sim.churn``) the env's period step
    injects a per-period ``sa_valid`` row into the state: the SA argmax
    masks invalid SAs to ``-inf`` so a failed (or not-yet-joined) SA is
    never selected.  With an all-valid row the mask is the bit-exact
    identity; without churn the branch is absent from the trace."""
    def act_fn(feats, mask, slots, st, key, noise):
        a = jnp.clip(P.actor_apply(params, pcfg, feats, mask) + noise,
                     -1.0, 1.0)
        prio = a[:, 0]
        logits = a[:, 1:]
        sv = st.get("sa_valid")
        if sv is not None:
            logits = jnp.where(sv, logits, -jnp.inf)
        sa = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return a, prio, sa
    return act_fn


def _runner_cache(env: SchedulingEnv) -> dict:
    cache = getattr(env, "_runner_cache", None)
    if cache is None:
        cache = {}
        env._runner_cache = cache
    return cache


def collect_episodes(env: SchedulingEnv, pcfg: P.PolicyConfig, params,
                     states, traces, key, sigma, collect: bool = True,
                     act_fn=None, act_dim: int | None = None, churn=None):
    """Traceable batched policy collection: draw the whole batch's
    exploration-noise block from ``key`` and run every episode through
    ``env.episode`` under ``vmap``.  The single definition of the
    noise scheme + episode wiring shared by the standalone collector
    (:func:`make_rollout_batch`), the fused training round
    (``repro.core.train``), and — via ``act_fn``/``act_dim`` overrides —
    the descriptor-conditioned generalist policy
    (``repro.core.generalist``), whose action space is ``1 + M_max``
    rather than the env's ``1 + M``.  ``churn`` optionally threads a
    batched compiled churn schedule (``(batch, periods, M)`` leaves,
    see ``repro.sim.churn``) into each episode.  Returns the vmapped
    episode outputs ``(final_states, transitions, infos, metrics)``."""
    batch = states["t"].shape[0]
    noise = sigma * jax.random.normal(
        key, (batch, env.cfg.periods, env.cfg.max_rq,
              act_dim or env.act_dim))
    act_fn = act_fn or _policy_act_fn(params, pcfg)

    def one(state, trace, ep_noise, ch=None):
        return env.episode(state, trace, act_fn,
                           aux=ep_noise, collect=collect, churn=ch)

    if churn is None:
        return jax.vmap(one)(states, traces, noise)
    return jax.vmap(one)(states, traces, noise, churn)


def make_rollout_batch(env: SchedulingEnv, pcfg: P.PolicyConfig,
                       collect: bool = True, devices=None):
    """Jitted batched collector.

    Returns ``rollout_batch(params, states, traces, key, sigma)`` ->
    (final_states, transitions, infos, metrics), everything stacked over
    the leading batch axis (transitions over (batch, periods, ...));
    ``key`` is a single PRNG key — the whole batch's exploration noise
    is drawn in one vectorized call.

    With ``devices`` (a list of >1 JAX devices) the batch additionally
    shards over a 1-D device mesh via ``shard_map`` — episodes are
    independent, so experience collection is embarrassingly
    data-parallel: the leading batch axis maps with
    ``PartitionSpec("dev")``, no collective anywhere (batch must divide
    evenly by the device count).
    """
    ndev = len(devices) if devices else 1
    key_ = ("rollout_batch", pcfg, collect, ndev)
    cache = _runner_cache(env)
    if key_ in cache:
        return cache[key_]

    if ndev <= 1:
        @jax.jit
        def rollout_batch(params, states, traces, key, sigma):
            return collect_episodes(env, pcfg, params, states, traces,
                                    key, sigma, collect)
    else:
        mesh = Mesh(np.asarray(devices), ("dev",))
        spec, rep = PartitionSpec("dev"), PartitionSpec()

        def _body(params, states, traces, keys, sigma):
            # per-device shard: (batch/ndev, ...) rows, one folded key
            return collect_episodes(env, pcfg, params, states, traces,
                                    keys[0], sigma, collect)

        # check_vma=False: every output carries the sharded batch axis
        _srun = jax.jit(jax.shard_map(
            _body, mesh=mesh, in_specs=(rep, spec, spec, spec, rep),
            out_specs=spec, check_vma=False))

        def rollout_batch(params, states, traces, key, sigma):
            batch = states["t"].shape[0]
            if batch % ndev:
                raise ValueError(f"batch {batch} not divisible by "
                                 f"{ndev} devices")
            return _srun(params, states, traces,
                         jax.random.split(key, ndev), sigma)

    cache[key_] = rollout_batch
    return rollout_batch


def make_evaluate_batch(env: SchedulingEnv, pcfg: P.PolicyConfig,
                        churn: bool = False):
    """Jitted batched evaluator (no noise, no transition collection).

    Returns ``eval_fn(params, states, traces)`` -> metrics stacked over
    the batch axis.  With ``churn=True`` the runner takes an extra
    trailing argument — a batched compiled churn schedule
    (``(batch, periods, M)`` leaves) — and is cached separately: the
    churn-enabled program scans extra ``xs``, so the two variants are
    distinct compiles.
    """
    key_ = ("evaluate_batch", pcfg, churn)
    cache = _runner_cache(env)
    if key_ in cache:
        return cache[key_]

    if churn:
        @jax.jit
        def eval_fn(params, states, traces, churn_scheds) -> Metrics:
            def one(state, trace, ch):
                *_, metrics = env.episode(
                    state, trace, _policy_act_fn(params, pcfg),
                    collect=False, churn=ch)
                return metrics
            return jax.vmap(one)(states, traces, churn_scheds)
    else:
        @jax.jit
        def eval_fn(params, states, traces) -> Metrics:
            def one(state, trace):
                *_, metrics = env.episode(
                    state, trace, _policy_act_fn(params, pcfg),
                    collect=False)
                return metrics
            return jax.vmap(one)(states, traces)

    cache[key_] = eval_fn
    return eval_fn


def make_baseline_episode_batch(env: SchedulingEnv, baseline_fn: Callable,
                                churn: bool = False):
    """Jitted batched episode runner for a baseline scheduler.

    ``baseline_fn(slots, state, env, key)`` — the one-shot heuristics
    ignore ``key``; MAGMA's scan-fused GA (``make_magma_baseline``)
    consumes it, which is what lets whole GA episodes run as one device
    call.  Returns ``eval_fn(states, traces, keys=None, *, seeds=None)``
    where ``keys`` is one PRNG key per episode (split per period inside
    the trace); when ``keys`` is omitted they are derived from the
    caller's episode ``seeds`` (``PRNGKey(seed)`` each, matching
    ``evaluate_batch_baseline``) so stochastic baselines stay
    correlated with the traces those same seeds generated — the old
    fallback folded ``PRNGKey(0)`` by batch *index*, silently
    decorrelating the GA's randomness from the episode seeds.

    With ``churn=True`` the runner takes a batched compiled churn
    schedule via the ``churn_scheds`` keyword (cached as a separate
    compile).  The heuristics need no masking of their own: an invalid
    SA advertises the saturated poison cost, which their greedy
    score-argmin avoids whenever any valid SA can take the slot.
    """
    key_ = ("baseline_batch", baseline_fn, churn)
    cache = _runner_cache(env)
    if key_ in cache:
        return cache[key_]

    if churn:
        @jax.jit
        def _eval(states, traces, keys, churn_scheds) -> Metrics:
            def one(state, trace, key, ch):
                def act_fn(feats, mask, slots, st, k, aux):
                    return baseline_fn(slots, st, env, k)
                *_, metrics = env.episode(state, trace, act_fn, key=key,
                                          collect=False, churn=ch)
                return metrics
            return jax.vmap(one)(states, traces, keys, churn_scheds)
    else:
        @jax.jit
        def _eval(states, traces, keys) -> Metrics:
            def one(state, trace, key):
                def act_fn(feats, mask, slots, st, k, aux):
                    return baseline_fn(slots, st, env, k)
                *_, metrics = env.episode(state, trace, act_fn, key=key,
                                          collect=False)
                return metrics
            return jax.vmap(one)(states, traces, keys)

    def eval_fn(states, traces, keys=None, *, seeds=None,
                churn_scheds=None) -> Metrics:
        if keys is None:
            if seeds is None:
                raise ValueError(
                    "pass per-episode PRNG `keys`, or the episode "
                    "`seeds` the traces were generated from")
            keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        if churn:
            return _eval(states, traces, keys, churn_scheds)
        return _eval(states, traces, keys)

    cache[key_] = eval_fn
    return eval_fn


def stack_episodes(env: SchedulingEnv, seeds, arrivals=None):
    """One fresh episode per seed, tree-stacked over the batch axis.

    ``arrivals`` optionally overrides the env's arrival process (e.g. a
    scenario preset) — the jitted runners are unaffected, so one
    compiled evaluator serves every scenario cell of a sweep.
    """
    pairs = [env.new_episode(np.random.default_rng(int(s)), arrivals)
             for s in seeds]
    traces = jax.tree.map(lambda *xs: jnp.stack(xs), *[p[0] for p in pairs])
    states = jax.tree.map(lambda *xs: jnp.stack(xs), *[p[1] for p in pairs])
    return traces, states


def _eval_churn_schedules(env: SchedulingEnv, churn, seeds):
    """Deterministic per-seed eval schedules (``repro.sim.churn``).

    Drawn over the env's *real* SA count (``true_num_sas`` on a padded
    env) and compiled at its table width, so padded and unpadded rows
    of the same fleet see identical real-SA events per seed.
    """
    from repro.sim.churn import churn_schedules
    real = getattr(env, "true_num_sas", env.num_sas)
    return churn_schedules(churn, env.cfg.periods, real, seeds,
                           width=env.num_sas)


def evaluate_batch(env: SchedulingEnv, pcfg: P.PolicyConfig, params,
                   seeds, arrivals=None, churn=None) -> dict[str, float]:
    """Mean policy metrics across seeds, one jitted device call.

    ``churn`` optionally names a :class:`~repro.sim.churn.ChurnConfig`:
    each seed gets a deterministic compiled schedule (decorrelated from
    its arrival trace) threaded through the churn-enabled evaluator.
    """
    traces, states = stack_episodes(env, seeds, arrivals)
    if churn is None:
        metrics = make_evaluate_batch(env, pcfg)(params, states, traces)
    else:
        metrics = make_evaluate_batch(env, pcfg, churn=True)(
            params, states, traces, _eval_churn_schedules(env, churn, seeds))
    return {k: float(jnp.mean(v)) for k, v in metrics.items()}


def evaluate_batch_baseline(env: SchedulingEnv, baseline_fn: Callable,
                            seeds, arrivals=None,
                            churn=None) -> dict[str, float]:
    """Mean baseline metrics across seeds, one jitted call.

    Works for the one-shot heuristics and for scan-fused MAGMA alike:
    each episode gets ``PRNGKey(seed)``, split per period in-trace.
    ``churn`` threads per-seed schedules exactly like
    :func:`evaluate_batch`.
    """
    traces, states = stack_episodes(env, seeds, arrivals)
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    if churn is None:
        metrics = make_baseline_episode_batch(env, baseline_fn)(
            states, traces, keys)
    else:
        metrics = make_baseline_episode_batch(env, baseline_fn, churn=True)(
            states, traces, keys,
            churn_scheds=_eval_churn_schedules(env, churn, seeds))
    return {k: float(jnp.mean(v)) for k, v in metrics.items()}


# --------------------------------------------------------------------------
# legacy per-period runners (compatibility wrappers + the "before"
# datapoint for benchmarks/rollout_throughput.py)
# --------------------------------------------------------------------------
def make_policy_period(env: SchedulingEnv, pcfg: P.PolicyConfig):
    """Jitted one-period step with the RELMAS actor (exploration optional)."""

    @functools.partial(jax.jit, static_argnames=("sigma",))
    def period(params, state, trace, key, sigma: float = 0.0):
        def act_fn(feats, mask, slots, st):
            a = P.actor_apply(params, pcfg, feats, mask)
            if sigma > 0.0:
                a = jnp.clip(a + sigma * jax.random.normal(key, a.shape),
                             -1.0, 1.0)
            prio = a[:, 0]
            sa = jnp.argmax(a[:, 1:], axis=-1).astype(jnp.int32)
            return a, prio, sa
        return env.period(state, trace, act_fn)

    return period


def make_baseline_period(env: SchedulingEnv, baseline_fn: Callable,
                         jit: bool = True):
    """One-period step with a heuristic baseline (acts on raw slot data)."""

    def period(state, trace):
        def act_fn(feats, mask, slots, st):
            return baseline_fn(slots, st, env)
        return env.period(state, trace, act_fn)

    return jax.jit(period) if jit else period


def run_episode(env: SchedulingEnv, period_fn, rng: np.random.Generator,
                *, params=None, key=None, sigma: float = 0.0,
                collect: bool = False, arrivals=None):
    """Run one episode with the legacy per-period Python loop.

    Returns (metrics, transitions|None).  Prefer ``make_rollout_batch``
    / ``evaluate_batch`` — this path pays one dispatch + host sync per
    period and exists for compatibility and as the benchmark baseline.
    """
    trace, state = env.new_episode(rng, arrivals)
    transitions = [] if collect else None
    for _ in range(env.cfg.periods):
        if params is not None:
            key, sub = jax.random.split(key)
            state, trans, _ = period_fn(params, state, trace, sub, sigma=sigma)
        else:
            state, trans, _ = period_fn(state, trace)
        if collect:
            transitions.append(jax.tree.map(np.asarray, trans))
    # final drop pass so late jobs are counted
    state = env.mark_drops(state, trace, state["t"])
    metrics = {k: float(v) for k, v in env.metrics(state, trace).items()}
    return metrics, transitions


def evaluate(env: SchedulingEnv, period_fn, seeds, *, params=None,
             key=None) -> dict[str, float]:
    """Mean metrics across episodes with different arrival traces."""
    out: dict[str, list[float]] = {}
    for s in seeds:
        m, _ = run_episode(env, period_fn, np.random.default_rng(s),
                           params=params,
                           key=None if params is None else
                           jax.random.PRNGKey(int(s)))
        for k, v in m.items():
            out.setdefault(k, []).append(v)
    return {k: float(np.mean(v)) for k, v in out.items()}
