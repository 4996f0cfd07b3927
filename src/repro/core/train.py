"""Fused RELMAS training rounds: one dispatch per round — or per chunk.

The last structural host<->device boundary in the training pipeline
(after the device-resident rollout of PR 1 and the scan-fused MAGMA of
PR 2) was the round loop itself: per-episode NumPy trace generation,
a separate dispatch each for rollout / replay write / update scan, an
un-donated O(capacity) replay copy per write, and a host sync per
round for sigma decay + logging.  This module removes all of it:

- :func:`make_train_round` builds ONE jitted, donated function that
  runs a full training round on device: ``jax.random`` trace
  generation (``SchedulingEnv.new_episodes_jax``) -> batched rollout
  (``lax.scan`` over periods inside ``vmap`` over episodes, with
  exploration noise drawn in-trace from the round key) -> replay ring
  write (``replay_add``, aliased in place via donation) -> ``K`` DDPG
  updates (``ddpg_update_rounds``, gated by ``do_update`` for warmup)
  -> on-device sigma decay.  Replay buffer and ``DDPGState`` are both
  donated: the two biggest allocations in the program update in place.

- :func:`make_train_rounds` wraps the round body in ``jax.lax.scan``
  over ``R`` rounds: a whole checkpoint/eval chunk of training becomes
  a single dispatch, returning per-round metrics stacked over the
  round axis so the host pays one transfer per chunk.

- :func:`train_rounds_host` is the per-round host loop over the SAME
  jitted round (same per-round keys): the numerical parity reference
  for the fused scan (``tests/test_train_fused.py``).  The throughput
  "before" arm in ``benchmarks/rollout_throughput.py --only
  train_throughput`` instead reproduces the *pre-PR* driver loop
  (NumPy trace-gen, separate un-donated dispatches, per-round syncs).

- :func:`make_sharded_train_rounds` shards the fused chunk over an
  explicit 1-D :class:`jax.sharding.Mesh` (named axis
  :data:`MESH_AXIS`) as ``jit``-of-``shard_map``: the collection half
  (trace gen -> episode scan) splits the episode batch embarrassingly
  across the mesh, each device owns a donated **double-buffered**
  replay ring pair (``repro.core.replay.replay_pair_*``) so round
  ``t``'s update sampling reads a different buffer than round ``t``'s
  collection writes, and the DDPG update consumes the **global**
  experience pool: every device samples its local read ring and the
  sampled rows are ``all_gather``'d along the axis
  (``replay_sample_global``), so the replicated update runs the
  identical plain step on the identical union-pool batch — replicas
  stay bit-identical with no gradient collective.  Per-round keys fold
  in the device index (:func:`shard_round_keys`) for decorrelated
  exploration streams; ``--devices 1`` in the driver routes to the
  plain :func:`make_train_rounds` path, which stays the numerical
  parity oracle.  :func:`sharded_rounds_reference` is the same
  per-device body under ``vmap`` (same ``axis_name`` collectives) —
  the single-device oracle.  (The PR 6 ``pmap`` arm served one
  migration-window release as the cross-implementation parity oracle
  and has been retired; the pmap CI lint in ``scripts/ci.sh`` now
  holds unconditionally.)

Every round maker accepts an optional ``churn``
(:class:`~repro.sim.churn.ChurnConfig`): the round splits one extra
key and draws a fresh batched churn schedule on device
(``churn_schedules_jax``) for its episode batch, so the policy trains
under fleet faults / throttles / joins exactly as it is evaluated.
``None`` (default) leaves the static-fleet program byte-identical.

Donation contract: the ``state`` and ``buf`` arguments of the returned
callables are consumed — always rebind to the returned values (the
driver in ``launch/rl_train.py`` does).  ``sigma`` stays a device
scalar across rounds; per-round ``keys`` should be derived by
``fold_in`` from a global round index so checkpoint resume replays the
identical stream (see ``round_keys``).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core import ddpg as D
from repro.core.replay import replay_add, replay_pair_step
from repro.core.rollout import _runner_cache, collect_episodes
from repro.sim.churn import churn_schedules_jax
from repro.sim.env import SchedulingEnv
from repro.telemetry.metrics import (ROUND_TELE_COUNTS, ROUND_TELE_GAUGES,
                                     round_telemetry)

Metrics = dict[str, jnp.ndarray]

# update-info keys mirrored by the warmup (no-update) branch of the
# round body — must match ddpg_update's info dict exactly
INFO_KEYS = ("critic_loss", "actor_loss", "q_mean", "target_mean")


def round_keys(seed: int, start_round: int, num_rounds: int) -> jnp.ndarray:
    """Per-round PRNG keys (num_rounds, 2) folded from the global round
    index, so a driver resuming at ``start_round`` draws the identical
    stream the uninterrupted run would have."""
    base = jax.random.PRNGKey(seed)
    return jax.vmap(lambda i: jax.random.fold_in(base, i))(
        jnp.arange(start_round, start_round + num_rounds))


def shard_round_keys(keys: jnp.ndarray, num_devices: int) -> jnp.ndarray:
    """Per-device per-round keys (num_devices, R, 2): each round key from
    :func:`round_keys` additionally folds in the device index, so the
    D exploration/trace streams of a sharded round are decorrelated
    from each other while staying a pure function of (seed, round,
    device) — resume at any round count or device count replays the
    same per-device stream."""
    return jax.vmap(
        lambda d: jax.vmap(lambda k: jax.random.fold_in(k, d))(keys))(
            jnp.arange(num_devices))


def _round_body(env: SchedulingEnv, dcfg: D.DDPGConfig, *,
                batch_episodes: int, num_updates: int, batch_size: int,
                sigma_min: float, sigma_decay: float, arrivals=None,
                churn=None, telemetry: bool = False):
    """Pure single-round body shared by the jitted round and the scan.

    ``churn`` (a :class:`~repro.sim.churn.ChurnConfig`, or ``None`` for
    a static fleet) splits one extra key per round and draws a fresh
    batched churn schedule on device — each episode of the batch trains
    against its own fault/throttle/join trace.

    ``telemetry`` additionally folds the round's in-graph telemetry
    block (``repro.telemetry.metrics.round_telemetry``: SLA/reward
    histograms, committed counter, replay-fill gauge) into the metrics
    dict.  It only READS values the round already computes, so weights,
    replay contents, and every pre-existing metric stay bit-identical
    and the block rides the chunk's one existing metrics transfer —
    no per-period host sync is added (``tests/test_telemetry.py``)."""
    pcfg = dcfg.policy

    def round_fn(state: D.DDPGState, buf: dict, key, sigma, do_update):
        if churn is None:
            ktrace, kroll, kup = jax.random.split(key, 3)
            scheds = None
        else:
            ktrace, kroll, kup, kchurn = jax.random.split(key, 4)
            scheds = churn_schedules_jax(
                churn, env.cfg.periods, env.num_sas,
                jax.random.split(kchurn, batch_episodes))
        with jax.named_scope("relmas.trace_gen"):
            traces, states = env.new_episodes_jax(ktrace, batch_episodes,
                                                  arrivals)
        with jax.named_scope("relmas.rollout"):
            _, trans, einfos, mets = collect_episodes(
                env, pcfg, state.actor, states, traces, kroll, sigma,
                churn=scheds)
        # (episodes, periods, ...) -> (episodes * periods, ...) ring write
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in trans.items()}
        with jax.named_scope("relmas.ring_write"):
            buf = replay_add(buf, flat)

        def upd(st):
            with jax.named_scope("relmas.ddpg_update"):
                st2, infos = D.ddpg_update_rounds(st, dcfg, buf, kup,
                                                  num_updates, batch_size)
            return st2, {k: infos[k][-1] for k in INFO_KEYS}

        def no_upd(st):
            return st, {k: jnp.zeros((), jnp.float32) for k in INFO_KEYS}

        state, info = jax.lax.cond(do_update, upd, no_upd, state)
        sigma = jnp.maximum(jnp.float32(sigma_min),
                            sigma * sigma_decay ** batch_episodes)
        metrics = dict(sla=jnp.mean(mets["sla_rate"]),
                       reward=jnp.mean(einfos["reward"]),
                       energy_uj=jnp.mean(mets["energy_uj"]),
                       sigma=sigma, did_update=do_update, **info)
        if telemetry:
            with jax.named_scope("relmas.telemetry"):
                metrics.update(round_telemetry(
                    mets["sla_rate"], einfos["reward"],
                    einfos["committed"], buf["size"], buf["r"].shape[0]))
        return state, buf, sigma, metrics

    return round_fn


def _cache_key(tag: str, dcfg, kw: dict[str, Any]):
    return (tag, dcfg) + tuple(sorted(kw.items()))


def make_train_round(env: SchedulingEnv, dcfg: D.DDPGConfig, *,
                     batch_episodes: int, num_updates: int, batch_size: int,
                     sigma_min: float, sigma_decay: float, arrivals=None,
                     churn=None, telemetry: bool = False):
    """One full training round as ONE jitted, donated device call.

    Returns ``round_fn(state, buf, key, sigma, do_update)`` ->
    ``(state, buf, sigma, metrics)``.  ``state`` and ``buf`` are
    donated (rebind!), ``sigma`` is a device scalar, ``do_update`` a
    device bool gating the update scan (False during warmup).
    ``batch_episodes * env.cfg.periods`` transitions ring-write per
    round and must fit the replay capacity (single-scatter ring).
    Compiled callables are cached per env instance.
    """
    kw = dict(batch_episodes=batch_episodes, num_updates=num_updates,
              batch_size=batch_size, sigma_min=sigma_min,
              sigma_decay=sigma_decay, arrivals=arrivals, churn=churn,
              telemetry=telemetry)
    key_ = _cache_key("train_round", dcfg, kw)
    cache = _runner_cache(env)
    if key_ not in cache:
        cache[key_] = jax.jit(_round_body(env, dcfg, **kw),
                              donate_argnums=(0, 1))
    return cache[key_]


def make_train_rounds(env: SchedulingEnv, dcfg: D.DDPGConfig, *,
                      batch_episodes: int, num_updates: int,
                      batch_size: int, sigma_min: float,
                      sigma_decay: float, arrivals=None, churn=None,
                      telemetry: bool = False):
    """A chunk of R rounds fused into one ``lax.scan`` dispatch.

    Returns ``rounds_fn(state, buf, keys, sigma, do_update)`` ->
    ``(state, buf, sigma, metrics)`` where ``keys`` is (R, 2) per-round
    keys (see :func:`round_keys`), ``do_update`` a (R,) bool vector
    (warmup rounds False), and ``metrics`` is the per-round dict
    stacked over the leading (R,) axis — one host transfer per chunk.
    ``state`` and ``buf`` are donated.  R is baked into the compiled
    program by the argument shapes — one compile per distinct chunk
    length.  The driver's eval/ckpt cadence is periodic in rounds, so
    a run sees only a handful of distinct lengths (the steady-state
    cycle, possibly a shorter first chunk after resume, and the tail
    round); each compiles once and is cached on the env.
    """
    kw = dict(batch_episodes=batch_episodes, num_updates=num_updates,
              batch_size=batch_size, sigma_min=sigma_min,
              sigma_decay=sigma_decay, arrivals=arrivals, churn=churn,
              telemetry=telemetry)
    key_ = _cache_key("train_rounds", dcfg, kw)
    cache = _runner_cache(env)
    if key_ in cache:
        return cache[key_]

    round_fn = _round_body(env, dcfg, **kw)

    def _scan(state, buf, keys, sigma, do_update):
        def step(carry, xs):
            st, bf, sg = carry
            k, du = xs
            st, bf, sg, m = round_fn(st, bf, k, sg, du)
            return (st, bf, sg), m

        (state, buf, sigma), metrics = jax.lax.scan(
            step, (state, buf, sigma), (keys, do_update))
        return state, buf, sigma, metrics

    rounds_fn = jax.jit(_scan, donate_argnums=(0, 1))
    cache[key_] = rounds_fn
    return rounds_fn


def train_rounds_scan(env: SchedulingEnv, dcfg: D.DDPGConfig, state, buf,
                      keys, sigma, do_update, **kw):
    """Call-style convenience over :func:`make_train_rounds`: scan the
    R rounds described by ``keys``/``do_update`` in one dispatch and
    return ``(state, buf, sigma, metrics)`` (metrics stacked over the
    round axis, one transfer).  ``state``/``buf`` are donated."""
    return make_train_rounds(env, dcfg, **kw)(state, buf, keys, sigma,
                                              do_update)


def train_rounds_host(env: SchedulingEnv, dcfg: D.DDPGConfig, state, buf,
                      keys, sigma, do_update, **kw):
    """Per-round host loop over the jitted single round (same keys).

    The unfused reference: R separate dispatches with a host round-trip
    each, numerically matching :func:`make_train_rounds` on identical
    ``keys``/``do_update`` up to XLA fusion-level float differences.
    Returns the same ``(state, buf, sigma, metrics)`` tuple with
    metrics stacked on the host.  ``state``/``buf`` are donated by the
    inner round — the originals are consumed here too.
    """
    round_fn = make_train_round(env, dcfg, **kw)
    out: list[Metrics] = []
    for i in range(len(do_update)):
        state, buf, sigma, m = round_fn(state, buf, keys[i], sigma,
                                        do_update[i])
        out.append(m)
    metrics = jax.tree.map(lambda *xs: jnp.stack(xs), *out)
    return state, buf, sigma, metrics


# ---------------------------------------------------------------------------
# mesh-sharded rounds (jit-of-shard_map over a 1-D named device mesh)
# ---------------------------------------------------------------------------
MESH_AXIS = "dev"


def make_device_mesh(devices=None) -> Mesh:
    """1-D device mesh over the named :data:`MESH_AXIS` axis.

    ``devices`` defaults to all local devices; the driver passes
    ``jax.local_devices()[:N]`` for ``--devices N``.  The explicit mesh
    is what ``pmap`` could never give us: a second named axis (device x
    fleet for the generalist) composes by adding a mesh dimension, not
    by rewriting the trainer.
    """
    devices = list(devices) if devices is not None else jax.local_devices()
    return Mesh(np.array(devices), (MESH_AXIS,))


def mesh_replicate(tree, mesh: Mesh):
    """Stack a single-device pytree D times with the leading axis
    sharded over the mesh axis, the (D, ...) layout
    :func:`make_sharded_train_rounds` takes, so shard_map moves no
    data."""
    ndev = mesh.devices.size
    spec = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    return jax.tree.map(
        lambda x: jax.device_put(
            jnp.broadcast_to(x[None], (ndev,) + x.shape), spec), tree)


def unreplicate(tree):
    """First replica of a replicated pytree — checkpoints and eval use
    plain single-device arrays so restore is device-count-agnostic."""
    return jax.tree.map(lambda x: x[0], tree)


def _sharded_round_body(env: SchedulingEnv, dcfg: D.DDPGConfig, *,
                        num_devices: int, batch_episodes: int,
                        num_updates: int, batch_size: int,
                        sigma_min: float, sigma_decay: float,
                        arrivals=None, axis_name: str = MESH_AXIS,
                        update_gather: bool = True,
                        telemetry: bool = False):
    """Per-device round body run under a mapped ``axis_name`` axis.

    Each device collects ``batch_episodes // num_devices`` episodes with
    its own device-folded key (embarrassingly parallel), runs the
    replicated update scan, and advances its private double-buffered
    ring pair — the update samples the ``read`` ring while the round's
    fresh transitions land in the ``write`` ring, so XLA may overlap
    the two (see ``repro.core.replay``).

    ``update_gather`` selects the update's sampling topology
    (``ddpg_update_rounds``): True (the mesh path) all-gathers each
    device's ``batch_size // num_devices`` sampled rows into the global
    union-pool minibatch every device updates on identically; False
    (the retiring pmap arm) updates from local samples with
    cross-device gradient averaging.  Sigma decays by the GLOBAL
    episode count so the exploration schedule matches the single-device
    run.  Episode metrics are ``pmean``'d: every replica returns the
    global round averages.
    """
    pcfg = dcfg.policy
    per_eps = batch_episodes // num_devices
    per_bs = batch_size // num_devices
    if per_eps * num_devices != batch_episodes:
        raise ValueError(f"batch_episodes={batch_episodes} not divisible "
                         f"by num_devices={num_devices}")
    if per_bs * num_devices != batch_size:
        raise ValueError(f"batch_size={batch_size} not divisible "
                         f"by num_devices={num_devices}")

    def round_fn(state: D.DDPGState, pair: dict, key, sigma, do_update):
        ktrace, kroll, kup = jax.random.split(key, 3)
        traces, states = env.new_episodes_jax(ktrace, per_eps, arrivals)
        _, trans, einfos, mets = collect_episodes(
            env, pcfg, state.actor, states, traces, kroll, sigma)
        flat = {k: v.reshape((-1,) + v.shape[2:]) for k, v in trans.items()}

        def upd(st):
            st2, infos = D.ddpg_update_rounds(
                st, dcfg, pair["read"], kup, num_updates, per_bs,
                axis_name=None if update_gather else axis_name,
                gather_axis=axis_name if update_gather else None)
            return st2, {k: infos[k][-1] for k in INFO_KEYS}

        def no_upd(st):
            return st, {k: jnp.zeros((), jnp.float32) for k in INFO_KEYS}

        state, info = jax.lax.cond(do_update, upd, no_upd, state)
        pair = replay_pair_step(pair, flat)
        sigma = jnp.maximum(jnp.float32(sigma_min),
                            sigma * sigma_decay ** batch_episodes)
        pm = lambda x: jax.lax.pmean(x, axis_name)
        metrics = dict(sla=pm(jnp.mean(mets["sla_rate"])),
                       reward=pm(jnp.mean(einfos["reward"])),
                       energy_uj=pm(jnp.mean(mets["energy_uj"])),
                       sigma=sigma, did_update=do_update, **info)
        if telemetry:
            # per-device aggregates reduced to the global view: counts
            # (histograms, committed jobs) sum over the device axis,
            # gauges (ring fill) average — every replica then carries
            # the same global telemetry block, matching the pmean'd
            # episode metrics above
            with jax.named_scope("relmas.telemetry"):
                tele = round_telemetry(
                    mets["sla_rate"], einfos["reward"],
                    einfos["committed"], pair["read"]["size"],
                    pair["read"]["r"].shape[0])
                for k in ROUND_TELE_COUNTS:
                    tele[k] = jax.lax.psum(tele[k], axis_name)
                for k in ROUND_TELE_GAUGES:
                    tele[k] = jax.lax.pmean(tele[k], axis_name)
                metrics.update(tele)
        return state, pair, sigma, metrics

    return round_fn


def _sharded_scan(round_fn):
    """Scan a per-device round body over the chunk's R rounds."""
    def _scan(state, pair, keys, sigma, do_update):
        def step(carry, xs):
            st, pr, sg = carry
            k, du = xs
            st, pr, sg, m = round_fn(st, pr, k, sg, du)
            return (st, pr, sg), m

        (state, pair, sigma), metrics = jax.lax.scan(
            step, (state, pair, sigma), (keys, do_update))
        return state, pair, sigma, metrics

    return _scan


def _jit_shard_map(scan_fn, mesh: Mesh, *, n_args: int,
                   sharded: tuple[int, ...]):
    """Wrap a per-device chunk scan as ``jit``-of-``shard_map``.

    Arguments at the ``sharded`` positions carry a leading ``D`` axis
    split over the mesh axis (each shard peels its singleton slice so
    the body sees pmap-style unbatched per-device arrays); the rest are
    replicated as-is (``do_update``, the generalist's shared fleet
    keys).  All outputs return with the leading ``D`` axis.  ``state``
    and ``pair`` (args 0 and 1) are donated.
    """
    axis = mesh.axis_names[0]
    spec, rep = PartitionSpec(axis), PartitionSpec()
    sharded = frozenset(sharded)

    def body(*args):
        peeled = tuple(jax.tree.map(lambda x: x[0], a) if i in sharded
                       else a for i, a in enumerate(args))
        out = scan_fn(*peeled)
        return jax.tree.map(lambda x: x[None], out)

    in_specs = tuple(spec if i in sharded else rep for i in range(n_args))
    # check_vma=False: every output legitimately carries the device
    # axis, so the varying-axes check has nothing to verify
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                                 out_specs=(spec, spec, spec, spec),
                                 check_vma=False),
                   donate_argnums=(0, 1))


def make_sharded_train_rounds(env: SchedulingEnv, dcfg: D.DDPGConfig, *,
                              mesh: Mesh, batch_episodes: int,
                              num_updates: int, batch_size: int,
                              sigma_min: float, sigma_decay: float,
                              arrivals=None, telemetry: bool = False):
    """A chunk of R rounds sharded over ``mesh`` in one jitted
    ``shard_map`` dispatch (the pmap successor — pmap is
    soft-deprecated and caps at a single axis; the named mesh is what
    the 2-D device x fleet extension hangs off).

    Returns ``rounds_fn(state, pair, keys, sigma, do_update)`` ->
    ``(state, pair, sigma, metrics)`` where every array carries a
    leading ``D = mesh.devices.size`` axis split over the mesh axis
    except ``do_update`` (an (R,) bool vector replicated to all
    devices):

    - ``state``: replicated ``DDPGState`` (:func:`mesh_replicate`);
      stays BIT-identical across replicas because every device runs
      the identical update on the identical all-gathered global batch
      — :func:`unreplicate` for checkpoints/eval;
    - ``pair``: per-device double-buffered ring pairs
      (``replay_pair_init`` then :func:`mesh_replicate` of a fresh
      pair — device streams diverge as soon as the first round
      writes);
    - ``keys``: (D, R, 2) from :func:`shard_round_keys`;
    - ``sigma``: replicated (D,) scalar;
    - ``metrics``: per-round dict stacked (D, R); episode metrics are
      pmean'd so row 0 equals the global average.

    ``state`` and ``pair`` are donated (rebind!).  Collection shards
    over devices (``batch_episodes / D`` episodes each); each update
    samples ``batch_size / D`` rows per device and ``all_gather``s
    them into the global minibatch (``replay_sample_global``) — the
    update consumes the union experience pool, not D disjoint local
    pools, at the memory cost of one replicated ``batch_size``
    minibatch per device (a few hundred KB at training shapes).  One
    compile per distinct (mesh, R) — cached on the env.
    """
    kw = dict(batch_episodes=batch_episodes, num_updates=num_updates,
              batch_size=batch_size, sigma_min=sigma_min,
              sigma_decay=sigma_decay, arrivals=arrivals,
              telemetry=telemetry)
    key_ = _cache_key("shardmap_rounds", dcfg, kw) + (mesh,)
    cache = _runner_cache(env)
    if key_ not in cache:
        round_fn = _sharded_round_body(
            env, dcfg, num_devices=mesh.devices.size,
            axis_name=mesh.axis_names[0], update_gather=True, **kw)
        cache[key_] = _jit_shard_map(_sharded_scan(round_fn), mesh,
                                     n_args=5, sharded=(0, 1, 2, 3))
    return cache[key_]


def sharded_rounds_reference(env: SchedulingEnv, dcfg: D.DDPGConfig, *,
                             num_devices: int, batch_episodes: int,
                             num_updates: int, batch_size: int,
                             sigma_min: float, sigma_decay: float,
                             arrivals=None, update_gather: bool = True,
                             telemetry: bool = False):
    """Single-device vmap oracle for :func:`make_sharded_train_rounds`.

    The SAME per-device round body mapped with ``jax.vmap(...,
    axis_name=MESH_AXIS)`` instead of shard_map — the ``pmean`` /
    ``all_gather`` collectives resolve identically, so on matching
    inputs the results must agree up to XLA fusion-level float
    differences regardless of how many physical devices exist.  Same
    signature and (D, R) output layout as the mesh callable; runs on
    the default device.  ``update_gather=False`` instead exercises the
    local-sampling + ``pmean``'d-gradient topology (the behaviour of
    the retired pmap arm).
    """
    kw = dict(batch_episodes=batch_episodes, num_updates=num_updates,
              batch_size=batch_size, sigma_min=sigma_min,
              sigma_decay=sigma_decay, arrivals=arrivals,
              telemetry=telemetry)
    key_ = _cache_key("sharded_rounds_ref", dcfg, kw) + (num_devices,
                                                         update_gather)
    cache = _runner_cache(env)
    if key_ not in cache:
        round_fn = _sharded_round_body(env, dcfg, num_devices=num_devices,
                                       update_gather=update_gather, **kw)
        vround = jax.vmap(round_fn, in_axes=(0, 0, 0, 0, None),
                          axis_name=MESH_AXIS)

        def _scan(state, pair, keys, sigma, do_update):
            def step(carry, xs):
                st, pr, sg = carry
                k, du = xs
                st, pr, sg, m = vround(st, pr, k, sg, du)
                return (st, pr, sg), m

            # scan over rounds: keys (D, R, 2) -> (R, D, 2) for the scan,
            # metrics back to the mesh layout (D, R, ...)
            (state, pair, sigma), metrics = jax.lax.scan(
                step, (state, pair, sigma),
                (jnp.swapaxes(keys, 0, 1), do_update))
            metrics = jax.tree.map(lambda x: jnp.swapaxes(x, 0, 1), metrics)
            return state, pair, sigma, metrics

        cache[key_] = jax.jit(_scan, donate_argnums=(0, 1))
    return cache[key_]
