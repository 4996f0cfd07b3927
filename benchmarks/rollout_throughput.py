"""Rollout throughput: seed collection pipeline vs device-resident batch.

Measures scheduler-periods simulated per second for the full
experience-collection pipeline (policy rollout + replay write):

- BEFORE (the seed repo's path): ``run_episode`` drives one jitted call
  per period from Python, round-trips every transition to the host, and
  writes the NumPy ``ReplayBuffer`` one transition at a time; the
  contention engine is the seed's ``segment_*`` formulation
  (``simulate_jax_segments``).
- AFTER (this repo's path): ``make_rollout_batch`` runs the whole batch
  of episodes in one jitted call (``lax.scan`` over periods, ``vmap``
  over episodes, sharded over local devices when available) with the
  one-hot engine, and ring-writes the stacked transitions into the
  device-resident ``DeviceReplay`` in one scatter.
- ``loop_current`` (reported for transparency): the per-period loop on
  top of the NEW engine — isolates how much of the speedup comes from
  batching vs. the engine rewrite.

Both arms run the same RELMAS actor with exploration noise and collect
transitions (the training configuration).  Compile time is excluded via
one untimed warmup call per arm.  Acceptance bar for the batched
pipeline PR: >= 5x periods/sec at batch >= 8 on CPU.

The ``magma_throughput`` section benchmarks the GA baseline the same
way: the legacy host loop (one jitted dispatch per generation, one
Python period step per period — how MAGMA was driven before the
scan-fused port) vs ``magma_search_scan`` running inside the batched
episode runner (whole episodes, all generations, one device call).
``--population/--generations`` scale the GA (paper settings: 100x100).
Acceptance bar for the scan-fused MAGMA PR: >= 5x periods/sec.

The ``train_throughput`` section measures full TRAINING rounds
(trace-gen + rollout + replay write + K DDPG updates + sigma decay):

- BEFORE (the per-round host loop the driver ran before the fused
  trainer): per-episode NumPy trace generation, one dispatch each for
  rollout / un-donated replay write / un-donated update scan, host
  sigma decay, and a per-round metrics sync for logging;
- AFTER: ``core.train.make_train_rounds`` — a whole chunk of rounds in
  ONE jitted ``lax.scan`` dispatch with the replay buffer and learner
  state donated, metrics transferred once per chunk.

Acceptance bar for the fused-trainer PR: >= 3x periods/sec at the CI
config.  ``--only train_throughput`` runs just this section (the CI
regression guard does).

``train_throughput`` additionally carries a ``devices`` scaling
subsection: rounds/sec and periods/sec for the SAME chunk config at
1/2/4 devices, all timed in this process over
``jax.local_devices()[:N]`` — 1 device runs the plain fused chunk,
N >= 2 the mesh-sharded ``jit``-of-``shard_map`` chunk (``core.train
.make_sharded_train_rounds``).  Counts above the local device count are
left out.  On the CPU, host devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` set before the
process starts (``scripts/ci.sh`` does).  One extra arm quantifies the
sharding machinery itself at ONE device, where compute is identical and
any delta is pure dispatch/collective overhead: ``shardmap_1dev`` (the
mesh path on a 1-device mesh) — ``overhead_1dev_shardmap`` is the
plain fused row's rounds/sec over that arm's.  ``host_cores`` is
recorded alongside: forced host devices *partition* the host's cores,
so on a single-core machine the N-device arms serialize and
``scaling_2dev`` measures sharding overhead, not speedup — the section
exists to track scaling efficiency as a trajectory, and reads as a
true scaling curve only where ``host_cores >= N`` (or on real
multi-accelerator hosts).

The ``fleet_scaling`` section reports batched-rollout periods/sec per
accelerator-fleet preset (``repro.costmodel.fleets``) — small (4-SA) vs
paper (6-SA) vs large (8-SA) platforms, one compiled evaluator each.

Results are also written to ``BENCH_rollout.json`` (periods/sec and
speedups per arm; schema in docs/BENCHMARKS.md) so future PRs can
track regressions.

Usage:
  PYTHONPATH=src python -m benchmarks.rollout_throughput --batch 32 \
      --population 16 --generations 8
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import REPO, bench_meta, make_env
from repro.core import baselines as BL
from repro.core import ddpg as D
from repro.core import policy as P
from repro.core.replay import (DeviceReplay, ReplayBuffer, replay_add,
                               replay_init, replay_pair_init)
from repro.core.rollout import (make_baseline_episode_batch,
                                make_policy_period, make_rollout_batch,
                                run_episode, stack_episodes)
from repro.core.train import (make_device_mesh,
                              make_sharded_train_rounds, make_train_rounds,
                              mesh_replicate, round_keys,
                              shard_round_keys)
from repro.launch.compile_cache import use_compile_cache
from repro.sim import engine as engine_mod
import repro.sim.env as env_mod


def run(*, batch: int = 32, legacy_episodes: int = 3, repeats: int = 3,
        periods: int = 60, max_rq: int = 96, max_jobs: int = 64,
        hidden: int = 64, sigma: float = 0.2, seed: int = 0,
        capacity: int = 4000) -> dict:
    pcfg = None

    def fresh_env():
        env = make_env("light", periods=periods, max_rq=max_rq,
                       max_jobs=max_jobs)
        nonlocal pcfg
        pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                              hidden=hidden)
        return env

    # ---- BEFORE: seed pipeline (segment engine + per-period loop +
    # host replay writes).  The engine is swapped at the module level;
    # a fresh env/period_fn pair keeps the jit caches of the arms apart.
    env_mod.simulate_jax = engine_mod.simulate_jax_segments
    try:
        env = fresh_env()
        params = P.init_actor(jax.random.PRNGKey(seed), pcfg)
        period_fn = make_policy_period(env, pcfg)
        buf = ReplayBuffer(capacity, env.seq_len, env.feat_dim, env.act_dim)

        def legacy_episode(i):
            _, trans = run_episode(env, period_fn,
                                   np.random.default_rng(seed + i),
                                   params=params, key=jax.random.PRNGKey(i),
                                   sigma=sigma, collect=True)
            for tr in trans:
                buf.add(tr["s"], tr["mask"], tr["a"], tr["r"], tr["s2"],
                        tr["mask2"])

        legacy_episode(0)                                # warmup/compile
        t0 = time.perf_counter()
        for i in range(legacy_episodes):
            legacy_episode(1 + i)
        pps_seed = legacy_episodes * periods / (time.perf_counter() - t0)
    finally:
        env_mod.simulate_jax = engine_mod.simulate_jax

    # ---- transparency arm: per-period loop on the NEW engine
    env = fresh_env()
    params = P.init_actor(jax.random.PRNGKey(seed), pcfg)
    period_fn = make_policy_period(env, pcfg)
    run_episode(env, period_fn, np.random.default_rng(seed), params=params,
                key=jax.random.PRNGKey(seed), sigma=sigma, collect=True)
    t0 = time.perf_counter()
    for i in range(legacy_episodes):
        run_episode(env, period_fn, np.random.default_rng(seed + 1 + i),
                    params=params, key=jax.random.PRNGKey(i), sigma=sigma,
                    collect=True)
    pps_loop = legacy_episodes * periods / (time.perf_counter() - t0)

    # ---- AFTER: batched device-resident pipeline ------------------------
    devs = jax.local_devices()
    devices = devs if len(devs) > 1 and batch % len(devs) == 0 else None
    rollout_fn = make_rollout_batch(env, pcfg, devices=devices)
    dbuf = DeviceReplay(capacity, env.seq_len, env.feat_dim, env.act_dim)

    def batched_round(i):
        traces, states = env.new_episodes(np.random.default_rng(seed + i),
                                          batch)
        _, trans, _, _ = rollout_fn(params, states, traces,
                                    jax.random.PRNGKey(100 + i), sigma)
        dbuf.add_batch(trans)
        jax.block_until_ready(dbuf.data["ptr"])

    batched_round(0)                                     # warmup/compile
    t0 = time.perf_counter()
    for i in range(repeats):
        batched_round(1 + i)
    pps_batch = repeats * batch * periods / (time.perf_counter() - t0)

    res = dict(batch=batch, periods=periods, devices=len(devs),
               periods_per_sec_legacy=round(pps_seed, 1),
               periods_per_sec_loop_current=round(pps_loop, 1),
               periods_per_sec_batched=round(pps_batch, 1),
               speedup=round(pps_batch / pps_seed, 2))
    print("rollout_throughput," + json.dumps(res), flush=True)
    return res


def run_magma(*, batch: int = 8, legacy_episodes: int = 1, repeats: int = 2,
              periods: int = 12, max_rq: int = 32, max_jobs: int = 12,
              population: int = 16, generations: int = 8,
              seed: int = 0) -> dict:
    """Host-loop MAGMA vs scan-fused batched MAGMA, periods/sec.

    The paper setting is ``--population 100 --generations 100``; the
    defaults are a CI-sized scale-down of the same shape (the host-loop
    arm pays ``periods x generations`` dispatches either way).
    """
    env = make_env("light", periods=periods, max_rq=max_rq,
                   max_jobs=max_jobs)
    mcfg = BL.MagmaConfig(population=population, generations=generations)

    # ---- BEFORE: per-period Python loop, one jitted dispatch per
    # generation (how benchmarks drove MAGMA before the scan port)
    def period(state, trace):
        def act_fn(feats, mask, slots, st):
            return BL.magma(slots, st, env, mcfg)
        return env.period(state, trace, act_fn)

    run_episode(env, period, np.random.default_rng(seed))  # warmup/compile
    t0 = time.perf_counter()
    for i in range(legacy_episodes):
        run_episode(env, period, np.random.default_rng(seed + 1 + i))
    pps_host = legacy_episodes * periods / (time.perf_counter() - t0)

    # ---- AFTER: whole GA episodes in one device call, vmapped over
    # traces like every other policy
    mag = BL.make_magma_baseline(mcfg)
    eval_fn = make_baseline_episode_batch(env, mag)

    def batched_round(i):
        seeds = range(seed + 100 * i, seed + 100 * i + batch)
        traces, states = stack_episodes(env, seeds)
        keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
        jax.block_until_ready(eval_fn(states, traces, keys))

    batched_round(0)                                     # warmup/compile
    t0 = time.perf_counter()
    for i in range(repeats):
        batched_round(1 + i)
    pps_scan = repeats * batch * periods / (time.perf_counter() - t0)

    res = dict(batch=batch, periods=periods, population=population,
               generations=generations,
               periods_per_sec_hostloop=round(pps_host, 2),
               periods_per_sec_scan_batched=round(pps_scan, 2),
               speedup=round(pps_scan / pps_host, 2))
    print("magma_throughput," + json.dumps(res), flush=True)
    return res


def run_train(*, rounds: int = 24, batch: int = 2, periods: int = 4,
              max_rq: int = 16, max_jobs: int = 8, hidden: int = 8,
              updates_per_round: int = 2, batch_size: int = 4,
              capacity: int = 8000, warmup_rounds: int = 1,
              sigma0: float = 0.4, sigma_min: float = 0.05,
              sigma_decay: float = 0.97, seed: int = 0) -> dict:
    """Per-round host training loop vs scan-fused multi-round trainer.

    Both arms run identical round *logic* (collect ``batch`` episodes,
    ring-write, ``updates_per_round`` DDPG updates, sigma decay); the
    BEFORE arm reproduces the pre-fusion driver faithfully — NumPy
    trace generation, three separate un-donated dispatches per round,
    and a per-round host sync for the log record.

    The defaults are the CI config: a deliberately small round (the
    regime where per-round host overhead — dispatch, sync, the
    un-donated O(capacity) ring copy — is visible next to compute) at
    a realistic replay capacity.  At production-sized rounds the same
    fusion mostly buys back the replay copy + trace-gen time.
    """
    env = make_env("light", periods=periods, max_rq=max_rq,
                   max_jobs=max_jobs)
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=hidden)
    dcfg = D.DDPGConfig(policy=pcfg)

    # ---- BEFORE: per-round host loop (the pre-fused-trainer driver) --
    # un-donated twins of the replay write and update scan — exactly
    # the jits the old driver dispatched
    add_undonated = jax.jit(replay_add)
    upd_undonated = jax.jit(D.ddpg_update_rounds,
                            static_argnames=("cfg", "num_updates",
                                             "batch_size"))
    rollout_fn = make_rollout_batch(env, pcfg)

    def host_loop(n_rounds):
        state = D.init_ddpg(jax.random.PRNGKey(seed), dcfg)
        buf = replay_init(capacity, env.seq_len, env.feat_dim, env.act_dim)
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(seed + 1)
        sigma = sigma0
        for i in range(n_rounds):
            key, kroll, kup = jax.random.split(key, 3)
            traces, states = env.new_episodes(rng, batch)  # host NumPy gen
            _, trans, _, mets = rollout_fn(state.actor, states, traces,
                                           kroll, jnp.float32(sigma))
            flat = {k: v.reshape((-1,) + v.shape[2:])
                    for k, v in trans.items()}
            buf = add_undonated(buf, flat)
            state, infos = upd_undonated(state, dcfg, buf, kup,
                                         num_updates=updates_per_round,
                                         batch_size=batch_size)
            sigma = max(sigma_min, sigma * sigma_decay ** batch)
            # the old driver logged every round -> one host sync each
            float(jnp.mean(mets["sla_rate"]))
            float(infos["critic_loss"][-1])
        return state

    host_loop(warmup_rounds)                             # compile
    t0 = time.perf_counter()
    host_loop(rounds)
    host_secs = time.perf_counter() - t0

    # ---- AFTER: one lax.scan dispatch per chunk of rounds, donated --
    kw = dict(batch_episodes=batch, num_updates=updates_per_round,
              batch_size=batch_size, sigma_min=sigma_min,
              sigma_decay=sigma_decay)
    rounds_fn = make_train_rounds(env, dcfg, **kw)
    flags = jnp.ones((rounds,), bool)

    def fused_chunk():
        state = D.init_ddpg(jax.random.PRNGKey(seed), dcfg)
        buf = replay_init(capacity, env.seq_len, env.feat_dim, env.act_dim)
        keys = round_keys(seed + 1, 0, rounds)
        state, buf, sigma, mets = rounds_fn(state, buf, keys,
                                            jnp.float32(sigma0), flags)
        jax.block_until_ready(mets["sla"])               # one sync per chunk
        return mets

    fused_chunk()                                        # warmup/compile
    t0 = time.perf_counter()
    fused_chunk()
    fused_secs = time.perf_counter() - t0

    p_total = rounds * batch * periods
    res = dict(rounds=rounds, batch=batch, periods=periods,
               updates_per_round=updates_per_round, batch_size=batch_size,
               capacity=capacity,
               rounds_per_sec_hostloop=round(rounds / host_secs, 2),
               rounds_per_sec_fused=round(rounds / fused_secs, 2),
               periods_per_sec_hostloop=round(p_total / host_secs, 1),
               periods_per_sec_fused=round(p_total / fused_secs, 1),
               speedup=round(host_secs / fused_secs, 2))
    print("train_throughput," + json.dumps(res), flush=True)
    return res


def time_devices_chunk(ndev: int, impl: str, *, rounds: int = 24,
                       batch: int = 4, periods: int = 4, max_rq: int = 16,
                       max_jobs: int = 8, hidden: int = 8,
                       updates_per_round: int = 2, batch_size: int = 4,
                       capacity: int = 8000, sigma0: float = 0.4,
                       sigma_min: float = 0.05, sigma_decay: float = 0.97,
                       seed: int = 0) -> dict:
    """Time one fused chunk of ``rounds`` rounds on the first ``ndev``
    local devices.

    ``impl`` selects the arm: ``fused`` (the plain single-device chunk
    — ``ndev`` must be 1), or ``shard_map`` (the mesh path, valid at
    any ``ndev`` including 1 — the 1-device row isolates the sharding
    machinery's overhead).  Same round logic and global batch/update
    sizes as :func:`run_train`'s AFTER arm (with ``batch`` raised so it
    splits over 4 devices), so the 1-device fused row doubles as that
    arm's twin.
    """
    env = make_env("light", periods=periods, max_rq=max_rq,
                   max_jobs=max_jobs)
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=hidden)
    dcfg = D.DDPGConfig(policy=pcfg)
    kw = dict(batch_episodes=batch, num_updates=updates_per_round,
              batch_size=batch_size, sigma_min=sigma_min,
              sigma_decay=sigma_decay)
    flags = jnp.ones((rounds,), bool)
    keys = round_keys(seed + 1, 0, rounds)

    if impl == "fused":
        rounds_fn = make_train_rounds(env, dcfg, **kw)

        def chunk():
            state = D.init_ddpg(jax.random.PRNGKey(seed), dcfg)
            buf = replay_init(capacity, env.seq_len, env.feat_dim,
                              env.act_dim)
            out = rounds_fn(state, buf, keys, jnp.float32(sigma0), flags)
            jax.block_until_ready(out[3]["sla"])
    else:
        mesh = make_device_mesh(jax.local_devices()[:ndev])
        rounds_fn = make_sharded_train_rounds(env, dcfg, mesh=mesh, **kw)
        repl = lambda t: mesh_replicate(t, mesh)
        dkeys = shard_round_keys(keys, ndev)
        round_size = (batch // ndev) * periods

        def chunk():
            state = repl(D.init_ddpg(jax.random.PRNGKey(seed), dcfg))
            pair = repl(replay_pair_init(
                replay_init(capacity // ndev, env.seq_len, env.feat_dim,
                            env.act_dim), round_size))
            out = rounds_fn(state, pair, dkeys,
                            repl(jnp.float32(sigma0)), flags)
            jax.block_until_ready(out[3]["sla"])

    chunk()                                              # warmup/compile
    t0 = time.perf_counter()
    chunk()
    secs = time.perf_counter() - t0
    return dict(devices=ndev, impl=impl, rounds=rounds, batch=batch,
                rounds_per_sec=round(rounds / secs, 2),
                periods_per_sec=round(rounds * batch * periods / secs, 1))


def run_train_devices(counts=(1, 2, 4), *, rounds: int = 24) -> dict:
    """The ``train_throughput.devices`` scaling section, timed in this
    process on ``jax.local_devices()[:N]`` for each count ``N`` that
    the process has devices for:

    - ``counts``: the scaling curve — the plain fused chunk at 1
      device, the mesh-sharded shard_map chunk at every N >= 2;
    - ``shardmap_1dev``: the 1-device overhead arm — at one forced
      device it runs the identical compute as the fused row, so
      ``overhead_1dev_shardmap`` (fused rounds/sec over the arm's)
      isolates what the sharding machinery itself costs;
    - ``scaling_2dev``: shard_map 2-device over fused 1-device
      rounds/sec (``None`` with fewer than 2 devices); ``host_cores``
      qualifies it — forced host devices split the physical cores, so
      the ratio is a real concurrency measure only when
      ``host_cores >= N``.
    """
    avail = len(jax.local_devices())
    out: dict[str, dict] = {}
    for n in counts:
        if n <= avail:
            impl = "fused" if n == 1 else "shard_map"
            out[str(n)] = time_devices_chunk(n, impl, rounds=rounds)
    sm1 = time_devices_chunk(1, "shard_map", rounds=rounds)
    fused_rps = out["1"]["rounds_per_sec"]
    cores = os.cpu_count() or 1
    res = dict(counts=out, shardmap_1dev=sm1, local_devices=avail,
               scaling_2dev=(round(out["2"]["rounds_per_sec"] / fused_rps, 2)
                             if "2" in out else None),
               overhead_1dev_shardmap=round(
                   fused_rps / sm1["rounds_per_sec"], 2),
               host_cores=cores,
               note=("forced host devices partition the physical cores; "
                     "with host_cores < N the N-device arms time-slice "
                     "one core and scaling_2dev tracks sharding overhead "
                     "rather than parallel speedup; overhead_1dev_* are "
                     "fused/arm rounds-per-sec ratios at ONE device — "
                     "identical compute, so >1 is pure machinery cost"))
    print("train_devices," + json.dumps(res), flush=True)
    return res


def run_fleet_scaling(*, fleets=("2simba_2eyeriss", "paper6",
                                 "4simba_4eyeriss"),
                      batch: int = 8, repeats: int = 2, periods: int = 24,
                      max_rq: int = 48, max_jobs: int = 32, hidden: int = 32,
                      sigma: float = 0.2, seed: int = 0) -> dict:
    """Batched-rollout periods/sec per accelerator-fleet preset.

    The fleet sets ``num_sas`` and therefore the engine's per-SA
    reduction width, the slot cost/bw table width and the policy
    feature/action dims — this section shows how collection throughput
    scales from a small (4-SA) to a large (8-SA) platform, each fleet
    with its own compiled evaluator (shape change = recompile).
    """
    out: dict[str, dict] = {}
    for fl in fleets:
        env = make_env("light", fleet=fl, periods=periods, max_rq=max_rq,
                       max_jobs=max_jobs)
        pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                              hidden=hidden)
        params = P.init_actor(jax.random.PRNGKey(seed), pcfg)
        rollout_fn = make_rollout_batch(env, pcfg)

        def one_round(i):
            traces, states = env.new_episodes(
                np.random.default_rng(seed + i), batch)
            _, trans, _, mets = rollout_fn(params, states, traces,
                                           jax.random.PRNGKey(100 + i),
                                           sigma)
            jax.block_until_ready(mets["sla_rate"])

        one_round(0)                                     # warmup/compile
        t0 = time.perf_counter()
        for i in range(repeats):
            one_round(1 + i)
        pps = repeats * batch * periods / (time.perf_counter() - t0)
        out[fl] = dict(num_sas=env.num_sas, feat_dim=env.feat_dim,
                       periods_per_sec=round(pps, 1))
    small = min(out.values(), key=lambda r: r["num_sas"])
    large = max(out.values(), key=lambda r: r["num_sas"])
    res = dict(batch=batch, periods=periods, fleets=out,
               small_vs_large=round(small["periods_per_sec"]
                                    / large["periods_per_sec"], 2))
    print("fleet_scaling," + json.dumps(res), flush=True)
    return res


SECTIONS = ("rollout", "magma_throughput", "train_throughput",
            "fleet_scaling")


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--legacy-episodes", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--periods", type=int, default=60)
    ap.add_argument("--max-rq", type=int, default=96)
    ap.add_argument("--max-jobs", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--population", type=int, default=16,
                    help="MAGMA population (paper: 100)")
    ap.add_argument("--generations", type=int, default=8,
                    help="MAGMA generations (paper: 100)")
    ap.add_argument("--magma-batch", type=int, default=8,
                    help="episodes per device call in the MAGMA arm")
    ap.add_argument("--magma-periods", type=int, default=12,
                    help="episode length for the MAGMA section; the "
                         "magma arms run their own CI-sized env "
                         "(--magma-* knobs), NOT --periods/--max-rq — "
                         "the host-loop arm pays periods x generations "
                         "dispatches")
    ap.add_argument("--magma-max-rq", type=int, default=32,
                    help="RQ slots for the MAGMA section env")
    ap.add_argument("--magma-max-jobs", type=int, default=12,
                    help="max jobs for the MAGMA section env")
    ap.add_argument("--no-magma", action="store_true",
                    help="skip the magma_throughput section")
    ap.add_argument("--only", choices=SECTIONS, default=None,
                    help="run a single section (e.g. the CI regression "
                         "guard runs --only train_throughput)")
    ap.add_argument("--train-rounds", type=int, default=24,
                    help="rounds per arm in the train_throughput section")
    ap.add_argument("--device-counts", default="1,2,4",
                    help="device counts for the train_throughput devices "
                         "scaling subsection (counts above the local "
                         "device count are left out)")
    ap.add_argument("--no-devices", action="store_true",
                    help="skip the devices scaling subsection")
    ap.add_argument("--train-batch", type=int, default=2,
                    help="episodes per round in the train_throughput "
                         "section (its own CI-sized env, like the "
                         "magma section)")
    ap.add_argument("--fleets", default="2simba_2eyeriss,paper6,"
                    "4simba_4eyeriss",
                    help="fleet presets for the fleet_scaling section "
                         "(small vs large platforms)")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_rollout.json"))
    args = ap.parse_args(argv)

    def want(section):
        if args.only is not None:
            return section == args.only
        return not (section == "magma_throughput" and args.no_magma)

    # partial runs (--only / --no-magma) merge into an existing out
    # file instead of clobbering its other sections — `--only
    # train_throughput --out BENCH_rollout.json` must not delete the
    # committed rollout/magma records
    results = {}
    if (args.only is not None or args.no_magma) and os.path.exists(args.out):
        try:
            with open(args.out) as f:
                results = {k: v for k, v in json.load(f).items()
                           if k in SECTIONS}
        except (json.JSONDecodeError, OSError):
            results = {}
    if want("rollout"):
        results["rollout"] = run(
            batch=args.batch, legacy_episodes=args.legacy_episodes,
            repeats=args.repeats, periods=args.periods, max_rq=args.max_rq,
            max_jobs=args.max_jobs, hidden=args.hidden)
    if want("magma_throughput"):
        results["magma_throughput"] = run_magma(
            batch=args.magma_batch, periods=args.magma_periods,
            max_rq=args.magma_max_rq, max_jobs=args.magma_max_jobs,
            population=args.population, generations=args.generations)
    if want("train_throughput"):
        results["train_throughput"] = run_train(
            rounds=args.train_rounds, batch=args.train_batch)
        if not args.no_devices:
            counts = tuple(int(c) for c in args.device_counts.split(","))
            results["train_throughput"]["devices"] = run_train_devices(
                counts, rounds=args.train_rounds)
    if want("fleet_scaling"):
        results["fleet_scaling"] = run_fleet_scaling(
            fleets=tuple(args.fleets.split(",")))
    # provenance stamped on every (also partial) run — numbers are only
    # comparable across runs on the same jax/backend/core count
    results["meta"] = bench_meta()
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"rollout_json,{args.out}", flush=True)
    return results


if __name__ == "__main__":
    main()
