"""RELMAS DDPG training driver (paper Sec. 4.2 / Sec. 5).

Single-dispatch training rounds (see ``repro.core.train``): each round
— jax.random trace generation, batched rollout (``lax.scan`` over
periods inside ``vmap`` over episodes), replay ring-write, and all of
the round's DDPG updates plus sigma decay — is ONE jitted call with
the replay buffer and learner state donated (updated in place, no
O(capacity) copies).  Consecutive rounds between checkpoint/eval
boundaries additionally fuse into a single ``lax.scan`` dispatch
(``make_train_rounds``): the driver pays one dispatch and one metrics
transfer per *chunk*, not per round.  Evaluation runs through the
jitted ``evaluate_batch``.

Knobs:
- ``--fleet NAME[,NAME...]``  accelerator-fleet preset(s) (``paper6``,
  ``4simba_4eyeriss``, ``8simba``, ``8eyeriss``, ``2simba_6eyeriss``,
  ``big_little``, ... — see ``repro.costmodel.fleets``): one name
  trains a per-fleet *specialist* (workload re-characterized on that
  platform, policy dims follow its ``num_sas``); a comma list trains a
  fleet-conditioned *generalist* (``repro.core.generalist``) — per-SA
  hardware descriptors in the features, channels padded to ``M_max``,
  and each fused round samples a fleet for its episode batch (fleet
  tensors are stacked trace data: no recompile per fleet).
  ``--bandwidth-gbps 0`` (the default) uses each fleet's shared DRAM
  bandwidth;
- ``--policy-kind KIND``  ``auto`` (default: generalist iff several
  fleets) | ``generalist`` (force the M-agnostic descriptor-conditioned
  policy even on one fleet — its checkpoints restore on ANY fleet with
  ``num_sas <= m_max``) | ``specialist``;
- ``--m-max M``           pad width for the generalist (0 = widest
  requested fleet; raise it to leave headroom for larger platforms);
- ``--batch-episodes N``  episodes collected per training round;
- ``--devices N``         shard each fused round (and chunk scan) over N
  local devices via ``jit``-of-``shard_map`` on an explicit 1-D device
  mesh (``core.train.make_device_mesh`` / ``MESH_AXIS``): collection
  splits the episode batch, each device owns a donated double-buffered
  replay ring pair, and every DDPG update ``all_gather``s the devices'
  sampled rows into one global union-pool minibatch so the replicated
  learner state stays bit-identical across devices
  (``core.train.make_sharded_train_rounds``); composes with chunked
  rounds, auto-resume, and checkpointing — checkpoints stay
  single-device arrays, so a run may restore at any ``--devices``.
  ``--devices 1`` (default) is the plain fused path and the numerical
  parity oracle (``tests/test_train_sharded.py``);
- ``--churn NAME``        fleet-churn preset (``none``, ``fail``,
  ``throttle``, ``slowdown``, ``join``, ``mixed`` — see
  ``repro.sim.churn``): each fused round draws a fresh per-episode
  churn schedule on device, so the policy trains against SA failures /
  degradations / elastic joins exactly as the churn benchmarks evaluate
  it.  ``none`` (default) keeps the static-fleet program; churn is a
  single-device feature (``--devices 1``);
- ``--scenario NAME``     arrival-process preset (``default``,
  ``steady``, ``burst``, ``diurnal``, ``heavy_tail`` — see
  ``repro.sim.arrivals``; the fused round draws traces on device via
  ``generate_traces_jax``);
- ``--eval-baselines L``  comma list of baselines ("fcfs,herald,magma")
  evaluated once on the eval seeds before training through the batched
  device-resident runners — MAGMA included, scan-fused — so every run
  logs in-regime reference SLA rates next to the learning curve.

Fault-tolerant training loop:
- periodic atomic checkpoints (CheckpointManager) of the full learner
  state (+ replay is re-warmed on restart, which is sound for an
  off-policy learner); checkpoint/eval cadence and crash injection are
  scan-chunk boundaries;
- per-round PRNG keys fold in the *global* round index
  (``core.train.round_keys``), so a resumed run replays the identical
  randomness stream the uninterrupted run would have;
- ``--fail-at`` injects a crash for restart testing; on startup the
  driver auto-resumes from the latest checkpoint.

Usage:
  PYTHONPATH=src python -m repro.launch.rl_train --workload light \
      --episodes 150 --hidden 64 --batch-episodes 8 --outdir runs/light_med
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.ckpt import CheckpointManager
from repro.core import baselines as BL
from repro.core import policy as P, ddpg as D
from repro.core.generalist import (GeneralistSpec, build_padded_envs,
                                   evaluate_generalist_batch,
                                   generalist_replay_init,
                                   make_generalist_round,
                                   make_generalist_rounds,
                                   make_sharded_generalist_rounds)
from repro.core.replay import replay_init, replay_pair_init
from repro.core.rollout import evaluate_batch, evaluate_batch_baseline
from repro.core.train import (INFO_KEYS, make_device_mesh,
                              make_sharded_train_rounds,
                              make_train_round, make_train_rounds,
                              mesh_replicate, round_keys,
                              shard_round_keys, unreplicate)
from repro.launch.compile_cache import use_compile_cache
from repro.sim.arrivals import ArrivalConfig
from repro.sim.churn import CHURN_SCENARIOS, churn_preset
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.telemetry import (compile_counts, console_line,
                             install_compile_counter, make_telemetry,
                             profile_trace)
from repro.telemetry.metrics import ROUND_TELE_KEYS
from repro.workloads import build_registry


@dataclasses.dataclass
class TrainConfig:
    workload: str = "light"
    # accelerator platform(s) (costmodel.fleets); a comma list trains a
    # fleet-conditioned generalist (repro.core.generalist)
    fleet: str = "paper6"
    # auto | generalist | specialist (auto: generalist iff several fleets)
    policy_kind: str = "auto"
    m_max: int = 0             # generalist pad width (0 = widest fleet)
    # best-checkpoint selection: mean | min_fleet (generalist only:
    # maximin over per-fleet eval SLA — keeps the saved policy from
    # trading its weakest platform away for the mean)
    best_metric: str = "mean"
    qos_level: str = "medium"
    qos_factor: float = 3.0
    load: float = 0.9
    scenario: str = "default"
    bandwidth_gbps: float = 0.0  # 0 = the fleet's dram_gbps
    t_s_us: float = 500.0
    periods: int = 60
    max_rq: int = 96
    max_jobs: int = 64
    hidden: int = 64
    episodes: int = 150
    batch_episodes: int = 8
    # shard each fused round over this many local devices (1 = the
    # single-device fused path, the numerical parity oracle)
    devices: int = 1
    # in-episode fleet-churn preset drawn fresh per fused round
    # (repro.sim.churn); "none" keeps the static-fleet program
    churn: str = "none"
    updates_per_episode: int = 30
    batch_size: int = 32
    replay_capacity: int = 4000
    warmup_episodes: int = 5
    sigma0: float = 0.4
    sigma_min: float = 0.05
    sigma_decay: float = 0.97
    eval_every: int = 10
    eval_seeds: int = 5
    # comma list of baselines to score on the eval seeds before
    # training ("" = skip); "magma" uses the scan-fused GA at the
    # CI-sized 24x12 config (paper settings are 100x100)
    eval_baselines: str = ""
    magma_population: int = 24
    magma_generations: int = 12
    seed: int = 0
    outdir: str = "runs/relmas"
    ckpt_every: int = 10
    fail_at: int = -1          # crash injection (episode index) for FT tests
    # telemetry: "" disables the machine-readable stream; a path streams
    # schema'd JSONL records there AND turns on the in-graph telemetry
    # block inside the fused round (bit-neutral — see docs/OBSERVABILITY.md)
    log_jsonl: str = ""
    # capture a jax.profiler trace of the training loop into this dir
    profile_dir: str = ""


def _env_cfgs(cfg: TrainConfig) -> tuple[EnvConfig, ArrivalConfig]:
    ecfg = EnvConfig(t_s_us=cfg.t_s_us, periods=cfg.periods,
                     max_rq=cfg.max_rq, max_jobs=cfg.max_jobs,
                     bandwidth_gbps=cfg.bandwidth_gbps)
    arr = ArrivalConfig(max_jobs=cfg.max_jobs, load=cfg.load,
                        qos_factor=cfg.qos_factor, qos_level=cfg.qos_level,
                        horizon_us=ecfg.horizon_us,
                        slack_us=2.0 * cfg.t_s_us,
                        scenario=cfg.scenario)
    return ecfg, arr


def build_env(cfg: TrainConfig, fleet: str | None = None) -> SchedulingEnv:
    reg = build_registry(cfg.workload, mas=fleet or cfg.fleet)
    ecfg, arr = _env_cfgs(cfg)
    return SchedulingEnv(reg, ecfg, arr)


def _resolve_kind(cfg: TrainConfig) -> tuple[str, list[str]]:
    """-> (policy_kind, fleet list) with ``auto`` resolved."""
    fleets = [f.strip() for f in cfg.fleet.split(",") if f.strip()]
    kind = cfg.policy_kind
    if kind == "auto":
        kind = "generalist" if len(fleets) > 1 else "specialist"
    if kind not in ("generalist", "specialist"):
        raise ValueError(f"--policy-kind must be auto|generalist|"
                         f"specialist, got {cfg.policy_kind!r}")
    if kind == "specialist" and len(fleets) > 1:
        raise ValueError("a specialist policy is fleet-shaped: train "
                         "one per --fleet, or use "
                         "--policy-kind generalist for a multi-fleet run")
    # fail fast, not after the training budget is spent at the first eval
    if cfg.best_metric not in ("mean", "min_fleet"):
        raise ValueError(f"--best-metric must be mean|min_fleet, got "
                         f"{cfg.best_metric!r}")
    if cfg.best_metric == "min_fleet" and kind != "generalist":
        raise ValueError("--best-metric min_fleet needs per-fleet eval — "
                         "a generalist run (--fleet a,b,... or "
                         "--policy-kind generalist)")
    return kind, fleets


def _plan_chunks(cfg: TrainConfig, start_ep: int) -> list[dict]:
    """Group training rounds into scan chunks.

    A chunk is a run of consecutive rounds with the same episode batch
    size and no interior boundary; eval/ckpt cadence, the final round,
    a batch-size change (the tail round), and the crash-injection round
    all end (or, for ``fail_at``, start) a chunk.  Each chunk dict
    carries its rounds ``[(start_ep, n), ...]``, the first round's
    global index (for the PRNG key stream), whether to raise the
    injected failure instead of dispatching, and the boundary actions
    (``eval`` / ``ckpt``) the driver must take after it — the planner
    is the single source of truth for cadence.
    """
    def crossed(every: int, s: int, ep: int) -> bool:
        return (ep + 1) // every > s // every

    chunks: list[dict] = []
    cur: list[tuple[int, int]] = []
    s = start_ep
    while s < cfg.episodes:
        n = min(cfg.batch_episodes, cfg.episodes - s)
        ep = s + n - 1
        fail_here = s <= cfg.fail_at <= ep
        if cur and (fail_here or n != cur[0][1]):
            chunks.append(dict(rounds=cur, fail=False, eval=False,
                               ckpt=False))
            cur = []
        cur.append((s, n))
        do_eval = crossed(cfg.eval_every, s, ep) or ep == cfg.episodes - 1
        do_ckpt = crossed(cfg.ckpt_every, s, ep)
        if fail_here or do_eval or do_ckpt:
            chunks.append(dict(rounds=cur, fail=fail_here,
                               eval=do_eval and not fail_here,
                               ckpt=do_ckpt and not fail_here))
            cur = []
        s += n
    if cur:
        chunks.append(dict(rounds=cur, fail=False, eval=False, ckpt=False))
    for c in chunks:
        c["round0"] = c["rounds"][0][0] // cfg.batch_episodes
    return chunks


def train(cfg: TrainConfig, log_fn=print) -> dict:
    if cfg.batch_episodes < 1:
        raise ValueError(f"--batch-episodes must be >= 1, "
                         f"got {cfg.batch_episodes}")
    if cfg.batch_episodes * cfg.periods > cfg.replay_capacity:
        # one ring scatter cannot wrap the buffer more than once
        raise ValueError(
            f"a collection round writes batch_episodes * periods = "
            f"{cfg.batch_episodes * cfg.periods} transitions, which must "
            f"fit --replay-capacity ({cfg.replay_capacity})")
    if cfg.devices < 1:
        raise ValueError(f"--devices must be >= 1, got {cfg.devices}")
    if cfg.churn not in CHURN_SCENARIOS:
        raise ValueError(f"--churn must be one of "
                         f"{'|'.join(CHURN_SCENARIOS)}, got {cfg.churn!r}")
    churn_cfg = None if cfg.churn == "none" else churn_preset(cfg.churn)
    if churn_cfg is not None and cfg.devices > 1:
        raise ValueError("--churn is a single-device feature: the "
                         "sharded round bodies do not thread churn "
                         "schedules; use --devices 1")
    if cfg.devices > 1:
        # fail fast with actionable messages, not inside shard_map tracing
        ndev = jax.local_device_count()
        if cfg.devices > ndev:
            raise ValueError(
                f"--devices {cfg.devices} exceeds jax.local_device_count()"
                f" = {ndev}; use --devices {ndev} or fewer (on CPU, "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"exposes N host devices)")
        for knob, val in (("batch-episodes", cfg.batch_episodes),
                          ("batch-size", cfg.batch_size),
                          ("replay-capacity", cfg.replay_capacity)):
            if val % cfg.devices:
                raise ValueError(f"--{knob} {val} must be divisible by "
                                 f"--devices {cfg.devices} (equal shards)")
        if cfg.episodes % cfg.batch_episodes:
            raise ValueError(
                f"--episodes {cfg.episodes} must be a multiple of "
                f"--batch-episodes {cfg.batch_episodes} when sharding "
                f"(a smaller tail round cannot split evenly over "
                f"--devices {cfg.devices})")
    install_compile_counter()
    compiled0 = compile_counts()
    kind, fleets = _resolve_kind(cfg)
    # telemetry session: console sink always (through log_fn, so test
    # captures keep working), JSONL stream when --log-jsonl was given;
    # the same flag turns on the in-graph telemetry block inside the
    # fused round (bit-neutral, rides the existing chunk transfer)
    if cfg.log_jsonl:
        os.makedirs(os.path.dirname(cfg.log_jsonl) or ".", exist_ok=True)
    tele = make_telemetry(log_fn=log_fn, jsonl_path=cfg.log_jsonl or None)
    dev_tele = bool(cfg.log_jsonl)
    tele.run_header("train", dataclasses.asdict(cfg))
    ecfg, arr = _env_cfgs(cfg)
    if kind == "generalist":
        envs = build_padded_envs(cfg.workload, fleets, ecfg, arr,
                                 m_max=cfg.m_max or None)
        env = envs[0]
        spec = GeneralistSpec(m_max=env.num_sas)
        pcfg = spec.pcfg(hidden=cfg.hidden)
        tele.note(f"[generalist] fleets={','.join(fleets)} "
                  f"m_max={spec.m_max} desc_dim={spec.desc_dim} "
                  f"feat_dim={pcfg.feat_dim}")
    else:
        envs, spec = None, None
        env = build_env(cfg)
        pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                              hidden=cfg.hidden)
    dcfg = D.DDPGConfig(policy=pcfg)
    key = jax.random.PRNGKey(cfg.seed)
    state = D.init_ddpg(key, dcfg)
    mgr = CheckpointManager(os.path.join(cfg.outdir, "ckpt"))
    start_ep = 0
    if (step := mgr.latest_step()) is not None:      # auto-resume
        try:
            state, step, meta = mgr.restore(state, step)
        except ValueError as e:
            # policy shapes follow --hidden, --policy-kind, and the
            # fleet's num_sas (feat/act dims) — a resume with any of
            # them changed lands here
            raise ValueError(
                f"checkpoint in {cfg.outdir} does not match this run's "
                f"policy shapes — resume with the --hidden/--fleet/"
                f"--policy-kind it was trained with (this run: --hidden "
                f"{cfg.hidden} --fleet {cfg.fleet} [{kind}]) or use a "
                f"fresh --outdir [{e}]") from None
        ck_kind = meta.get("policy_kind", "specialist")
        ck_fleet = meta.get("fleet", "paper6")
        if ck_kind == "generalist" or kind == "generalist":
            # a generalist is fleet-independent by construction: accept
            # the checkpoint on ANY fleet list (shape mismatches — a
            # different m_max/hidden — were already caught above); a
            # kind flip between runs also lands in the shape error
            if ck_kind != kind:
                raise ValueError(
                    f"checkpoint in {cfg.outdir} is {ck_kind!r} but this "
                    f"run is {kind!r}; use a fresh --outdir")
            if ck_fleet != cfg.fleet:
                tele.note(f"[resume] generalist checkpoint trained on "
                          f"{ck_fleet!r}, continuing on {cfg.fleet!r}")
        elif ck_fleet != cfg.fleet:
            # legacy per-fleet checkpoints stay platform-locked:
            # same-width fleets restore cleanly but are different
            # platforms — refuse to silently continue cross-fleet
            raise ValueError(
                f"checkpoint in {cfg.outdir} was trained on fleet "
                f"{ck_fleet!r} but --fleet is {cfg.fleet!r}; use a fresh "
                f"--outdir to train a {cfg.fleet!r} agent")
        start_ep = meta.get("episode", 0) + 1
        tele.note(f"[resume] restored checkpoint at episode {start_ep - 1}")

    baseline_scores: dict[str, dict] = {}
    if cfg.eval_baselines:
        # reference points on the exact eval seeds/regime, all through
        # the batched device-resident runners (one jitted call each);
        # heuristics act on raw slot tables, so a generalist run scores
        # them on each fleet's UNPADDED env (padding columns would
        # distort cost-greedy baselines)
        eval_seed_range = range(7000, 7000 + cfg.eval_seeds)
        benvs = ([build_env(cfg, f) for f in fleets]
                 if kind == "generalist" else [env])
        for name in cfg.eval_baselines.split(","):
            name = name.strip()
            fn = (BL.make_magma_baseline(BL.MagmaConfig(
                      population=cfg.magma_population,
                      generations=cfg.magma_generations))
                  if name == "magma" else BL.BASELINES[name])
            ms = [evaluate_batch_baseline(e, fn, eval_seed_range)
                  for e in benvs]
            m = {k: float(np.mean([x[k] for x in ms])) for k in ms[0]}
            baseline_scores[name] = {k: round(v, 4) for k, v in m.items()}
            tele.emit("baseline", name=name,
                      sla_rate=round(m["sla_rate"], 4))

    sharded = cfg.devices > 1
    devs = jax.local_devices()[:cfg.devices]
    mesh = make_device_mesh(devs) if sharded else None
    # mesh_replicate lays the leading D axis out over the mesh axis so
    # shard_map moves no data
    repl = lambda t: mesh_replicate(t, mesh)
    if not sharded and len(jax.local_devices()) > 1:
        # --devices N shards the fused round over N local devices
        # (collection splits, the update consumes all-gathered global
        # minibatches, per-device double-buffered rings; see
        # docs/ARCHITECTURE.md "Mesh-sharded rounds"); default is the
        # single-device fused path
        tele.note(f"[note] {len(jax.local_devices())} local devices; pass "
                  f"--devices N to shard the fused rounds over them")

    cap = cfg.replay_capacity // cfg.devices     # per-device ring shard
    buf = (generalist_replay_init(cap, env.seq_len, spec)
           if kind == "generalist" else
           replay_init(cap, env.seq_len, env.feat_dim, env.act_dim))
    if sharded:
        # per-device double-buffered ring pair; checkpoints never hold
        # replay, so restore stays device-count-agnostic
        round_size = (cfg.batch_episodes // cfg.devices) * cfg.periods
        buf = repl(replay_pair_init(buf, round_size))
    os.makedirs(cfg.outdir, exist_ok=True)
    logf = open(os.path.join(cfg.outdir, "log.jsonl"), "a")
    if baseline_scores:
        logf.write(json.dumps({"baselines": baseline_scores}) + "\n")
        logf.flush()
    best = {"sla_rate": -1.0}
    history = []
    sigma = jnp.float32(max(cfg.sigma_min,
                            cfg.sigma0 * cfg.sigma_decay ** start_ep))

    def trainer_kw(n: int) -> dict:
        kw = dict(batch_episodes=n,
                  num_updates=cfg.updates_per_episode * n,
                  batch_size=cfg.batch_size, sigma_min=cfg.sigma_min,
                  sigma_decay=cfg.sigma_decay, telemetry=dev_tele)
        if churn_cfg is not None:   # single-device only (validated above)
            kw["churn"] = churn_cfg
        return kw

    if kind == "generalist":
        make_round = lambda **kw: make_generalist_round(envs, dcfg, **kw)
        make_rounds = lambda **kw: make_generalist_rounds(envs, dcfg, **kw)
        make_sharded = lambda **kw: make_sharded_generalist_rounds(
            envs, dcfg, mesh=mesh, **kw)

        def eval_policy_fn(params, seeds):
            """Mean metrics across every training fleet (+ per-fleet)."""
            per = {f: evaluate_generalist_batch(e, pcfg, params, seeds)
                   for f, e in zip(fleets, envs)}
            mean = {k: float(np.mean([m[k] for m in per.values()]))
                    for k in next(iter(per.values()))}
            mean["per_fleet"] = {f: round(m["sla_rate"], 4)
                                 for f, m in per.items()}
            return mean
    else:
        make_round = lambda **kw: make_train_round(env, dcfg, **kw)
        make_rounds = lambda **kw: make_train_rounds(env, dcfg, **kw)
        make_sharded = lambda **kw: make_sharded_train_rounds(
            env, dcfg, mesh=mesh, **kw)
        eval_policy_fn = lambda params, seeds: evaluate_batch(
            env, pcfg, params, seeds)

    if sharded:
        # learner state and sigma replicate once (and once more after
        # any restore above); chunk boundaries unreplicate for
        # eval/checkpointing so saved artifacts stay single-device
        state = repl(state)
        sigma = repl(sigma)

    ckpt_meta = dict(fleet=cfg.fleet, policy_kind=kind,
                     hidden=cfg.hidden, feat_dim=pcfg.feat_dim,
                     act_dim=pcfg.act_dim, churn=cfg.churn)
    if spec is not None:
        ckpt_meta.update(m_max=spec.m_max, desc_dim=spec.desc_dim,
                         fleets=fleets)

    with profile_trace(cfg.profile_dir):
      for chunk in _plan_chunks(cfg, start_ep):
        if chunk["fail"]:
            raise RuntimeError(f"injected failure at episode {cfg.fail_at}")
        rounds = chunk["rounds"]
        n = rounds[0][1]
        flags = np.array([s + m > cfg.warmup_episodes for s, m in rounds])
        keys = round_keys(cfg.seed + 1, chunk["round0"], len(rounds))
        t0 = time.time()
        # span "collect": the chunk dispatch INCLUDING the metrics
        # transfer — the honest wall-clock cost of the fused rounds
        with tele.span("collect", episodes=int(sum(m for _, m in rounds))):
            if sharded:
                # chunk sharded over the device axis: ONE jitted
                # shard_map dispatch; keys fold in the device index, the
                # generalist's fleet draw uses the shared (replicated,
                # un-sharded) round keys
                rounds_fn = make_sharded(**trainer_kw(n))
                dkeys = shard_round_keys(keys, cfg.devices)
                args = ((state, buf, dkeys, keys, sigma, jnp.asarray(flags))
                        if kind == "generalist" else
                        (state, buf, dkeys, sigma, jnp.asarray(flags)))
                state, buf, sigma, mets = rounds_fn(*args)
                # row 0 carries the pmean'd global round averages
                mets = jax.tree.map(lambda x: np.asarray(x)[0], mets)
            elif len(rounds) == 1:
                # single round (tail / tight cadence): one jitted dispatch
                round_fn = make_round(**trainer_kw(n))
                state, buf, sigma, mets = round_fn(state, buf, keys[0],
                                                   sigma, bool(flags[0]))
                mets = jax.tree.map(lambda x: np.asarray(x)[None], mets)
            else:
                # a whole eval/ckpt chunk of rounds in one scan dispatch
                rounds_fn = make_rounds(**trainer_kw(n))
                state, buf, sigma, mets = rounds_fn(state, buf, keys, sigma,
                                                    jnp.asarray(flags))
                # one transfer per chunk
                mets = jax.tree.map(np.asarray, mets)
        elapsed = max(time.time() - t0, 1e-9)
        chunk_eps = sum(m for _, m in rounds)
        pps = round(chunk_eps * cfg.periods / elapsed, 1)

        for i, (rs, rn) in enumerate(rounds):
            ep = rs + rn - 1
            rec = dict(episode=ep, batch_episodes=rn,
                       sla=round(float(mets["sla"][i]), 4),
                       sigma=round(float(mets["sigma"][i]), 4),
                       periods_per_sec=pps,
                       secs=round(elapsed / len(rounds), 3))
            if "fleet" in mets:     # generalist: sampled fleet per round
                rec["fleet"] = fleets[int(mets["fleet"][i])]
            if mets["did_update"][i]:
                rec.update({k: round(float(mets[k][i]), 5)
                            for k in INFO_KEYS})
            history.append(rec)
            logf.write(json.dumps(rec) + "\n")
            emit = dict(rec)
            if all(k in mets for k in ROUND_TELE_KEYS):
                # the in-graph block: already on host via the chunk's
                # existing metrics transfer — zero added syncs
                emit.update(
                    replay_fill=round(float(mets["tele_replay_fill"][i]), 4),
                    sla_hist=[int(x) for x in mets["tele_sla_hist"][i]],
                    reward_hist=[int(x) for x in mets["tele_reward_hist"][i]],
                    committed=int(mets["tele_committed"][i]))
            tele.emit("train_round", **emit)
        logf.flush()

        # chunk boundary: eval / best-checkpoint / periodic checkpoint
        # (the planner already decided which actions this chunk ends on)
        rs, rn = rounds[-1]
        ep = rs + rn - 1
        st = unreplicate(state) if sharded else state
        if chunk["eval"]:
            with tele.span("eval"):
                ev = eval_policy_fn(st.actor,
                                    seeds=range(7000,
                                                7000 + cfg.eval_seeds))
            history[-1]["eval_sla"] = round(ev["sla_rate"], 4)
            evrec = {"episode": ep, "eval_sla": history[-1]["eval_sla"]}
            if "per_fleet" in ev:
                history[-1]["eval_sla_per_fleet"] = ev["per_fleet"]
                evrec["eval_sla_per_fleet"] = ev["per_fleet"]
            logf.write(json.dumps(evrec) + "\n")
            logf.flush()
            tele.emit("train_eval", **evrec)
            score = (min(ev["per_fleet"].values())
                     if cfg.best_metric == "min_fleet"
                     else ev["sla_rate"])   # validated in _resolve_kind
            if score > best.get("score", -1.0):
                best = {**ev, "episode": ep, "score": score}
                mgr_best = CheckpointManager(
                    os.path.join(cfg.outdir, "best"), keep=1)
                mgr_best.save(ep, st.actor,
                              dict(episode=ep, sla=ev["sla_rate"],
                                   **ckpt_meta))
        if chunk["ckpt"]:
            # single-device arrays: restore works at any --devices
            with tele.span("ckpt"):
                mgr.save(ep, st, dict(episode=ep, **ckpt_meta))
    logf.close()
    if sharded:
        state = unreplicate(state)
    tele.emit("run_end", best_sla=round(float(best.get("sla_rate", -1.0)), 4),
              compile=compile_counts(since=compiled0))
    tele.close()
    return dict(best=best, history=history, env=env, pcfg=pcfg, state=state,
                baselines=baseline_scores, policy_kind=kind, fleets=fleets,
                spec=spec)


_HELP = {
    "workload": "tenant set: light | heavy | mixed (workloads.cnn_zoo)",
    "fleet": "accelerator-fleet preset(s) (repro.costmodel.fleets): paper6, "
             "4simba_4eyeriss, 8simba, 8eyeriss, 2simba_6eyeriss, "
             "big_little, ...; one name = per-fleet specialist, a comma "
             "list = fleet-conditioned generalist (one fleet sampled per "
             "fused round)",
    "policy_kind": "auto | generalist | specialist (auto: generalist iff "
                   "several fleets; generalist checkpoints restore on any "
                   "fleet with num_sas <= m_max)",
    "m_max": "generalist SA-channel pad width (0 = widest requested fleet)",
    "best_metric": "best-checkpoint selection: mean | min_fleet (maximin "
                   "over per-fleet eval SLA; generalist runs only)",
    "bandwidth_gbps": "shared DRAM GB/s; 0 = the fleet's dram_gbps",
    "scenario": "arrival preset: default | steady | burst | diurnal | "
                "heavy_tail (sim.arrivals)",
    "batch_episodes": "episodes collected per fused training round",
    "devices": "shard each fused round over N local devices "
               "(jit-of-shard_map on a 1-D mesh: collection splits, each "
               "update all-gathers a global union-pool minibatch, "
               "per-device double-buffered replay rings); requires "
               "batch-episodes/batch-size/replay-capacity divisible by N "
               "and N <= jax.local_device_count(); 1 = single-device "
               "fused path (parity oracle)",
    "churn": "in-episode fleet-churn preset drawn fresh per fused round: "
             "none | fail | throttle | slowdown | join | mixed "
             "(sim.churn); single-device only",
    "eval_baselines": 'comma list scored on the eval seeds before '
                      'training, e.g. "fcfs,herald,magma" ("" = skip)',
    "fail_at": "inject a crash at this episode (fault-tolerance tests)",
    "log_jsonl": "stream schema'd JSONL telemetry records to this path and "
                 "enable the in-graph telemetry block (bit-neutral; "
                 "validate/render with scripts/metrics_summary.py)",
    "profile_dir": "capture a jax.profiler trace of the training loop "
                   "into this directory (view in TensorBoard/Perfetto)",
}


def main(argv=None):
    use_compile_cache()
    ap = argparse.ArgumentParser(
        description="RELMAS DDPG training driver (single-dispatch fused "
                    "rounds; see module docstring / docs/ARCHITECTURE.md)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    for f in dataclasses.fields(TrainConfig):
        ap.add_argument(f"--{f.name.replace('_', '-')}", type=type(f.default),
                        default=f.default, help=_HELP.get(f.name, " "))
    args = ap.parse_args(argv)
    cfg = TrainConfig(**vars(args))
    console_line(f"RELMAS DDPG training: {cfg}")
    out = train(cfg)
    console_line(f"best eval: {out['best']}")


if __name__ == "__main__":
    main()
