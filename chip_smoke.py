"""Chip smoke run: drive the RELMAS scheduler's main path once on a TPU.

One process, no fallback: if JAX finds no TPU the script names the
platform it found and exits non-zero before any phase runs.

Default (one chip), at the widths ``rl_train`` trains with
(``paper6`` fleet, ``light`` tenants, max_rq 96, max_jobs 64, hidden 64):

1. ``engine``: ``sim.engine.simulate_jax`` against the float64
   ``simulate_np`` oracle on seeded random ready queues built from the
   fleet's own latency and bandwidth tables.
2. ``train``: ``rl_train.train`` for 3 fused rounds (one warmup round,
   two with DDPG updates), a ``fcfs`` baseline eval and one policy eval
   that writes ``best/``.
3. ``serve``: a ``MultiTenantService`` restores that ``best/``
   checkpoint (checked against the trained actor, since the service
   would fall back to an untrained policy in silence) and serves 96
   ``loadgen`` streams through the batched tick; then the batched tick
   and the per-period host loop serve the same few traces, for the
   relmas and the fcfs arm, and must agree on SLA, hits and counted.

``--chips 4`` runs only the mesh-sharded training chunk
(``core.train.make_sharded_train_rounds``, what ``rl_train --devices N``
runs) on a 4-chip mesh and compares it with its vmap oracle
``sharded_rounds_reference`` on the same keys, at the tolerances of
``tests/test_train_sharded.py``, for two seeds; the replay rings'
SA-busy features carry engine finish times and are held to the engine's
tolerance (see ``_check_sharded``).

The seconds printed per phase are smoke-run figures (compile and wall
time of one cold or warm pass), not benchmark results.  The last line of
standard output is ``{"ok": true, "device": {...}}``.

Usage:
  python chip_smoke.py [--chips 4] [--out DIR]
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
# the checkout's own sources, not an installed package
sys.path.insert(0, os.path.join(ROOT, "src"))

# tolerances of tests/test_engine.py (float32 engine vs float64 oracle)
# and tests/test_train_sharded.py (shard_map vs vmap oracle)
ENGINE_TOL = dict(rtol=1e-3, atol=1e-2)
SHARDED_METRIC_ATOL = 1e-4
SHARDED_PARAM_ATOL = 1e-4
SHARDED_RING_ATOL = 1e-6

SEED = 0
# the 4-chip comparison is made from two seeds' keys and initial states
SHARDED_SEEDS = (SEED, SEED + 1)

# rl_train's default training widths
WIDTHS = dict(workload="light", fleet="paper6", max_rq=96, max_jobs=64,
              periods=60, hidden=64, batch_episodes=8,
              updates_per_episode=30, batch_size=32, replay_capacity=4000)


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# per-phase compile / wall accounting
# ---------------------------------------------------------------------------
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_counts: collections.Counter = collections.Counter()
_secs: collections.Counter = collections.Counter()


def _on_event(event: str, **_) -> None:
    _counts[event] += 1


def _on_duration(event: str, secs: float, **_) -> None:
    _secs[event] += secs


@contextlib.contextmanager
def phase(name: str):
    c0, s0 = collections.Counter(_counts), collections.Counter(_secs)
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    compile_s = sum(_secs[e] - s0[e] for e in _COMPILE_EVENTS)
    hits = _counts["/jax/compilation_cache/cache_hits"] - \
        c0["/jax/compilation_cache/cache_hits"]
    misses = _counts["/jax/compilation_cache/cache_misses"] - \
        c0["/jax/compilation_cache/cache_misses"]
    print(f"[smoke run, not a benchmark] phase={name} wall_s={wall:.3f} "
          f"compile_s={compile_s:.3f} cache_hits={hits} "
          f"cache_misses={misses}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def random_queues(registry, n_queues: int, R: int, t_s: float, seed: int):
    """Seeded ready queues of ``R`` slots in the env's packing: jobs of
    consecutive layers chained by ``dep``, per-slot costs and bandwidth
    demands read from the fleet's tables at a random SA assignment."""
    d = registry.dense()
    M = d["num_sas"]
    rng = np.random.default_rng(seed)
    cols = collections.defaultdict(list)
    for _ in range(n_queues):
        n_valid = int(rng.integers(1, R + 1))
        model = np.zeros(R, np.int64)
        layer = np.zeros(R, np.int64)
        dep = np.full(R, -1, np.int64)
        ready = np.zeros(R)
        i = 0
        while i < n_valid:
            m = int(rng.integers(d["num_models"]))
            first = int(rng.integers(d["n_layers"][m]))
            k = min(int(d["n_layers"][m]) - first, n_valid - i)
            model[i:i + k] = m
            layer[i:i + k] = np.arange(first, first + k)
            dep[i + 1:i + k] = np.arange(i, i + k - 1)
            ready[i] = rng.uniform(0.0, 2.0 * t_s)
            i += k
        valid = np.arange(R) < n_valid
        assign = rng.integers(0, M, R)
        cols["valid"].append(valid)
        cols["assign"].append(assign)
        cols["prio"].append(rng.uniform(-1.0, 1.0, R))
        cols["cost"].append(np.where(valid, d["lat"][model, layer, assign], 0))
        cols["bw"].append(np.where(valid, d["bw"][model, layer, assign], 0))
        cols["dep"].append(dep)
        cols["ready"].append(ready)
        cols["sa_free"].append(rng.uniform(0.0, t_s, M))
    return {k: np.stack(v) for k, v in cols.items()}, M


def phase_engine(n_queues: int = 300) -> None:
    from repro.sim.engine import INF, simulate_jax, simulate_np
    from repro.workloads import build_registry
    reg = build_registry(WIDTHS["workload"], mas=WIDTHS["fleet"])
    B = float(reg.mas.dram_gbps)
    q, M = random_queues(reg, n_queues, WIDTHS["max_rq"], 500.0, SEED)
    run = jax.jit(jax.vmap(lambda *a: simulate_jax(
        *a, jnp.float32(B), num_sas=M)))
    f32 = lambda k: jnp.asarray(q[k], jnp.float32)
    i32 = lambda k: jnp.asarray(q[k], jnp.int32)
    start, finish = jax.device_get(run(
        jnp.asarray(q["valid"]), i32("assign"), f32("prio"), f32("cost"),
        f32("bw"), i32("dep"), f32("ready"), f32("sa_free")))
    names = ("valid", "assign", "prio", "cost", "bw", "dep", "ready",
             "sa_free")
    bad = []
    for i in range(n_queues):
        s, f = simulate_np(*(q[k][i] for k in names), B)
        v = q["valid"][i]
        ok = (np.all(np.isfinite(f[v])) and np.all(finish[i][v] < INF / 2)
              and np.allclose(start[i][v], s[v], **ENGINE_TOL)
              and np.allclose(finish[i][v], f[v], **ENGINE_TOL))
        if not ok:
            bad.append(i)
    print(f"engine: {n_queues - len(bad)}/{n_queues} queues of "
          f"{WIDTHS['max_rq']} slots on M={M} match the float64 oracle "
          f"(rtol={ENGINE_TOL['rtol']}, atol={ENGINE_TOL['atol']})",
          flush=True)
    check(not bad, f"engine disagrees with simulate_np on queues {bad[:10]}")


def phase_train(out_dir: str) -> dict:
    from repro.ckpt.checkpoint import latest_step
    from repro.core.train import INFO_KEYS
    from repro.launch.rl_train import TrainConfig, train
    run_dir = tempfile.mkdtemp(prefix="train-", dir=out_dir)
    cfg = TrainConfig(**WIDTHS, episodes=24, warmup_episodes=8,
                      eval_every=24, ckpt_every=24, eval_baselines="fcfs",
                      seed=SEED, outdir=run_dir)
    out = train(cfg)
    hist = out["history"]
    check(len(hist) == 3, f"expected 3 rounds, got {len(hist)}")
    updated = [r for r in hist if "critic_loss" in r]
    check(updated, "no round ran a DDPG update")
    for r in updated:
        check(all(np.isfinite(r[k]) for k in INFO_KEYS),
              f"non-finite loss in round {r}")
    slas = ([r["sla"] for r in hist] + [hist[-1]["eval_sla"],
            out["baselines"]["fcfs"]["sla_rate"]])
    check(all(0.0 <= s <= 1.0 for s in slas), f"SLA outside [0, 1]: {slas}")
    best_dir = os.path.join(run_dir, "best")
    check(latest_step(best_dir) is not None, f"no checkpoint in {best_dir}")
    # the only eval is the last round's, so best/ holds the final actor
    check(out["best"]["episode"] == cfg.episodes - 1,
          f"best checkpoint is from episode {out['best']['episode']}")
    print(f"train: rounds={len(hist)} updated={len(updated)} "
          f"sla={[r['sla'] for r in hist]} eval_sla={hist[-1]['eval_sla']} "
          f"fcfs_sla={out['baselines']['fcfs']['sla_rate']} "
          f"best={best_dir}", flush=True)
    return dict(out=out, best_dir=best_dir, hidden=cfg.hidden)


def _parity(svc, env, streams: int = 4) -> list[dict]:
    """serving_bench's parity check: the same traces through the batched
    tick and the per-period host loop; returns the mismatching streams."""
    from repro.serving import trace_to_requests
    traces = [env.new_episode(np.random.default_rng(SEED + 1000 + s))[0]
              for s in range(streams)]
    refs = [svc.serve_trace_host(tr, seed=SEED + 7) for tr in traces]
    got = svc.serve_stream([trace_to_requests(env, tr) for tr in traces],
                           tick_k=env.cfg.max_jobs, seed=SEED + 7)["metrics"]
    keys = ("sla_rate", "hits", "counted")
    return [dict(stream=s, host={k: ref[k] for k in keys},
                 batched={k: m[k] for k in keys})
            for s, (ref, m) in enumerate(zip(refs, got))
            if any(ref[k] != m[k] for k in keys)]


def phase_serve(trained: dict) -> None:
    from repro.serving import LoadGenConfig, MultiTenantService, \
        request_streams
    env = trained["out"]["env"]
    svc = MultiTenantService(env.registry, policy="relmas",
                             ckpt_dir=trained["best_dir"],
                             hidden=trained["hidden"], env_cfg=env.cfg,
                             arrivals=env.arrivals)
    actor = trained["out"]["state"].actor
    same = jax.tree.all(jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        svc.params, actor))
    check(svc.policy_kind == "specialist" and same,
          "the service did not restore the trained checkpoint")
    reqs = request_streams(svc.env, LoadGenConfig(scenario="steady",
                                                  rate_scale=1.0,
                                                  n_requests=32),
                           96, seed=SEED + 5)
    res = svc.serve_stream(reqs, tick_k=8, ticks=60, seed=SEED + 10)
    agg = res["aggregate"]
    ticks = np.asarray(res["stats"]["tick_wall_us"])
    print(f"serve: streams=96 ticks={len(ticks)} counted={agg['counted']} "
          f"sla={agg['sla_rate']:.4f} deferred={res['stats']['deferred']} "
          f"tick_wall_us first={ticks[0]:.0f} "
          f"median_rest={np.median(ticks[1:]):.0f}", flush=True)
    check(agg["counted"] > 0, "no request was counted")
    check(0.0 <= agg["sla_rate"] <= 1.0, f"SLA {agg['sla_rate']}")
    fcfs = MultiTenantService(env.registry, policy="fcfs", env_cfg=env.cfg,
                              arrivals=env.arrivals)
    mism = {arm: _parity(s, s.env) for arm, s in
            (("relmas", svc), ("fcfs", fcfs))}
    print("serve parity (batched tick vs host loop, 4 traces): "
          + json.dumps({arm: m or "equal" for arm, m in mism.items()}),
          flush=True)
    check(not mism["fcfs"], "fcfs: batched tick differs from the host loop")
    check(not mism["relmas"],
          "relmas: batched tick differs from the host loop")


def phase_sharded(devices, rounds: int = 2) -> None:
    from repro.core import ddpg as D
    from repro.core import policy as P
    from repro.core.replay import replay_init, replay_pair_init
    from repro.core.train import (make_device_mesh,
                                  make_sharded_train_rounds,
                                  mesh_replicate, round_keys,
                                  shard_round_keys,
                                  sharded_rounds_reference)
    from repro.launch.rl_train import TrainConfig, build_env
    n = len(devices)
    cfg = TrainConfig(**WIDTHS, devices=n, seed=SEED)
    env = build_env(cfg)
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=cfg.hidden)
    dcfg = D.DDPGConfig(policy=pcfg)
    kw = dict(batch_episodes=cfg.batch_episodes,
              num_updates=cfg.updates_per_episode * cfg.batch_episodes,
              batch_size=cfg.batch_size, sigma_min=cfg.sigma_min,
              sigma_decay=cfg.sigma_decay)
    flags = jnp.arange(rounds) > 0            # first round is warmup
    round_size = cfg.batch_episodes // n * cfg.periods
    sigma0 = jnp.float32(cfg.sigma0)

    def fresh(seed):
        state = D.init_ddpg(jax.random.PRNGKey(seed), dcfg)
        pair = replay_pair_init(
            replay_init(cfg.replay_capacity // n, env.seq_len,
                        env.feat_dim, env.act_dim), round_size)
        return state, pair

    mesh = make_device_mesh(devices)
    repl = lambda t: mesh_replicate(t, mesh)
    stack = lambda t: jax.tree.map(lambda x: jnp.stack([x] * n), t)
    mesh_rounds = make_sharded_train_rounds(env, dcfg, mesh=mesh, **kw)
    ref_rounds = sharded_rounds_reference(env, dcfg, num_devices=n, **kw)
    for seed in SHARDED_SEEDS:
        dkeys = shard_round_keys(round_keys(seed + 1, 0, rounds), n)
        # float32 matmuls in both arms: at the TPU's default (one bf16
        # pass) the two programs round differently, and 240 Adam updates
        # carry that apart by ~1e-3 (actor_loss); the comparison is about
        # the sharding, which must hold at the tests' 1e-4
        with jax.default_matmul_precision("float32"):
            state, pair = fresh(seed)
            mesh_out = jax.block_until_ready(mesh_rounds(
                repl(state), repl(pair), dkeys, repl(sigma0), flags))
            state, pair = fresh(seed)
            ref_out = ref_rounds(stack(state), stack(pair), dkeys,
                                 stack(sigma0), flags)
        _check_sharded(env, devices, seed, mesh_out, ref_out)
    print(f"sharded: {rounds} rounds on {n} chips match the vmap oracle "
          f"for seeds {list(SHARDED_SEEDS)}; replicas bit-identical; "
          f"every output on all {n} chips", flush=True)


def _check_sharded(env, devices, seed: int, mesh_out, ref_out) -> None:
    from repro.core.replay import replay_fields
    from repro.core.train import unreplicate
    homes = {frozenset(sh.device for sh in leaf.addressable_shards)
             for leaf in jax.tree.leaves(mesh_out)}
    (s1, p1, _, m1), (s2, p2, _, m2) = jax.device_get((mesh_out, ref_out))
    gap = lambda a, b: float(np.max(np.abs(np.asarray(a, np.float64) - b)))
    metric_gap = {k: gap(m1[k], m2[k]) for k in m1}
    metric_bad = [k for k in m1
                  if not np.allclose(m1[k], m2[k], atol=SHARDED_METRIC_ATOL)]
    actor_gap = max(jax.tree.leaves(jax.tree.map(
        gap, unreplicate(s1).actor, unreplicate(s2).actor)))
    spread = max(gap(x, x[:1]) for x in jax.tree.leaves(s1))
    # The primer's SA-busy features are an engine finish time less the
    # clock, both absolute float32 times, so they keep that time's
    # rounding: on the TPU the mesh program and the vmap oracle lay the
    # engine out differently and may end a few ulps of sa_free apart.
    # Those entries are held to the engine's own tolerance (ENGINE_TOL
    # atol, in us), every other ring entry to the tests' 1e-6.
    busy, us_per_unit = env.primer_sa_busy
    busy = (Ellipsis, *busy)
    busy_atol = ENGINE_TOL["atol"] / us_per_unit
    ring_bad, busy_gap = {}, 0.0
    for ring in ("read", "write"):
        for k in list(replay_fields(p1[ring])) + ["ptr", "size"]:
            a, b = p1[ring][k], p2[ring][k]
            if a.dtype.kind != "f":
                off = a != b
            else:
                off = ~np.isclose(a, b, atol=SHARDED_RING_ATOL)
                if k in ("s", "s2"):
                    off[busy] = ~np.isclose(a[busy], b[busy], atol=busy_atol)
                    busy_gap = max(busy_gap, gap(a[busy], b[busy]))
            if off.any():
                ring_bad[f"{ring}.{k}"] = dict(
                    n=int(off.sum()), of=int(off.size), gap=gap(a, b))
    print(f"sharded seed={seed}: devices={[d.id for d in devices]} output "
          f"placements={[sorted(d.id for d in h) for h in homes]} "
          f"metric_gap={json.dumps(metric_gap)} actor_gap={actor_gap:.3g} "
          f"replica_spread={spread} ring_mismatch={ring_bad} "
          f"sa_busy_gap={busy_gap:.3g} (atol {busy_atol:.3g}) "
          f"sla={m1['sla'][0].tolist()}", flush=True)
    check(homes == {frozenset(devices)},
          "outputs are not spread over the mesh's devices")
    check(not metric_bad, f"seed {seed}: round metrics differ from the "
          f"oracle: {metric_bad}")
    check(actor_gap < SHARDED_PARAM_ATOL,
          f"seed {seed}: actor differs from the oracle by {actor_gap}")
    check(spread == 0.0, f"seed {seed}: learner replicas differ by {spread}")
    check(not ring_bad, f"seed {seed}: replay rings differ from the "
          f"oracle: {ring_bad}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded training chunk on "
                         "four chips against its vmap oracle")
    ap.add_argument("--out", default=os.path.join(ROOT, "runs",
                                                  "chip_smoke"),
                    help="directory for the run's training output")
    args = ap.parse_args(argv)

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{d0.platform!r} ({d0.device_kind}, {len(devices)} "
              f"device(s)). Nothing was run.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips; "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"chip_smoke: device_kind={d0.device_kind!r} "
          f"devices={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache_dir}", flush=True)

    if args.chips == 4:
        used = devices[:4]
        with phase("sharded_4chip"):
            phase_sharded(used)
    else:
        used = devices[:1]
        os.makedirs(args.out, exist_ok=True)
        with phase("engine"):
            phase_engine()
        with phase("train"):
            trained = phase_train(args.out)
        with phase("serve"):
            phase_serve(trained)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(used)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
