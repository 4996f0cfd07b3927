"""Sharded trainer tests: double-buffered replay ring pair semantics
(masked add, read-ring invariant under donation and wrap-around),
device-folded key streams, global-gather sampling vs the single-ring
oracle, driver --devices validation and routing, and subprocess parity
at 2 forced host devices — the mesh shard_map path vs the vmap oracle
(metrics, final DDPGState, replica bit-identity, and ring contents
under the fixed device-keyed stream) — plus a generalist 2-device x
2-fleet driver smoke and cross-device-count checkpoint resumes in both
directions."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ddpg as D
from repro.core import policy as P
from repro.core.replay import (replay_add, replay_add_batch,
                               replay_add_masked, replay_fields,
                               replay_init, replay_pair_init,
                               replay_pair_step, replay_sample,
                               replay_sample_global)
from repro.core.train import round_keys, shard_round_keys, train_rounds_scan
from repro.launch.rl_train import TrainConfig, build_env, train
from repro.sim.env import EnvConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV2 = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"}

SMOKE_ARGS = ["--workload", "light", "--episodes", "4",
              "--batch-episodes", "2", "--periods", "6", "--max-rq", "16",
              "--max-jobs", "8", "--hidden", "8",
              "--updates-per-episode", "2", "--batch-size", "8",
              "--replay-capacity", "64", "--warmup-episodes", "2",
              "--eval-every", "100", "--eval-seeds", "2"]


def _batch(r_values, T=3, F=2, G=1):
    n = len(r_values)
    return dict(s=jnp.zeros((n, T, F)), mask=jnp.ones((n, T), bool),
                a=jnp.zeros((n, T - 1, G)),
                r=jnp.asarray(r_values, jnp.float32),
                s2=jnp.zeros((n, T, F)), mask2=jnp.ones((n, T), bool))


# ---------------------------------------------------------------------------
# masked ring write + double-buffered pair
# ---------------------------------------------------------------------------
def test_replay_add_masked_partial_and_empty():
    buf = replay_init(8, 3, 2, 1)
    # n = 0: nothing written, bookkeeping untouched
    out = replay_add_masked(buf, _batch([1.0, 2.0, 3.0]), jnp.int32(0))
    assert int(out["ptr"]) == 0 and int(out["size"]) == 0
    assert float(jnp.sum(jnp.abs(out["r"]))) == 0.0
    # n = 2 of 3 rows: only the first two land
    out = replay_add_masked(buf, _batch([1.0, 2.0, 3.0]), jnp.int32(2))
    assert int(out["ptr"]) == 2 and int(out["size"]) == 2
    assert np.asarray(out["r"][:3]).tolist() == [1.0, 2.0, 0.0]


def test_replay_add_masked_wraps_like_replay_add():
    """With n == rows (and a traced n) the masked add is the plain ring
    add, including wrap-around."""
    masked = jax.jit(replay_add_masked)
    buf_m = replay_init(8, 3, 2, 1)
    buf_p = replay_init(8, 3, 2, 1)
    for lo in range(0, 15, 5):
        vals = list(range(lo, lo + 5))
        buf_m = masked(buf_m, _batch(vals), jnp.int32(5))
        buf_p = replay_add_batch(buf_p, _batch(vals))
    for k in list(replay_fields(buf_p)) + ["ptr", "size"]:
        assert np.array_equal(np.asarray(buf_m[k]), np.asarray(buf_p[k])), k


def test_replay_pair_read_ring_matches_single_ring_under_donation():
    """Ring-content invariant: after every pair step, the read ring is
    bit-identical to a single donated ring fed the same per-round
    batches in order (wrap-around included), and the write ring lags
    exactly one round behind."""
    cap, rnd = 8, 5
    pair = replay_pair_init(replay_init(cap, 3, 2, 1), rnd)
    single = replay_init(cap, 3, 2, 1)
    step = jax.jit(replay_pair_step, donate_argnums=(0,))
    prev = jax.tree.map(np.asarray, single)
    for r in range(4):                      # 20 writes > cap: wraps twice
        vals = [float(r * rnd + i) for i in range(rnd)]
        pair = step(pair, _batch(vals))     # donated: rebind
        prev = jax.tree.map(np.asarray, single)
        single = replay_add_batch(single, _batch(vals))
        for k in list(replay_fields(single)) + ["ptr", "size"]:
            assert np.array_equal(np.asarray(pair["read"][k]),
                                  np.asarray(single[k])), (r, k)
    # write ring == the single ring one round ago (it gets this round's
    # batch replayed from `pending` at the next step)
    for k in list(replay_fields(single)) + ["ptr", "size"]:
        assert np.array_equal(np.asarray(pair["write"][k]), prev[k]), k
    assert int(pair["pending_n"]) == rnd


# ---------------------------------------------------------------------------
# device-folded key streams
# ---------------------------------------------------------------------------
def test_shard_round_keys_shape_distinct_and_resumable():
    keys = round_keys(0, 0, 6)
    dk = np.asarray(shard_round_keys(keys, 3))
    assert dk.shape == (3, 6, 2)
    # all (device, round) keys distinct, and distinct from the base keys
    rows = {tuple(k) for k in dk.reshape(-1, 2)}
    assert len(rows) == 18
    assert not rows & {tuple(k) for k in np.asarray(keys)}
    # resume continuity: folding commutes with slicing the round stream
    resumed = np.asarray(shard_round_keys(round_keys(0, 4, 2), 3))
    assert np.array_equal(dk[:, 4:], resumed)


# ---------------------------------------------------------------------------
# global-gather sampling vs a single-ring oracle
# ---------------------------------------------------------------------------
def test_global_sample_is_single_ring_oracle_sample():
    """``replay_sample_global``'s gathered minibatch must BE a sample of
    one big ring fed every device's per-round batches in device-major
    round order: local slot ``s`` of device ``d`` holds the oracle's
    slot ``(s//n * D + d) * n + s%n`` (n = per-round write size,
    cap % n == 0).  Runs under vmap's named-axis collective — the same
    ``all_gather`` the mesh path lowers, no forced devices needed."""
    Dn, cap, n, rounds, per_bs = 2, 12, 4, 5, 5
    pairs = [replay_pair_init(replay_init(cap, 3, 2, 1), n)
             for _ in range(Dn)]
    oracle = replay_init(cap * Dn, 3, 2, 1)
    step = jax.jit(replay_pair_step)
    for r in range(rounds):                 # 20 writes/device > cap: wraps
        batches = [_batch([float(100 * d + 10 * r + i) for i in range(n)])
                   for d in range(Dn)]
        pairs = [step(p, b) for p, b in zip(pairs, batches)]
        for b in batches:                   # device-major round order
            oracle = replay_add_batch(oracle, b)
    # the affine slot map holds row-for-row, wrap-around included
    o_r = np.asarray(oracle["r"])
    s = np.arange(cap)
    for d, p in enumerate(pairs):
        np.testing.assert_array_equal(np.asarray(p["read"]["r"]),
                                      o_r[(s // n * Dn + d) * n + s % n])
    # the gathered global batch == the SAME draws read out of the oracle
    # ring through the slot map, concatenated in device order
    stacked = jax.tree.map(lambda *x: jnp.stack(x),
                           *[p["read"] for p in pairs])
    keys = jax.random.split(jax.random.PRNGKey(3), Dn)
    got = jax.vmap(lambda b, k: replay_sample_global(b, k, per_bs, "dev"),
                   axis_name="dev")(stacked, keys)
    rows = []
    for d in range(Dn):                     # recover each device's draws
        idx = np.asarray(replay_sample(
            dict(size=jnp.int32(cap), r=jnp.arange(cap, dtype=jnp.float32)),
            keys[d], per_bs)["r"]).astype(int)
        rows.append(o_r[(idx // n * Dn + d) * n + idx % n])
    want = np.concatenate(rows)
    assert got["r"].shape == (Dn, Dn * per_bs)
    for d in range(Dn):                     # identical on every device
        np.testing.assert_array_equal(np.asarray(got["r"][d]), want)


# ---------------------------------------------------------------------------
# driver: --devices validation and single-device routing
# ---------------------------------------------------------------------------
def test_devices_exceeding_local_count_errors_clearly(tmp_path):
    """This pytest process has 1 CPU device: --devices 2 must fail fast
    with a message naming both numbers, not inside pmap."""
    assert jax.local_device_count() == 1
    cfg = TrainConfig(devices=2, outdir=str(tmp_path / "x"))
    with pytest.raises(ValueError, match=r"local_device_count\(\) = 1"):
        train(cfg, log_fn=lambda *a: None)
    with pytest.raises(ValueError, match="--devices must be >= 1"):
        train(TrainConfig(devices=0, outdir=str(tmp_path / "y")),
              log_fn=lambda *a: None)


def test_devices_1_routes_to_plain_fused_path(tmp_path):
    """--devices 1 must reproduce the existing fused-round metrics
    exactly — the single-device path is the parity oracle, not a
    1-device pmap."""
    cfg = TrainConfig(workload="light", episodes=4, batch_episodes=2,
                      periods=6, max_rq=16, max_jobs=8, hidden=8,
                      updates_per_episode=2, batch_size=8,
                      replay_capacity=64, warmup_episodes=2,
                      eval_every=100, eval_seeds=2, devices=1,
                      outdir=str(tmp_path / "run"))
    out = train(cfg, log_fn=lambda *a: None)
    driver_sla = [rec["sla"] for rec in out["history"]]

    # the same two rounds straight through the fused scan
    env = build_env(cfg)
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=cfg.hidden)
    dcfg = D.DDPGConfig(policy=pcfg)
    state = D.init_ddpg(jax.random.PRNGKey(cfg.seed), dcfg)
    buf = replay_init(cfg.replay_capacity, env.seq_len, env.feat_dim,
                      env.act_dim)
    keys = round_keys(cfg.seed + 1, 0, 2)
    *_, mets = train_rounds_scan(
        env, dcfg, state, buf, keys, jnp.float32(cfg.sigma0),
        jnp.array([False, True]), batch_episodes=2,
        num_updates=cfg.updates_per_episode * 2, batch_size=cfg.batch_size,
        sigma_min=cfg.sigma_min, sigma_decay=cfg.sigma_decay)
    expect = [round(float(s), 4) for s in np.asarray(mets["sla"])]
    assert driver_sla == expect


# ---------------------------------------------------------------------------
# 2-device subprocess tests (host devices forced through XLA_FLAGS)
# ---------------------------------------------------------------------------
_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax, jax.numpy as jnp, numpy as np
from repro.core import ddpg as D, policy as P
from repro.core.replay import replay_fields, replay_init, replay_pair_init
from repro.core.train import (make_device_mesh,
                              make_sharded_train_rounds, mesh_replicate,
                              round_keys, shard_round_keys,
                              sharded_rounds_reference, unreplicate)
from repro.sim.arrivals import ArrivalConfig
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.workloads import build_registry

ECFG = EnvConfig(t_s_us=500.0, periods=6, max_rq=16, max_jobs=8)
reg = build_registry("light")
arr = ArrivalConfig(max_jobs=ECFG.max_jobs, horizon_us=ECFG.horizon_us,
                    slack_us=2 * ECFG.t_s_us)
env = SchedulingEnv(reg, ECFG, arr)
pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim, hidden=8)
dcfg = D.DDPGConfig(policy=pcfg)
KW = dict(batch_episodes=2, num_updates=3, batch_size=8,
          sigma_min=0.05, sigma_decay=0.97)
DEV = jax.local_devices()
assert len(DEV) == 2
keys = round_keys(7, 0, 4)
dkeys = shard_round_keys(keys, 2)
flags = jnp.array([False, True, True, True])
round_size = (KW["batch_episodes"] // 2) * ECFG.periods

def fresh():
    state = D.init_ddpg(jax.random.PRNGKey(1), dcfg)
    pair = replay_pair_init(
        replay_init(16, env.seq_len, env.feat_dim, env.act_dim), round_size)
    return state, pair                      # cap 16 < 4*6 writes: wraps

mesh = make_device_mesh(DEV)
state, pair = fresh()
fn = make_sharded_train_rounds(env, dcfg, mesh=mesh, **KW)
s1, p1, sg1, m1 = fn(mesh_replicate(state, mesh), mesh_replicate(pair, mesh),
                     dkeys, mesh_replicate(jnp.float32(0.4), mesh), flags)

state, pair = fresh()
stack2 = lambda t: jax.tree.map(lambda x: jnp.stack([x, x]), t)
ref = sharded_rounds_reference(env, dcfg, num_devices=2, **KW)
s2, p2, sg2, m2 = ref(stack2(state), stack2(pair), dkeys,
                      jnp.stack([jnp.float32(0.4)] * 2), flags)

for k in m1:
    assert np.allclose(np.asarray(m1[k]), np.asarray(m2[k]), atol=1e-4), k
deltas = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                      unreplicate(s1).actor, unreplicate(s2).actor)
assert max(jax.tree.leaves(deltas)) < 1e-4
# gathered global batches make every replica consume identical inputs:
# the shard_map learner must stay BIT-identical across devices
for leaf in jax.tree.leaves(jax.tree.map(
        lambda x: float(jnp.max(jnp.abs(x[0] - x[1]))), s1.actor)):
    assert leaf == 0.0
# ring contents: the fixed device-keyed stream makes shard_map and the
# vmap oracle fill identical per-device rings (wrap included)
for ring in ("read", "write"):
    for k in replay_fields(p1[ring]):
        a, b = np.asarray(p1[ring][k]), np.asarray(p2[ring][k])
        if a.dtype == bool:
            assert np.array_equal(a, b), (ring, k)
        else:
            assert np.allclose(a, b, atol=1e-6), (ring, k)
    for k in ("ptr", "size"):
        assert np.array_equal(np.asarray(p1[ring][k]),
                              np.asarray(p2[ring][k])), (ring, k)
assert int(p1["read"]["size"][0]) == 16     # wrapped: capacity reached
print("PARITY_OK")
"""

_VALIDATION_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
assert jax.local_device_count() == 2
from repro.launch.rl_train import TrainConfig, train
checks = [
    (dict(devices=2, batch_episodes=3), "batch-episodes 3"),
    (dict(devices=2, batch_episodes=2, batch_size=9), "batch-size 9"),
    (dict(devices=2, batch_episodes=2, replay_capacity=121),
     "replay-capacity 121"),
    (dict(devices=2, batch_episodes=2, episodes=5), "multiple of"),
    (dict(devices=2, batch_episodes=2, churn="fail"),
     "single-device feature"),
    (dict(devices=1, churn="meteor"), "--churn must be one of"),
]
for kw, frag in checks:
    try:
        train(TrainConfig(outdir="/tmp/never", **kw), log_fn=lambda *a: None)
    except ValueError as e:
        assert frag in str(e), (frag, str(e))
    else:
        raise AssertionError(f"no ValueError for {kw}")
print("VALIDATION_OK")
"""


@pytest.mark.slow
def test_shard_map_matches_vmap_oracle_subproc():
    r = subprocess.run([sys.executable, "-c", _PARITY_SCRIPT], env=ENV2,
                       cwd=REPO, capture_output=True, text=True, timeout=540)
    assert "PARITY_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


@pytest.mark.slow
def test_devices_divisibility_validation_subproc():
    r = subprocess.run([sys.executable, "-c", _VALIDATION_SCRIPT], env=ENV2,
                       cwd=REPO, capture_output=True, text=True, timeout=300)
    assert "VALIDATION_OK" in r.stdout, r.stdout[-1500:] + r.stderr[-1500:]


@pytest.mark.slow
def test_generalist_two_device_two_fleet_smoke(tmp_path):
    """The full driver: 2 forced devices x 2 fleets, 2 sharded rounds
    with the shared per-round fleet draw, eval at the end."""
    out = str(tmp_path / "gen")
    cmd = [sys.executable, "-m", "repro.launch.rl_train", *SMOKE_ARGS,
           "--fleet", "paper6,8simba", "--devices", "2", "--outdir", out]
    r = subprocess.run(cmd, env=ENV2, cwd=REPO, capture_output=True,
                       text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    recs = [json.loads(l) for l in open(os.path.join(out, "log.jsonl"))]
    eps = [rec for rec in recs if "sla" in rec]
    assert len(eps) == 2
    assert all(rec["fleet"] in ("paper6", "8simba") for rec in eps)
    assert any("eval_sla" in rec for rec in recs)


@pytest.mark.slow
def test_checkpoint_resume_across_device_counts(tmp_path):
    """Checkpoints are single-device arrays, so device count is a
    per-launch choice: train sharded at --devices 2 and resume the same
    outdir at --devices 1, AND the reverse — a single-device run picked
    up by a 2-device mesh."""
    env1 = {**ENV2, "XLA_FLAGS": "--xla_force_host_platform_device_count=1"}
    out = str(tmp_path / "ck")
    base = [sys.executable, "-m", "repro.launch.rl_train", *SMOKE_ARGS,
            "--ckpt-every", "2", "--outdir", out]
    r = subprocess.run(base + ["--devices", "2"], env=ENV2, cwd=REPO,
                       capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    r2 = subprocess.run(base + ["--devices", "1", "--episodes", "8"],
                        env=env1, cwd=REPO, capture_output=True, text=True,
                        timeout=540)
    assert r2.returncode == 0, r2.stdout[-2000:] + r2.stderr[-2000:]
    assert "[resume] restored checkpoint" in r2.stdout

    out_b = str(tmp_path / "ck_up")
    base_b = [sys.executable, "-m", "repro.launch.rl_train", *SMOKE_ARGS,
              "--ckpt-every", "2", "--outdir", out_b]
    r3 = subprocess.run(base_b + ["--devices", "1"], env=env1, cwd=REPO,
                        capture_output=True, text=True, timeout=540)
    assert r3.returncode == 0, r3.stdout[-2000:] + r3.stderr[-2000:]
    r4 = subprocess.run(base_b + ["--devices", "2", "--episodes", "8"],
                        env=ENV2, cwd=REPO, capture_output=True, text=True,
                        timeout=540)
    assert r4.returncode == 0, r4.stdout[-2000:] + r4.stderr[-2000:]
    assert "[resume] restored checkpoint" in r4.stdout
