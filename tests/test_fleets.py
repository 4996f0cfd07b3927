"""Fleet presets as a first-class axis: registry re-characterization,
engine correctness at non-default ``num_sas``, env/policy dims following
the platform, and the sweep/training surfaces on non-default fleets."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as BL
from repro.core.rollout import evaluate_batch_baseline
from repro.costmodel import DEFAULT_MAS, FLEETS, get_fleet
from repro.costmodel.fleets import fleet_names
from repro.sim.engine import simulate_jax, simulate_np
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.workloads import build_registry
from repro.workloads.cnn_zoo import WORKLOADS


# ---------------------------------------------------------------------------
# preset registry
# ---------------------------------------------------------------------------
def test_presets_cover_required_mixes():
    names = fleet_names()
    for required in ("paper6", "4simba_4eyeriss", "8simba", "8eyeriss",
                     "2simba_6eyeriss", "big_little"):
        assert required in names
    # paper6 IS the committed-benchmark platform
    assert get_fleet("paper6").sas == DEFAULT_MAS.sas
    assert get_fleet("paper6").dram_gbps == DEFAULT_MAS.dram_gbps
    # MASConfig passthrough + informative failure on unknown names
    assert get_fleet(DEFAULT_MAS) is DEFAULT_MAS
    with pytest.raises(ValueError, match="8simba"):
        get_fleet("not_a_fleet")


def test_dataflow_mixes():
    flows = lambda n: {sa.dataflow for sa in get_fleet(n).sas}
    assert flows("8simba") == {"ws"}          # all weight-stationary
    assert flows("8eyeriss") == {"rs"}        # all row-stationary
    for n in ("paper6", "4simba_4eyeriss", "2simba_6eyeriss", "big_little"):
        assert flows(n) == {"rs", "ws"}
    assert all(f.name == n for n, f in FLEETS.items())


# ---------------------------------------------------------------------------
# registration phase per fleet
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("workload", sorted(WORKLOADS))
@pytest.mark.parametrize("fleet", fleet_names())
def test_registry_tables_follow_fleet_shape(fleet, workload):
    fl = get_fleet(fleet)
    d = build_registry(workload, mas=fleet).dense()
    n = len(WORKLOADS[workload])
    assert d["num_sas"] == fl.num_sas and d["num_models"] == n
    assert d["lat"].shape == (n, d["lmax"], fl.num_sas)
    assert d["bw"].shape == d["lat"].shape == d["en"].shape
    for i in range(n):  # real layers characterize positive on every SA
        L = d["n_layers"][i]
        assert (d["lat"][i, :L] > 0).all() and (d["en"][i, :L] > 0).all()
    assert (d["min_lat"] > 0).all()


def test_characterization_parity_across_fleets():
    """A column depends only on (SAClass, dram_gbps), not on the fleet
    around it — re-characterization must be per-SA deterministic."""
    d6 = build_registry("light", mas="paper6").dense()
    d8 = build_registry("light", mas="2simba_6eyeriss").dense()
    col6 = [sa.name for sa in get_fleet("paper6").sas]
    col8 = [sa.name for sa in get_fleet("2simba_6eyeriss").sas]
    for cls in ("simba_large", "eyeriss_small"):
        np.testing.assert_array_equal(d6["lat"][..., col6.index(cls)],
                                      d8["lat"][..., col8.index(cls)])
        np.testing.assert_array_equal(d6["en"][..., col6.index(cls)],
                                      d8["en"][..., col8.index(cls)])
    # and identical SAs inside one fleet get identical columns
    dup = build_registry("light", mas="8simba").dense()
    names = [sa.name for sa in get_fleet("8simba").sas]
    first, last = names.index("simba_large"), 3  # SAs 0-3 are simba_large
    np.testing.assert_array_equal(dup["lat"][..., first],
                                  dup["lat"][..., last])


def test_big_little_scaling_orders_latency():
    """The scaled-up cores must dominate their little siblings on big
    layers (that's the point of the big/LITTLE preset)."""
    d = build_registry("heavy", mas="big_little").dense()
    names = [sa.name for sa in get_fleet("big_little").sas]
    big, little = names.index("simba_big"), names.index("simba_little")
    # summed over each model's real layers, big is strictly faster
    for i in range(d["num_models"]):
        L = d["n_layers"][i]
        assert d["lat"][i, :L, big].sum() < d["lat"][i, :L, little].sum()


# ---------------------------------------------------------------------------
# engine at non-default num_sas
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("M", [4, 8])
def test_engine_oracle_parity_at_nondefault_m(M):
    rng = np.random.default_rng(M)
    n = 32
    dep = np.arange(n) - 1
    dep[::5] = -1
    valid = rng.random(n) < 0.9
    assign = rng.integers(0, M, n)
    prio = rng.uniform(size=n)
    cost = rng.uniform(50, 500, n)
    bw = rng.uniform(1, 8, n)
    ready = np.where(rng.random(n) < 0.3, rng.uniform(0, 200, n), 0.0)
    sa_free = rng.uniform(0, 100, M)
    s, f = simulate_np(valid, assign, prio, cost, bw, dep, ready,
                       sa_free, 16.0)
    sj, fj = simulate_jax(
        jnp.asarray(valid), jnp.asarray(assign, jnp.int32),
        jnp.asarray(prio, jnp.float32), jnp.asarray(cost, jnp.float32),
        jnp.asarray(bw, jnp.float32), jnp.asarray(dep, jnp.int32),
        jnp.asarray(ready, jnp.float32), jnp.asarray(sa_free, jnp.float32),
        jnp.float32(16.0), num_sas=M)
    ran = np.asarray(f) < 1e29
    np.testing.assert_allclose(np.asarray(sj)[ran], s[ran],
                               rtol=1e-3, atol=1e-2)
    np.testing.assert_allclose(np.asarray(fj)[ran], f[ran],
                               rtol=1e-3, atol=1e-2)


# ---------------------------------------------------------------------------
# env + policy dims follow the fleet; whole episodes run
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fleet,m", [("8simba", 8), ("2simba_2eyeriss", 4)])
def test_env_episode_on_fleet(fleet, m):
    """Includes the all-one-dataflow case: every layer kind must still
    characterize, schedule, and commit on a ws-only platform."""
    reg = build_registry("light", mas=fleet)
    env = SchedulingEnv(reg, EnvConfig(periods=6, max_rq=16, max_jobs=8))
    assert env.num_sas == m
    assert env.feat_dim == 4 + 2 * m and env.act_dim == 1 + m
    assert env.cfg.bandwidth_gbps == get_fleet(fleet).dram_gbps
    res = evaluate_batch_baseline(env, BL.BASELINES["fcfs"],
                                  seeds=range(3000, 3002))
    assert 0.0 <= res["sla_rate"] <= 1.0
    assert res["counted"] > 0 and np.isfinite(res["energy_uj"])


def test_explicit_bandwidth_still_overrides_fleet():
    reg = build_registry("light", mas="datacenter")
    assert SchedulingEnv(reg, EnvConfig()).cfg.bandwidth_gbps == 819.0
    env = SchedulingEnv(reg, EnvConfig(bandwidth_gbps=32.0))
    assert env.cfg.bandwidth_gbps == 32.0


# ---------------------------------------------------------------------------
# sweep + training surfaces
# ---------------------------------------------------------------------------
def test_sweep_distinct_fleets_distinct_cells(tmp_path):
    from benchmarks import sweep
    res = sweep.run(smoke=True, fleets=("8simba", "8eyeriss"),
                    scenarios=("default",), policies=("fcfs",),
                    out=str(tmp_path / "sweep.json"))
    assert res["meta"]["fleets"] == ["8simba", "8eyeriss"]
    a = res["cells"]["8simba/default/fcfs/bw16"]
    b = res["cells"]["8eyeriss/default/fcfs/bw16"]
    # different hardware => different schedule outcomes: the SLA/energy
    # cell contents must not coincide across fleets
    assert (a["sla_rate"], a["energy_uj"]) != (b["sla_rate"], b["energy_uj"])


@pytest.mark.slow
def test_resume_rejects_cross_fleet_checkpoint(tmp_path):
    """Auto-resume must not silently continue another fleet's weights:
    same-width fleets are caught by the meta check, different-width
    fleets by a shape error with a fleet-aware message."""
    from repro.launch.rl_train import TrainConfig, train
    kw = dict(workload="light", episodes=2, batch_episodes=2, periods=4,
              max_rq=12, max_jobs=6, hidden=8, updates_per_episode=1,
              batch_size=4, replay_capacity=32, warmup_episodes=99,
              eval_every=100, eval_seeds=2, ckpt_every=1,
              outdir=str(tmp_path))
    train(TrainConfig(fleet="paper6", **kw), log_fn=lambda *_: None)
    with pytest.raises(ValueError, match="big_little"):   # same M=6
        train(TrainConfig(fleet="big_little", **kw), log_fn=lambda *_: None)
    with pytest.raises(ValueError, match="policy shapes"):  # M=8
        train(TrainConfig(fleet="8simba", **kw), log_fn=lambda *_: None)


@pytest.mark.slow
def test_rl_train_fused_rounds_on_nondefault_fleet(tmp_path):
    """--fleet trains end-to-end through the single-dispatch fused
    rounds on an 8-SA platform (policy dims re-derived from the fleet)."""
    from repro.launch.rl_train import TrainConfig, train
    cfg = TrainConfig(workload="light", fleet="2simba_6eyeriss",
                      episodes=4, batch_episodes=2, periods=5, max_rq=12,
                      max_jobs=6, hidden=8, updates_per_episode=2,
                      batch_size=4, replay_capacity=64, warmup_episodes=1,
                      eval_every=100, eval_seeds=2, outdir=str(tmp_path))
    out = train(cfg, log_fn=lambda *_: None)
    assert out["env"].num_sas == 8
    assert out["pcfg"].feat_dim == 4 + 2 * 8
    h = out["history"]
    assert h[-1]["episode"] == 3
    assert all(np.isfinite(r["sla"]) for r in h)
    assert any("critic_loss" in r for r in h)   # updates ran post-warmup
