"""Environment semantics + baseline scheduler tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as BL
from repro.core.rollout import make_baseline_period, run_episode
from repro.sim.arrivals import ArrivalConfig, generate_trace
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.workloads import build_registry

ECFG = EnvConfig(t_s_us=500.0, periods=12, max_rq=32, max_jobs=12)


@pytest.fixture(scope="module")
def env():
    reg = build_registry("light")
    arr = ArrivalConfig(max_jobs=ECFG.max_jobs, horizon_us=ECFG.horizon_us,
                        slack_us=2 * ECFG.t_s_us)
    return SchedulingEnv(reg, ECFG, arr)


def test_trace_generation_properties(env):
    rng = np.random.default_rng(0)
    tr = generate_trace(np.asarray(env.min_lat), env.arrivals, rng)
    a = tr["arrival"][tr["arrival"] < 1e29]
    assert a[0] == 0.0 and (np.diff(a) >= 0).all()
    assert (tr["q"][tr["arrival"] < 1e29] > 0).all()
    assert (tr["deadline"] >= tr["arrival"]).all()


def test_build_slots_deadline_order_and_chains(env):
    rng = np.random.default_rng(1)
    trace, state = env.new_episode(rng)
    state = {**state, "t": jnp.asarray(2000.0)}
    slots = env.build_slots(state, trace, cutoff=2000.0)
    valid = np.asarray(slots["valid"])
    job = np.asarray(slots["job"])
    dl = np.asarray(slots["deadline"])
    layer = np.asarray(slots["layer"])
    dep = np.asarray(slots["dep"])
    vi = np.flatnonzero(valid)
    # non-decreasing deadline over distinct jobs in slot order
    seen, order_dl = set(), []
    for i in vi:
        if job[i] not in seen:
            seen.add(job[i])
            order_dl.append(dl[i])
    assert all(order_dl[i] <= order_dl[i + 1] + 1e-3
               for i in range(len(order_dl) - 1))
    # a job's layers are contiguous ascending; dep chain is i-1
    for i in vi[1:]:
        if job[i] == job[i - 1]:
            assert layer[i] == layer[i - 1] + 1
            assert dep[i] == i - 1


def test_primer_sa_busy_locates_the_busy_features(env):
    trace, state = env.new_episode(np.random.default_rng(2))
    busy_us = np.array([0.0, 250.0, 1000.0, 2500.0, 9000.0, 40.0])
    state = {**state, "t": jnp.asarray(3000.0),
             "sa_free": jnp.asarray(3000.0 + busy_us, jnp.float32)}
    slots = env.build_slots(state, trace, cutoff=3000.0)
    feats = np.asarray(env.encode(slots, state)[0])
    idx, us_per_unit = env.primer_sa_busy
    np.testing.assert_allclose(
        feats[idx], np.minimum(busy_us, us_per_unit) / us_per_unit,
        rtol=1e-6)
    rest = feats[0].copy()
    rest[idx[1]] = 0.0
    assert not rest.any()


def test_reward_hand_computed(env):
    """One job, one layer, hits the deadline -> alpha + gamma*slack."""
    cfg = env.cfg
    R = cfg.max_rq
    slots = dict(
        valid=jnp.zeros((R,), bool).at[0].set(True),
        deadline=jnp.full((R,), 1000.0),
        q=jnp.full((R,), 900.0),
    )
    state = {"t": jnp.asarray(0.0)}
    fin = jnp.full((R,), 1e30).at[0].set(400.0)     # finishes inside T_s
    r = env.reward(state, slots, fin)
    slack = (1000.0 - 400.0) / 900.0
    want = cfg.alpha + cfg.gamma_r * slack
    assert float(r) == pytest.approx(want, rel=1e-4)


def test_episode_conservation(env):
    """Every arrived job ends counted (hit, missed or done)."""
    period = make_baseline_period(env, BL.fcfs_h)
    m, _ = run_episode(env, period, np.random.default_rng(3))
    assert m["counted"] <= m["arrived"]
    assert 0.0 <= m["sla_rate"] <= 1.0
    assert m["energy_uj"] > 0


@pytest.mark.parametrize("name", ["fcfs", "prema", "herald"])
def test_baselines_emit_valid_actions(env, name):
    rng = np.random.default_rng(0)
    trace, state = env.new_episode(rng)
    slots = env.build_slots(state, trace, cutoff=0.0)
    a, prio, sa = BL.BASELINES[name](slots, state, env)
    assert a.shape == (env.cfg.max_rq, env.act_dim)
    assert sa.dtype == jnp.int32
    assert int(sa.min()) >= 0 and int(sa.max()) < env.num_sas
    assert float(jnp.max(jnp.abs(prio))) <= 1.0


def test_greedy_sa_picks_min_finish(env):
    """Single ready SJ: the heuristic must pick the fastest idle SA."""
    rng = np.random.default_rng(0)
    trace, state = env.new_episode(rng)
    slots = env.build_slots(state, trace, cutoff=0.0)
    a, prio, sa = BL.fcfs_h(slots, state, env)
    i = int(np.flatnonzero(np.asarray(slots["valid"]))[0])
    cost = np.asarray(slots["cost_all"])[i]
    assert int(sa[i]) == int(np.argmin(np.where(cost > 0, cost, 1e30)))


def test_magma_tiny_improves_over_random(env):
    rng = np.random.default_rng(0)
    trace, state = env.new_episode(rng)
    state = {**state, "t": jnp.asarray(1000.0)}
    state = env.mark_drops(state, trace, 1000.0)
    slots = env.build_slots(state, trace, cutoff=1000.0)
    mcfg = BL.MagmaConfig(population=16, generations=4)
    key = jax.random.PRNGKey(0)
    prio0 = jax.random.uniform(key, (16, env.cfg.max_rq), minval=-1,
                               maxval=1)
    sa0 = jax.random.randint(key, (16, env.cfg.max_rq), 0, env.num_sas)
    fit0 = BL._magma_fitness(env, state, slots, prio0, sa0)
    a, prio, sa = BL.magma(slots, state, env, mcfg, key=key)
    fit_final = BL._magma_fitness(env, state, slots, prio[None], sa[None])
    assert float(fit_final[0]) >= float(jnp.max(fit0)) - 1e-5
