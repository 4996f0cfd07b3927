"""Fleet-churn tests: traced in-episode event schedules (repro.sim.churn).

Pins the churn contract end-to-end:

- ``compile_schedule`` event semantics (fail/join windows, last-event-
  wins revival, degradation multipliers) and the no-op identity;
- the ACCEPTANCE criterion: an all-no-op churn schedule threaded
  through the churn-enabled episode program is **bit-identical** to the
  static-fleet path — specialist AND generalist;
- a failed SA is never selected (direct act_fn unit + full episode);
- a join event flips validity (absent before, schedulable after);
- throttle monotonicity: SLA under memory-path degradation never beats
  the no-churn run on the same traces/seeds;
- fused-training smoke with a churn schedule drawn per round
  (specialist and generalist round bodies).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines as BL
from repro.core import ddpg as D
from repro.core import policy as P
from repro.core.generalist import (GeneralistSpec, build_padded_envs,
                                   evaluate_generalist_batch,
                                   generalist_act_fn,
                                   generalist_replay_init,
                                   make_generalist_rounds)
from repro.core.replay import replay_init
from repro.core.rollout import (_policy_act_fn, evaluate_batch,
                                evaluate_batch_baseline)
from repro.core.train import make_train_rounds, round_keys
from repro.sim.arrivals import ArrivalConfig
from repro.sim.churn import (CHURN_SCENARIOS, EV_FAIL, EV_JOIN, EV_NONE,
                             EV_SLOWDOWN, EV_THROTTLE, ChurnConfig,
                             churn_events, churn_events_jax, churn_preset,
                             churn_schedule, churn_schedules,
                             churn_schedules_jax, compile_schedule,
                             failure_schedule, join_schedule,
                             no_op_events, no_op_schedule,
                             slowdown_schedule, throttle_schedule)
from repro.sim.env import EnvConfig, SchedulingEnv
from repro.workloads import build_registry

ECFG = EnvConfig(t_s_us=500.0, periods=6, max_rq=16, max_jobs=8)


@pytest.fixture(scope="module")
def env():
    reg = build_registry("light")
    arr = ArrivalConfig(max_jobs=ECFG.max_jobs, horizon_us=ECFG.horizon_us,
                        slack_us=2 * ECFG.t_s_us)
    return SchedulingEnv(reg, ECFG, arr)


@pytest.fixture(scope="module")
def loaded_env():
    """Calibrated-regime env: enough contention that SLA discriminates
    (the tiny smoke env hits 1.0 everywhere)."""
    reg = build_registry("light")
    ecfg = EnvConfig(t_s_us=500.0, periods=16, max_rq=32, max_jobs=16)
    arr = ArrivalConfig(max_jobs=16, load=1.3, qos_factor=2.5,
                        horizon_us=ecfg.horizon_us,
                        slack_us=2 * ecfg.t_s_us)
    return SchedulingEnv(reg, ecfg, arr)


def _events(rows, E=4):
    """Build fixed-shape event arrays from (period, sa, code, mag) rows."""
    ev = no_op_events(E)
    for i, (p, s, c, g) in enumerate(rows):
        ev["period"][i], ev["sa"][i] = p, s
        ev["code"][i], ev["mag"][i] = c, g
    return {k: jnp.asarray(v) for k, v in ev.items()}


# ---------------------------------------------------------------------------
# compile_schedule semantics
# ---------------------------------------------------------------------------
def test_compile_noop_is_identity_schedule():
    sched = compile_schedule(_events([]), periods=5, num_sas=3)
    ref = no_op_schedule(5, 3)
    for k in ("valid", "lat_mult", "bw_mult"):
        assert np.array_equal(np.asarray(sched[k]), np.asarray(ref[k])), k


def test_compile_fail_and_join_windows():
    sched = compile_schedule(
        _events([(2, 1, EV_FAIL, 1.0), (3, 2, EV_JOIN, 1.0)]),
        periods=6, num_sas=4)
    v = np.asarray(sched["valid"])
    assert v[:2, 1].all() and not v[2:, 1].any()     # fail from period 2
    assert not v[:3, 2].any() and v[3:, 2].all()     # join absent until 3
    assert v[:, 0].all() and v[:, 3].all()           # untouched SAs
    assert np.asarray(sched["lat_mult"]).min() == 1.0
    assert np.asarray(sched["bw_mult"]).min() == 1.0


def test_compile_join_revives_earlier_fail():
    """Later event rows win per period: a JOIN of the same SA after a
    FAIL revives it from the join period onward."""
    sched = compile_schedule(
        _events([(1, 0, EV_FAIL, 1.0), (4, 0, EV_JOIN, 1.0)]),
        periods=6, num_sas=2)
    v = np.asarray(sched["valid"])[:, 0]
    # the JOIN also marks its target absent before its period (t=0)
    assert not v[:4].any() and v[4:].all()


def test_compile_degradation_multipliers():
    sched = compile_schedule(
        _events([(2, 0, EV_SLOWDOWN, 3.0), (1, 1, EV_THROTTLE, 8.0)]),
        periods=4, num_sas=2)
    lat = np.asarray(sched["lat_mult"])
    bwm = np.asarray(sched["bw_mult"])
    assert (lat[:2, 0] == 1.0).all() and (lat[2:, 0] == 3.0).all()
    assert (bwm[:1, 1] == 1.0).all() and (bwm[1:, 1] == 8.0).all()
    assert np.asarray(sched["valid"]).all()          # degraded, not failed


def test_churn_events_deterministic_and_in_window():
    cfg = churn_preset("mixed", n_events=3)
    ev1 = churn_events(cfg, periods=20, num_sas=6,
                       rng=np.random.default_rng(5))
    ev2 = churn_events(cfg, periods=20, num_sas=6,
                       rng=np.random.default_rng(5))
    for k in ev1:
        assert np.array_equal(ev1[k], ev2[k]), k
    live = ev1["code"] != EV_NONE
    assert live.sum() == 3
    assert (ev1["period"][live] >= 5).all()          # window (0.25, 0.75)
    assert (ev1["period"][live] < 15).all()
    assert (ev1["sa"] < 6).all()


def test_churn_events_jax_plan_and_window():
    cfg = churn_preset("fail", n_events=2)
    ev = jax.jit(lambda k: churn_events_jax(cfg, 20, 6, k))(
        jax.random.PRNGKey(0))
    code = np.asarray(ev["code"])
    assert (code[:2] == EV_FAIL).all() and (code[2:] == EV_NONE).all()
    p = np.asarray(ev["period"])
    assert (p >= 5).all() and (p < 15).all()
    sa = np.asarray(ev["sa"])[:2]
    assert len(set(sa.tolist())) == 2                # distinct targets


def test_churn_events_jax_respects_sa_mask():
    cfg = churn_preset("fail", n_events=2)
    mask = jnp.asarray([True, True, True, False, False, False])
    for s in range(8):
        ev = churn_events_jax(cfg, 20, 6, jax.random.PRNGKey(s), mask)
        assert (np.asarray(ev["sa"])[:2] < 3).all()


def test_churn_preset_validation():
    with pytest.raises(ValueError, match="unknown churn scenario"):
        churn_preset("meteor")
    assert churn_preset("none").n_events == 0
    assert "none" in CHURN_SCENARIOS


def test_churn_schedules_batched_deterministic():
    cfg = churn_preset("throttle", magnitude=6.0)
    s1 = churn_schedules(cfg, 12, 4, seeds=[3, 4])
    s2 = churn_schedules(cfg, 12, 4, seeds=[3, 4])
    assert s1["valid"].shape == (2, 12, 4)
    for k in s1:
        assert np.array_equal(np.asarray(s1[k]), np.asarray(s2[k])), k
    assert np.asarray(s1["bw_mult"]).max() == 6.0


def test_churn_schedule_padded_width_contract():
    """Events drawn over the real SAs, compiled at a wider table: the
    padding columns stay valid with unit multipliers."""
    cfg = ChurnConfig(scenario="fail", n_events=2)
    sched = churn_schedule(cfg, 10, 4, np.random.default_rng(0), width=7)
    v = np.asarray(sched["valid"])
    assert v.shape == (10, 7)
    assert v[:, 4:].all()
    assert not v.all()                               # some real SA failed
    assert np.asarray(sched["lat_mult"])[:, 4:].min() == 1.0


# ---------------------------------------------------------------------------
# ACCEPTANCE: zero-churn bit-parity with the static path
# ---------------------------------------------------------------------------
def _assert_tree_bitequal(t1, t2):
    leaves1, leaves2 = jax.tree.leaves(t1), jax.tree.leaves(t2)
    assert len(leaves1) == len(leaves2)
    for l1, l2 in zip(leaves1, leaves2):
        a1, a2 = np.asarray(l1), np.asarray(l2)
        assert a1.dtype == a2.dtype and a1.shape == a2.shape
        assert a1.tobytes() == a2.tobytes()


def test_zero_churn_bit_parity_specialist(env):
    """The churn-enabled episode program with an all-no-op schedule is
    bit-identical to the static-fleet program — every churn application
    site is an IEEE identity (x * 1.0 / where(True, x, _))."""
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    params = P.init_actor(jax.random.PRNGKey(0), pcfg)
    trace, state = env.new_episode(np.random.default_rng(11))
    act = _policy_act_fn(params, pcfg)
    static = jax.jit(lambda s, t: env.episode(s, t, act))(state, trace)
    churned = jax.jit(
        lambda s, t, c: env.episode(s, t, act, churn=c))(
        state, trace, no_op_schedule(ECFG.periods, env.num_sas))
    _assert_tree_bitequal(static, churned)


def test_zero_churn_bit_parity_generalist(env):
    """Same identity through the descriptor-conditioned path: the no-op
    rows must reproduce the static conditioning (masks AND descriptors)
    bit-for-bit on a padded env."""
    reg = build_registry("light")
    arr = ArrivalConfig(max_jobs=ECFG.max_jobs, horizon_us=ECFG.horizon_us,
                        slack_us=2 * ECFG.t_s_us)
    genv = build_padded_envs("light", ("paper6",), ECFG, arr, m_max=8)[0]
    spec = GeneralistSpec(m_max=8)
    pcfg = spec.pcfg(hidden=8)
    params = P.init_actor(jax.random.PRNGKey(0), pcfg)
    trace, state = genv.new_episode(np.random.default_rng(12))
    act = generalist_act_fn(params, pcfg, genv.descriptors, genv.sa_mask)
    static = jax.jit(lambda s, t: genv.episode(s, t, act))(state, trace)
    churned = jax.jit(
        lambda s, t, c: genv.episode(s, t, act, churn=c))(
        state, trace, no_op_schedule(ECFG.periods, genv.num_sas))
    _assert_tree_bitequal(static, churned)


def test_zero_churn_preset_matches_plain_eval(env):
    """churn_preset("none") through the evaluators reproduces the plain
    eval numbers exactly (the batched twin of the bit-parity tests)."""
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    params = P.init_actor(jax.random.PRNGKey(1), pcfg)
    seeds = [21, 22]
    plain = evaluate_batch(env, pcfg, params, seeds)
    nochurn = evaluate_batch(env, pcfg, params, seeds,
                             churn=churn_preset("none"))
    assert plain == nochurn


# ---------------------------------------------------------------------------
# event semantics end-to-end
# ---------------------------------------------------------------------------
def test_failed_sa_never_selected_act_fn(env):
    """Direct act_fn unit: the SA argmax never lands on an invalid SA
    even when its logit would win unmasked."""
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    params = P.init_actor(jax.random.PRNGKey(0), pcfg)
    act = _policy_act_fn(params, pcfg)
    trace, state = env.new_episode(np.random.default_rng(0))
    slots = env.build_slots(state, trace, cutoff=state["t"])
    feats, mask = env.encode(slots, state)
    noise = jnp.zeros((env.cfg.max_rq, env.act_dim))
    valid = jnp.asarray([False] * (env.num_sas - 1) + [True])
    _, _, sa = act(feats, mask, slots,
                   {**state, "sa_valid": valid}, None, noise)
    assert (np.asarray(sa) == env.num_sas - 1).all()


def test_failed_sa_gets_no_work_end_to_end(loaded_env):
    """An SA failed from period 0 never accumulates busy time over a
    loaded episode; the same episode without churn uses it."""
    env = loaded_env
    dead = 2
    valid = np.ones((env.cfg.periods, env.num_sas), bool)
    valid[:, dead] = False
    sched = dict(valid=jnp.asarray(valid),
                 lat_mult=jnp.ones_like(jnp.asarray(valid), jnp.float32),
                 bw_mult=jnp.ones_like(jnp.asarray(valid), jnp.float32))
    trace, state = env.new_episode(np.random.default_rng(3))

    def act_fn(feats, mask, slots, st, key, aux):
        return BL.BASELINES["fcfs"](slots, st, env, key)

    run = jax.jit(lambda s, t, c: env.episode(s, t, act_fn, churn=c)[0])
    final_churn = run(state, trace, sched)
    assert float(final_churn["sa_free"][dead]) == 0.0
    final_plain = jax.jit(
        lambda s, t: env.episode(s, t, act_fn)[0])(state, trace)
    assert float(final_plain["sa_free"][dead]) > 0.0


def test_join_event_validity_flip_end_to_end(loaded_env):
    """A join target is absent until its event period, then picks up
    work: busy time stays zero under never-join, grows once joined."""
    env = loaded_env
    j, T = 1, env.cfg.periods
    never = compile_schedule(
        _events([(T + 1, j, EV_JOIN, 1.0)]), T, env.num_sas)
    mid = compile_schedule(
        _events([(T // 2, j, EV_JOIN, 1.0)]), T, env.num_sas)
    assert not np.asarray(never["valid"])[:, j].any()
    v = np.asarray(mid["valid"])[:, j]
    assert not v[:T // 2].any() and v[T // 2:].all()
    trace, state = env.new_episode(np.random.default_rng(4))

    def act_fn(feats, mask, slots, st, key, aux):
        return BL.BASELINES["fcfs"](slots, st, env, key)

    run = jax.jit(lambda s, t, c: env.episode(s, t, act_fn, churn=c)[0])
    assert float(run(state, trace, never)["sa_free"][j]) == 0.0
    assert float(run(state, trace, mid)["sa_free"][j]) > 0.0


def test_throttle_sla_monotone(loaded_env):
    """Memory-path degradation never improves the SLA rate on the same
    traces/seeds (fcfs: deterministic, unaffected by the churn masks
    beyond the advertised costs)."""
    env = loaded_env
    seeds = [31, 32, 33]
    base = evaluate_batch_baseline(env, BL.BASELINES["fcfs"], seeds)
    hit = evaluate_batch_baseline(
        env, BL.BASELINES["fcfs"], seeds,
        churn=churn_preset("throttle", n_events=2, magnitude=16.0))
    assert hit["sla_rate"] <= base["sla_rate"] + 1e-9


# ---------------------------------------------------------------------------
# fused training rounds with churn
# ---------------------------------------------------------------------------
def test_fused_rounds_churn_smoke(env):
    pcfg = P.PolicyConfig(feat_dim=env.feat_dim, act_dim=env.act_dim,
                          hidden=8)
    dcfg = D.DDPGConfig(policy=pcfg)
    state = D.init_ddpg(jax.random.PRNGKey(1), dcfg)
    buf = replay_init(64, env.seq_len, env.feat_dim, env.act_dim)
    rounds = make_train_rounds(
        env, dcfg, batch_episodes=2, num_updates=2, batch_size=8,
        sigma_min=0.05, sigma_decay=0.97, churn=churn_preset("mixed"))
    keys = round_keys(7, 0, 3)
    flags = jnp.array([False, True, True])
    state, buf, sigma, mets = rounds(state, buf, keys, jnp.float32(0.4),
                                     flags)
    assert np.isfinite(np.asarray(mets["sla"])).all()
    assert np.isfinite(np.asarray(mets["critic_loss"])).all()
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(state.actor))


def test_generalist_fused_rounds_churn_smoke():
    arr = ArrivalConfig(max_jobs=ECFG.max_jobs, horizon_us=ECFG.horizon_us,
                        slack_us=2 * ECFG.t_s_us)
    envs = build_padded_envs("light", ("paper6", "8simba"), ECFG, arr)
    spec = GeneralistSpec(m_max=envs[0].num_sas)
    pcfg = spec.pcfg(hidden=8)
    dcfg = D.DDPGConfig(policy=pcfg)
    state = D.init_ddpg(jax.random.PRNGKey(2), dcfg)
    buf = generalist_replay_init(64, envs[0].seq_len, spec)
    rounds = make_generalist_rounds(
        envs, dcfg, batch_episodes=2, num_updates=2, batch_size=8,
        sigma_min=0.05, sigma_decay=0.97, churn=churn_preset("fail"))
    keys = round_keys(9, 0, 2)
    flags = jnp.array([False, True])
    state, buf, sigma, mets = rounds(state, buf, keys, jnp.float32(0.4),
                                     flags)
    assert np.isfinite(np.asarray(mets["sla"])).all()
    assert np.isfinite(np.asarray(mets["critic_loss"])).all()


def test_churn_schedules_jax_shapes_and_masked_targets():
    cfg = churn_preset("slowdown", magnitude=2.0)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    scheds = jax.jit(
        lambda k: churn_schedules_jax(cfg, 8, 6, k))(keys)
    assert scheds["valid"].shape == (3, 8, 6)
    assert np.asarray(scheds["valid"]).all()         # slowdown: no fails
    lat = np.asarray(scheds["lat_mult"])
    assert lat.max() == 2.0 and lat.min() == 1.0


# ------------------------------------------- churn event generators
def test_failure_schedule_window_distinct_and_clamped():
    rng = np.random.default_rng(0)
    p, sa = failure_schedule(rng, periods=20, num_sas=4, n=10)
    assert len(p) == len(sa) == 3           # clamped: one SA survives
    assert p.dtype == np.int32 and sa.dtype == np.int32
    assert (p >= 5).all() and (p < 15).all()    # window (0.25, 0.75)
    assert len(set(sa.tolist())) == 3           # distinct targets
    p2, sa2 = failure_schedule(np.random.default_rng(0), periods=20,
                               num_sas=4, n=10)
    assert np.array_equal(p, p2) and np.array_equal(sa, sa2)


def test_join_schedule_shapes_and_window():
    p, sa = join_schedule(np.random.default_rng(1), periods=16, num_sas=6,
                          n=2, window=(0.5, 1.0))
    assert len(p) == 2
    assert (p >= 8).all() and (p < 16).all()
    assert len(set(sa.tolist())) == 2


def test_degradation_schedules_magnitude():
    for fn in (slowdown_schedule, throttle_schedule):
        p, sa, mag = fn(np.random.default_rng(2), periods=12, num_sas=5,
                        n=3, magnitude=6.0)
        assert len(p) == len(sa) == len(mag) == 3
        assert (mag == np.float32(6.0)).all()
        assert len(set(sa.tolist())) == 3
    # n clamps to the fleet width (degradation may hit every SA)
    p, sa, _ = slowdown_schedule(np.random.default_rng(3), periods=12,
                                 num_sas=2, n=9)
    assert len(sa) == 2
