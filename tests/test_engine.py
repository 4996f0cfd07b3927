"""Contention-engine tests: NumPy oracle vs JAX twin + invariants."""
import numpy as np
import pytest

try:
    from hypothesis import example, given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:          # property tests skip; deterministic ones run
    HAS_HYPOTHESIS = False

from repro.costmodel.fleets import fleet_names
from repro.sim.engine import _EPS, INF, simulate_jax, simulate_np


def run_both(valid, assign, prio, cost, bw, dep, ready, sa_free, B, M):
    s_np, f_np = simulate_np(valid, assign, prio, cost, bw, dep, ready,
                             sa_free, B)
    import jax.numpy as jnp
    s_j, f_j = simulate_jax(
        jnp.asarray(valid), jnp.asarray(assign), jnp.asarray(prio),
        jnp.asarray(cost, jnp.float32), jnp.asarray(bw, jnp.float32),
        jnp.asarray(dep), jnp.asarray(ready, jnp.float32),
        jnp.asarray(sa_free, jnp.float32), jnp.float32(B), num_sas=M)
    return (s_np, f_np), (np.asarray(s_j), np.asarray(f_j))


def test_single_job_no_contention():
    # one SJ, SA free, plenty of bandwidth -> start 0, finish = cost
    (s, f), (sj, fj) = run_both(
        valid=[True], assign=[0], prio=[0.5], cost=[10.0], bw=[4.0],
        dep=[-1], ready=[0.0], sa_free=[0.0], B=16.0, M=2)
    assert s[0] == 0.0 and f[0] == pytest.approx(10.0)
    assert fj[0] == pytest.approx(10.0, rel=1e-5)


def test_bandwidth_contention_slowdown():
    # two SJs on different SAs, each demanding 12 GB/s of a 16 GB/s bus:
    # D=24 > 16 -> rho = 2/3 -> both take cost / (2/3) = 15
    (s, f), (sj, fj) = run_both(
        valid=[True, True], assign=[0, 1], prio=[0.5, 0.5],
        cost=[10.0, 10.0], bw=[12.0, 12.0], dep=[-1, -1],
        ready=[0.0, 0.0], sa_free=[0.0, 0.0], B=16.0, M=2)
    assert f[0] == pytest.approx(15.0) and f[1] == pytest.approx(15.0)
    np.testing.assert_allclose(fj, f, rtol=1e-4)


def test_partial_overlap_contention():
    # SJ0: cost 10 bw 12; SJ1 arrives ready at t=5, bw 12.
    # [0,5): rho=1 (prog0=5); [5,?): rho=2/3.
    # SJ0 remaining 5 at rate 2/3 -> finishes at 5 + 7.5 = 12.5
    # SJ1: progress 7.5*2/3 = 5 by 12.5, then alone rate 1 -> 12.5+5 = 17.5
    (s, f), (_, fj) = run_both(
        valid=[True, True], assign=[0, 1], prio=[0.5, 0.5],
        cost=[10.0, 10.0], bw=[12.0, 12.0], dep=[-1, -1],
        ready=[0.0, 5.0], sa_free=[0.0, 0.0], B=16.0, M=2)
    assert f[0] == pytest.approx(12.5) and f[1] == pytest.approx(17.5)
    np.testing.assert_allclose(fj, f, rtol=1e-4)


def test_priority_order_on_same_sa():
    (s, f), _ = run_both(
        valid=[True, True], assign=[0, 0], prio=[-0.5, 0.9],
        cost=[5.0, 5.0], bw=[1.0, 1.0], dep=[-1, -1],
        ready=[0.0, 0.0], sa_free=[0.0], B=16.0, M=1)
    assert s[1] == 0.0 and s[0] == pytest.approx(5.0)  # slot1 runs first


def test_dependency_chain():
    # slot1 depends on slot0 (different SAs): must start at slot0's finish
    (s, f), (_, fj) = run_both(
        valid=[True, True], assign=[0, 1], prio=[0.5, 0.9],
        cost=[5.0, 3.0], bw=[1.0, 1.0], dep=[-1, 0],
        ready=[0.0, 0.0], sa_free=[0.0, 0.0], B=16.0, M=2)
    assert s[1] == pytest.approx(5.0) and f[1] == pytest.approx(8.0)
    np.testing.assert_allclose(fj, f, rtol=1e-4)


def test_sa_initially_busy():
    (s, f), _ = run_both(
        valid=[True], assign=[0], prio=[0.0], cost=[2.0], bw=[1.0],
        dep=[-1], ready=[0.0], sa_free=[7.0], B=16.0, M=1)
    assert s[0] == pytest.approx(7.0) and f[0] == pytest.approx(9.0)


def test_ready_skip_does_not_deadlock():
    # higher-priority SJ not ready until t=10; lower-prio one runs first
    (s, f), _ = run_both(
        valid=[True, True], assign=[0, 0], prio=[0.9, 0.1],
        cost=[4.0, 4.0], bw=[1.0, 1.0], dep=[-1, -1],
        ready=[10.0, 0.0], sa_free=[0.0], B=16.0, M=1)
    assert s[1] == 0.0 and s[0] == pytest.approx(10.0)

if HAS_HYPOTHESIS:
    @st.composite
    def scenario(draw):
        n = draw(st.integers(2, 12))
        M = draw(st.integers(1, 4))
        n_jobs = draw(st.integers(1, 4))
        job_of = [draw(st.integers(0, n_jobs - 1)) for _ in range(n)]
        job_of.sort()  # contiguous layers per job, like the env packing
        dep = [-1] * n
        for i in range(1, n):
            if job_of[i] == job_of[i - 1]:
                dep[i] = i - 1
        fl = st.floats(0.5, 20.0, allow_nan=False, width=32)
        return dict(
            valid=[True] * n,
            assign=[draw(st.integers(0, M - 1)) for _ in range(n)],
            prio=[draw(st.floats(-1, 1, allow_nan=False, width=32))
                  for _ in range(n)],
            cost=[draw(fl) for _ in range(n)],
            bw=[draw(st.floats(0.5, 16.0, allow_nan=False, width=32))
                for _ in range(n)],
            dep=dep,
            ready=[0.0 if dep[i] >= 0 else draw(st.floats(0, 10, width=32))
                   for i in range(n)],
            sa_free=[draw(st.floats(0, 5, width=32)) for _ in range(M)],
            B=draw(st.floats(4.0, 16.0, width=32)), M=M)


    @given(scenario())
    @settings(max_examples=60, deadline=None)
    def test_property_jax_matches_oracle(sc):
        M = sc.pop("M")
        (s, f), (sj, fj) = run_both(**sc, M=M)
        n = len(sc["valid"])
        assert np.all(np.isfinite(f)), "oracle must finish every valid SJ"
        assert np.all(fj < INF / 2), "jax engine must finish every valid SJ"
        np.testing.assert_allclose(sj, s, rtol=1e-3, atol=1e-2)
        np.testing.assert_allclose(fj, f, rtol=1e-3, atol=1e-2)


    @given(scenario())
    @example(dict(valid=[True, True], assign=[0, 1], prio=[0.5, 0.5],
                  cost=[3.0, 2.0], bw=[1.0, 1.0], dep=[-1, -1],
                  ready=[1.2e-6, 9e-6], sa_free=[0.0, 0.0], B=16.0, M=2))
    @settings(max_examples=40, deadline=None)
    def test_property_schedule_invariants(sc):
        """No SA overlap; precedence respected; finish >= start + cost.

        Each bound holds to the engines' event tolerance ``_EPS``
        (1e-5 us) and no tighter.  Both engines treat times less than
        ``_EPS`` apart as one instant: an SJ whose ready time or SA-free
        time lies within ``_EPS`` after ``t`` starts at ``t`` (a drawn
        ready of 1.2e-6 us starts at 0), and one whose progress is
        within ``_EPS`` of its cost finishes.  The float64 oracle keeps
        that slack to agree with the float32 engine, which resolves
        period-relative times no finer: one float32 ulp is 7.6e-6 us
        between 64 and 128 us.  One ulp at the largest time this test
        draws (10 us: 9.5e-7) would still reject a ready of 9e-6 us that
        both engines start at 0.
        """
        M = sc.pop("M")
        (s, f), _ = run_both(**sc, M=M)
        n = len(sc["valid"])
        cost = np.asarray(sc["cost"])
        tol = _EPS
        # duration can only stretch under contention, never shrink
        assert np.all(f - s >= cost - tol)
        # SA exclusivity: intervals on the same SA don't overlap
        for m in range(M):
            idx = [i for i in range(n) if sc["assign"][i] == m]
            iv = sorted((s[i], f[i]) for i in idx)
            for (s1, f1), (s2, f2) in zip(iv, iv[1:]):
                assert s2 >= f1 - tol
            for i in idx:  # respects initial busy period
                assert s[i] >= sc["sa_free"][m] - tol
        # precedence
        for i in range(n):
            d = sc["dep"][i]
            if d >= 0:
                assert s[i] >= f[d] - tol
            assert s[i] >= sc["ready"][i] - tol
else:
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_property_engine():
        pass


# ---------------------------------------------------------------------------
# gather-free event loop: batched parity with the segment-op engine
# ---------------------------------------------------------------------------
def _batch(seed, S, n, M, deps):
    """S streams of n slots packed like the env: a valid prefix of jobs,
    each job's layers contiguous; ``deps`` "chain" links each layer to
    the one before it, "any" to a random earlier valid slot."""
    rng = np.random.default_rng(seed)
    valid = np.zeros((S, n), bool)
    dep = np.full((S, n), -1, np.int32)
    ready = np.zeros((S, n), np.float32)
    for s in range(S):
        k = int(rng.integers(n // 2, n + 1))
        valid[s, :k] = True
        i = 0
        while i < k:
            ready[s, i] = rng.uniform(0, 300)
            end = min(i + int(rng.integers(1, 8)), k)
            for j in range(i + 1, end):
                dep[s, j] = j - 1 if deps == "chain" else rng.integers(0, j)
            i = end
    return dict(
        valid=valid, assign=rng.integers(0, M, (S, n)).astype(np.int32),
        prio=rng.uniform(-1, 1, (S, n)).astype(np.float32),
        cost=rng.uniform(5, 200, (S, n)).astype(np.float32),
        bw=rng.uniform(0.5, 8, (S, n)).astype(np.float32),
        dep=dep, ready=ready,
        sa_free=rng.uniform(0, 100, (S, M)).astype(np.float32))


_KEYS = ("valid", "assign", "prio", "cost", "bw", "dep", "ready", "sa_free")


@pytest.mark.parametrize("fleet", fleet_names())
@pytest.mark.parametrize("stop", [None, 500.0])
@pytest.mark.parametrize("deps", ["chain", "any"])
def test_vmapped_engine_matches_segments_and_oracle(deps, stop, fleet):
    """Under vmap, the one-hot engine equals the segment-op engine (which
    keeps its gathers) bit for bit, and the float64 oracle within the
    property test's tolerance; with ``stop_start_after`` set, on the
    full run's committed prefix (SJs starting before the horizon)."""
    import jax
    import jax.numpy as jnp
    from repro.costmodel import get_fleet
    from repro.sim.engine import simulate_jax_segments
    M, B = get_fleet(fleet).num_sas, 16.0
    b = _batch(17, 8, 32, M, deps)
    args = [jnp.asarray(b[k]) for k in _KEYS]
    run = lambda fn, **kw: jax.vmap(lambda *a: fn(
        *a, jnp.float32(B), num_sas=M, return_iters=True, **kw))(*args)
    s_seg, f_seg, it_seg = map(np.asarray, run(simulate_jax_segments))
    s, f, it = map(np.asarray, run(simulate_jax, stop_start_after=stop))
    keep = b["valid"] if stop is None else s_seg < stop
    assert keep.any() and (stop is None or not keep[b["valid"]].all())
    np.testing.assert_array_equal(s[keep], s_seg[keep])
    np.testing.assert_array_equal(f[keep], f_seg[keep])
    if stop is None:
        np.testing.assert_array_equal(s, s_seg)
        np.testing.assert_array_equal(f, f_seg)
        np.testing.assert_array_equal(it, it_seg)
    for i in range(len(b["valid"])):
        s_np, f_np = simulate_np(*(b[k][i] for k in _KEYS), B)
        k = keep[i]
        np.testing.assert_allclose(s[i][k], s_np[k], rtol=1e-3, atol=1e-2)
        np.testing.assert_allclose(f[i][k], f_np[k], rtol=1e-3, atol=1e-2)


def test_vmapped_engine_lowers_without_gather():
    """At serving shapes (96 streams x 96 slots, 6 SAs, the tick's
    horizon) the batched engine's StableHLO holds no gather, in the
    event loop or before it: XLA:TPU runs each batched gather at ~80 us
    a loop trip."""
    import jax
    import jax.numpy as jnp
    S, n, M = 96, 96, 6
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    args = (spec((S, n), jnp.bool_), spec((S, n), jnp.int32),
            *(spec((S, n), jnp.float32) for _ in range(3)),
            spec((S, n), jnp.int32), spec((S, n), jnp.float32),
            spec((S, M), jnp.float32))
    engine = jax.jit(jax.vmap(lambda *a: simulate_jax(
        *a, jnp.float32(16.0), num_sas=M, stop_start_after=500.0,
        return_iters=True)))
    text = engine.lower(*args).as_text()
    assert "stablehlo.while" in text
    assert "gather" not in text
