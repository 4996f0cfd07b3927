"""Slot packing (``SchedulingEnv.build_slots``) against the sort-and-search
packing it replaced, kept here as the oracle: a stable ``argsort`` of the
deadlines, ``searchsorted`` over the cumulative layer counts, and batched
gathers for every per-slot value.  The program's packing has none of
those; every field it returns must equal the oracle's bit for bit,
invalid slots included, on every queue state below.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.generalist.env import PaddedEnv
from repro.costmodel.fleets import fleet_names
from repro.sim.engine import INF
from repro.sim.env import CHURN_POISON_US, EnvConfig, SchedulingEnv
from repro.workloads import LM_WORKLOADS, build_llm_registry, build_registry

CFG = EnvConfig()                     # the cells' R = 96 slots, J = 64 jobs
REGISTRIES = ["light", "heavy", "lm_dsv2lite"]
STATES = ["empty", "partial", "overfull", "tied", "mid_pass", "churn"]


def _packing_oracle(env, state, trace, cutoff):
    """Sort + ``searchsorted`` + gathers: the packing as first written."""
    R, J = env.cfg.max_rq, env.cfg.max_jobs
    deadline = env.pass_deadline(state, trace)
    active = ((trace["arrival"] <= cutoff) & ~state["done"]
              & ~state["missed"])
    rem = jnp.where(active, env.pass_end(state, trace) - state["nls"], 0)
    key = jnp.where(active & (rem > 0), deadline, INF)
    order = jnp.argsort(key)
    rem_o = rem[order]
    cum = jnp.cumsum(rem_o)
    starts = cum - rem_o
    total = cum[-1]
    i = jnp.arange(R)
    k = jnp.clip(jnp.searchsorted(cum, i, side="right"), 0, J - 1)
    valid = i < jnp.minimum(total, R)
    job = jnp.where(valid, order[k], 0)
    layer = jnp.where(valid, state["nls"][job] + (i - starts[k]), 0)
    layer = jnp.clip(layer, 0, env.lmax - 1)
    prev_same = jnp.concatenate(
        [jnp.array([False]), (job[1:] == job[:-1]) & valid[1:] & valid[:-1]])
    dep = jnp.where(prev_same, i - 1, -1)
    model = trace["model"][job]
    ready_rel = jnp.where(
        dep < 0, jnp.maximum(0.0, state["jready"][job] - state["t"]), 0.0)
    cost_all = env.lat[model, layer]
    bw_all = env.bw[model, layer]
    en_all = env.en[model, layer]
    if "lat_mult" in state:
        cost_all = cost_all * state["lat_mult"][None, :]
    if "bw_mult" in state:
        bw_all = bw_all * state["bw_mult"][None, :]
    if "sa_valid" in state:
        cost_all = jnp.where(state["sa_valid"][None, :], cost_all,
                             CHURN_POISON_US)
    zero = jnp.where(valid[:, None], 1.0, 0.0)
    return dict(job=job, layer=layer, valid=valid, dep=dep,
                ready_rel=ready_rel * valid,
                cost_all=cost_all * zero, bw_all=bw_all * zero,
                en_all=en_all * zero, model=model,
                deadline=deadline[job], q=trace["q"][job],
                arrival=trace["arrival"][job])


def _env(name):
    build = build_llm_registry if name.startswith("lm_") else build_registry
    return SchedulingEnv(build(name), CFG)


@pytest.fixture(scope="module", params=REGISTRIES)
def packers(request):
    env = _env(request.param)
    new = jax.jit(env.build_slots)
    old = jax.jit(lambda s, tr, c: _packing_oracle(env, s, tr, c))
    return env, new, old


def _queue(env, kind, seed=0):
    """``(state, trace, cutoff)`` of one queue state of ``kind``."""
    rng = np.random.default_rng(seed)
    trace, state = env.new_episode(rng)
    trace = {k: np.asarray(v) for k, v in trace.items()}
    state = {k: np.asarray(v) for k, v in state.items()}
    J, njl = env.cfg.max_jobs, trace["njl"]
    if kind not in ("empty", "partial"):
        # every one of the J jobs a real request, not a free row
        pad = trace["arrival"] >= INF / 2
        a = rng.uniform(0.0, 2000.0, J).astype(np.float32)
        q = (3.0 * np.asarray(env.min_lat)[trace["model"]]).astype(np.float32)
        trace["arrival"] = np.where(pad, a, trace["arrival"])
        trace["q"] = np.where(pad, q, trace["q"])
        trace["deadline"] = np.where(pad, a + q, trace["deadline"])
        if env.reenters:
            state["dl"] = trace["deadline"]
    arrived = trace["arrival"] < INF / 2
    t = np.float32(np.median(trace["arrival"][arrived]))
    state["t"] = t
    state["jready"] = np.where(
        rng.random(J) < 0.5, trace["arrival"],
        t + rng.uniform(-300.0, 900.0, J)).astype(np.float32)
    cutoff = INF
    if kind == "empty":
        cutoff = np.float32(-1.0)            # nothing has arrived
    elif kind == "partial":
        keep = rng.permutation(np.flatnonzero(arrived))[:3]
        state["done"] = ~np.isin(np.arange(J), keep)
        state["nls"] = np.where(state["done"], 0,
                                rng.integers(0, njl)).astype(np.int32)
    elif kind == "tied":
        trace["deadline"] = rng.choice(
            np.float32([2500.0, 4000.0, 4000.5]), J).astype(np.float32)
        if env.reenters:
            state["dl"] = trace["deadline"]
        # a few layers left each, so that many jobs share the R slots
        state["nls"] = np.maximum(njl - rng.integers(1, 6, J),
                                  0).astype(np.int32)
        state["done"] = rng.random(J) < 0.3
        state["missed"] = ~state["done"] & (rng.random(J) < 0.1)
    elif kind in ("mid_pass", "churn"):
        state["nls"] = rng.integers(0, njl).astype(np.int32)
        if env.reenters:
            # requests of several tokens: some at decode_start with their
            # first token out, some mid-decode, the rest mid-prefill
            ds = trace["ds"]
            n_out = rng.integers(2, 9, J).astype(np.int32)
            u = rng.random(J)
            nls = np.where(u < 0.15, ds, np.where(
                u < 0.25, rng.integers(ds, njl),
                rng.integers(0, ds))).astype(np.int32)
            decoding = nls >= ds
            passes_left = np.where(
                decoding, rng.integers(1, n_out), n_out - 1).astype(np.int32)
            t_first = np.where(decoding, t - 50.0, INF).astype(np.float32)
            tpot = rng.uniform(100.0, 1000.0, J).astype(np.float32)
            # prefills due about as soon as the next tokens, so that the
            # R slots hold both kinds of pass
            ttft = (t + rng.uniform(0.0, 3000.0, J)).astype(np.float32)
            trace.update(n_out=n_out, tpot=tpot, deadline=ttft)
            state.update(nls=nls, passes_left=passes_left, t_first=t_first,
                         dl=np.where(decoding, t_first + tpot * (n_out - 1),
                                     ttft).astype(np.float32))
        if kind == "churn":
            M = env.num_sas
            state["lat_mult"] = np.where(np.arange(M) == 0, 2.5,
                                         1.0).astype(np.float32)
            state["bw_mult"] = np.where(np.arange(M) == 1, 0.5,
                                        1.0).astype(np.float32)
            state["sa_valid"] = np.arange(M) != M - 1
    # "overfull": every job arrived, none started (init_state's nls 0)
    return state, trace, np.float32(cutoff)


def _assert_bitwise(got, want):
    assert set(got) == set(want)
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        assert (g.dtype, g.shape) == (w.dtype, w.shape), k
        assert g.tobytes() == w.tobytes(), (k, g, w)


@pytest.mark.parametrize("kind", STATES)
def test_packing_equals_sort_and_search_bitwise(packers, kind):
    env, new, old = packers
    state, trace, cutoff = _queue(env, kind)
    want = old(state, trace, cutoff)
    _assert_bitwise(new(state, trace, cutoff), want)
    # each state is what its name says
    n = int(np.sum(want["valid"]))
    R = env.cfg.max_rq
    if kind == "empty":
        assert n == 0
    elif kind == "partial":
        assert 0 < n < R
    elif kind == "overfull":
        assert n == R and np.sum(env.pass_end(state, trace)) > R
    elif kind == "tied":
        dl = np.asarray(want["deadline"])[np.asarray(want["valid"])]
        jobs = np.asarray(want["job"])[np.asarray(want["valid"])]
        assert len(set(jobs)) > len(set(dl))          # ties were packed
    elif kind == "mid_pass" and env.reenters:
        layer = np.asarray(want["layer"])[np.asarray(want["valid"])]
        ds = int(np.asarray(trace["ds"])[0])
        assert (layer < ds).any() and (layer >= ds).any()
        assert (state["nls"] == trace["ds"]).any()
    elif kind == "churn":
        cost = np.asarray(want["cost_all"])[np.asarray(want["valid"])]
        assert (cost[:, -1] == CHURN_POISON_US).all()


def test_packing_holds_no_gather_loop_or_sort(packers):
    """Compiled for a batch of streams, as the serving tick runs it."""
    env, _, _ = packers
    S = 4
    state, trace, cutoff = _queue(env, "mid_pass")
    batch = lambda tree: jax.tree.map(lambda x: np.stack([x] * S), tree)
    hlo = jax.jit(jax.vmap(env.build_slots, in_axes=(0, 0, None))).lower(
        batch(state), batch(trace), cutoff).compile().as_text()
    for op in ("gather", "while", "sort"):
        assert not re.search(rf"\b{op}\(", hlo), op
    # the oracle's program has all three, so the search would find them
    old = jax.jit(jax.vmap(lambda s, tr: _packing_oracle(env, s, tr, cutoff)))
    old_hlo = old.lower(batch(state), batch(trace)).compile().as_text()
    assert all(re.search(rf"\b{op}\(", old_hlo)
               for op in ("gather", "while", "sort"))


def _every_table():
    cnn = [(w, f) for f in fleet_names() for w in ("heavy", "light", "mixed")]
    return cnn + [(w, None) for w in sorted(LM_WORKLOADS)]


@pytest.mark.parametrize("workload,fleet", _every_table())
def test_cost_contraction_equals_the_table_gather(workload, fleet):
    """Every (model, layer) row of every tenant table, on every fleet (and
    padded to the widest fleet, whose phantom SAs cost 1e7)."""
    if fleet is None:
        envs = [_env(workload)]
    else:
        reg = build_registry(workload, mas=fleet)
        envs = [SchedulingEnv(reg, CFG), PaddedEnv(reg, CFG, m_max=8)]
    for env in envs:
        n, L = env.num_models, env.lmax
        model = jnp.repeat(jnp.arange(n), L)
        layer = jnp.tile(jnp.arange(L), n)
        got = jax.jit(env.layer_costs)(model, layer)
        for g, table in zip(got, (env.lat, env.bw, env.en)):
            want = np.asarray(table).reshape(n * L, -1)
            assert np.asarray(g).tobytes() == want.tobytes()
