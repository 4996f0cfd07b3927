"""Bandwidth-contention schedule executor.

Semantics (paper Sec. 3):
- Each sub-accelerator (SA) executes one sub-job (SJ) at a time,
  non-preemptively, in descending priority order among *ready* SJs
  (ready = predecessor finished, ready-time reached, SA idle).
- All SJs active at an instant share the off-chip bandwidth ``B``. When
  total demand ``D = sum(b_i) > B``, every active SJ progresses at the
  uniform rate ``rho = B / D`` — each demands bandwidth proportional to
  its requirement and all overlapping SJs suffer the *same stall
  cycles*, exactly the contention model of the paper.
- Time advances event-by-event (finish events + enabling times).

Three implementations with identical semantics:
- ``simulate_np``  — float64 NumPy oracle (tests, MAGMA fitness).
- ``simulate_jax`` — fixed-shape ``lax.while_loop`` version used inside
  the jitted environment/rollout (float32; times are period-relative so
  magnitudes stay small).  Per-SA reductions are one-hot masked
  max/min instead of ``jax.ops.segment_*``: XLA CPU lowers segment
  scatters to serial per-element loops, which destroys the ``vmap``
  vectorization the batched rollout pipeline depends on.  Per-slot
  lookups (``x[assign]``, ``finished[dep]``) are one-hot masked selects
  too, so the event loop holds no gather: XLA:TPU runs a batched gather
  at about 80 µs per 9,216 indices (96 streams x 96 slots), some 30x
  the rest of a loop trip.  ``assign`` must lie in ``[0, num_sas)``
  (``SchedulingEnv.simulate`` clips it so) and ``dep`` in ``[-1, n)``.
- ``simulate_jax_segments`` — the seed's segment-op formulation, kept
  as the "before" arm of ``benchmarks/rollout_throughput.py`` and as a
  third engine for parity cross-checks.

Times are in microseconds, bandwidths in GB/s.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = 1e30
_EPS = 1e-5


# --------------------------------------------------------------------------
# NumPy oracle
# --------------------------------------------------------------------------
def simulate_np(valid, assign, prio, cost, bw, dep, ready, sa_free, B):
    """Run the ready queue to completion. Returns (start, finish) float64.

    valid:  (n,) bool   slot holds a real SJ
    assign: (n,) int    SA index per SJ
    prio:   (n,) float  higher runs first (tie: lower slot index)
    cost:   (n,) float  contention-free execution time on assigned SA (us)
    bw:     (n,) float  bandwidth demand on assigned SA (GB/s)
    dep:    (n,) int    predecessor slot (-1 = none)
    ready:  (n,) float  earliest start time (us, external constraints)
    sa_free:(M,) float  time each SA becomes idle
    B:      float       shared DRAM bandwidth (GB/s)
    """
    valid = np.asarray(valid, bool)
    assign = np.asarray(assign, np.int64)
    prio = np.asarray(prio, np.float64)
    cost = np.asarray(cost, np.float64)
    bw = np.asarray(bw, np.float64)
    dep = np.asarray(dep, np.int64)
    ready = np.asarray(ready, np.float64)
    sa_free = np.asarray(sa_free, np.float64).copy()
    n, M = len(valid), len(sa_free)

    started = np.zeros(n, bool)
    finished = np.zeros(n, bool)
    progress = np.zeros(n)
    start = np.full(n, INF)
    finish = np.full(n, INF)
    t = 0.0

    def dep_ok():
        ok = dep < 0
        has = ~ok
        ok[has] = finished[dep[has]]
        return ok

    for _ in range(2 * n + M + 8):
        if not (valid & ~finished).any():
            break
        # ---- start phase: each idle SA admits its best ready candidate
        active = started & ~finished & valid
        for m in range(M):
            if t + _EPS < sa_free[m] or (active & (assign == m)).any():
                continue
            cand = valid & ~started & (assign == m) & dep_ok() & (ready <= t + _EPS)
            if cand.any():
                idxs = np.flatnonzero(cand)
                # identical scoring rule as the JAX engine: priorities are
                # tie-broken by slot index at 1e-6 granularity
                score = prio[idxs] - idxs * 1e-6
                i = idxs[np.argmax(score)]
                started[i] = True
                start[i] = t
                active[i] = True
        # ---- advance to next event
        next_t = INF
        if active.any():
            D = bw[active].sum()
            rho = min(1.0, B / D) if D > 0 else 1.0
            rem = (cost[active] - progress[active]) / max(rho, 1e-12)
            next_t = t + max(rem.min(), 0.0)
        else:
            rho = 1.0
        # enabling times (SA becoming free per config, or SJ ready-times)
        pend = valid & ~started & dep_ok()
        if pend.any():
            enab = np.maximum(sa_free[assign[pend]], ready[pend])
            enab = enab[enab > t + _EPS]
            if enab.size:
                next_t = min(next_t, enab.min())
        if next_t >= INF:
            break  # nothing can make progress (should not happen)
        if active.any():
            progress[active] += (next_t - t) * rho
            done = active & (progress >= cost - _EPS)
            finish[done] = next_t
            finished |= done
        t = next_t
    return start, finish


def commit_period_np(start, finish, valid, assign, t_s, num_sas):
    """Split a simulated schedule at the period boundary ``t_s``.

    Committed = SJs that *started* before t_s (non-preemptive: they run to
    completion).  Returns (committed mask, residual mask, new sa_free
    relative to the next period start).
    """
    committed = valid & (start < t_s)
    residual = valid & ~committed
    sa_free = np.zeros(num_sas)
    for m in range(num_sas):
        f = finish[committed & (assign == m)]
        if f.size:
            sa_free[m] = max(0.0, f.max() - t_s)
    return committed, residual, sa_free


# --------------------------------------------------------------------------
# JAX engine (jit / vmap friendly)
# --------------------------------------------------------------------------
@functools.partial(jax.jit,
                   static_argnames=("num_sas", "max_iters",
                                    "stop_start_after", "return_iters"))
def simulate_jax(valid, assign, prio, cost, bw, dep, ready, sa_free, B,
                 *, num_sas: int, max_iters: int | None = None,
                 stop_start_after: float | None = None,
                 return_iters: bool = False):
    """Fixed-shape JAX twin of :func:`simulate_np`. float32, (start, finish).

    ``return_iters=True`` also returns the event loop's iteration count
    ``(start, finish, iters)``: under ``vmap`` the batched loop runs
    until its slowest lane is done, so the maximum over the batch is
    the batched loop's trip count (the serving tick's ``tele`` block
    counts both).

    ``stop_start_after``: optional event-loop early exit for callers
    that only consume SJs *starting* before this time (the serving
    tick: committed = ``start < T_s``, and every committed-path state
    update derives from those SJs alone).  The loop runs the identical
    event sequence but stops once the clock has passed the horizon AND
    every SJ that started before it has finished — late starters still
    participate in bandwidth contention up to that point (so the early
    starters' finish times are exact), they just aren't simulated to
    completion afterwards (their ``finish`` stays ``INF``; their
    ``start`` is exact whenever it was assigned before the exit).
    ``None`` (default) runs to full completion — bit-identical to the
    unhorizoned loop, which is the prefix property the serving parity
    tests pin down.
    """
    n = valid.shape[0]
    M = num_sas
    if max_iters is None:
        max_iters = 3 * n + M + 16
    valid = valid.astype(bool)
    assign = assign.astype(jnp.int32)
    prio = prio.astype(jnp.float32)
    cost = cost.astype(jnp.float32)
    bw = bw.astype(jnp.float32)
    dep = dep.astype(jnp.int32)
    ready = ready.astype(jnp.float32)
    sa_free = sa_free.astype(jnp.float32)
    idx = jnp.arange(n)
    # (n, M) SA one-hot, loop-invariant: per-SA reductions below are
    # masked max/min over this instead of segment_* (XLA CPU lowers
    # segment scatters to serial per-element loops, which destroys the
    # vmap vectorization the batched rollout pipeline relies on), and
    # per-slot lookups x[assign] are masked selects over it (XLA:TPU
    # runs each batched gather at ~80 us a loop trip).  One True per row.
    onehot = assign[:, None] == jnp.arange(M)[None, :]
    # (n, n) predecessor one-hot, loop-invariant: finished[dep] as a 0/1
    # matvec, exact at any matmul precision (at most one non-zero term;
    # rows with dep < 0 are all zero).  On v5e it runs ~1 us a trip
    # faster than a masked any over a bool one-hot.
    dephot = (dep[:, None] == idx[None, :]).astype(jnp.bfloat16)

    def at_sa(x, fill, reduce):
        """x[assign] for an (M,) x: exactly one unmasked term per row."""
        return reduce(jnp.where(onehot, x[None, :], fill), axis=1)

    # loop-invariant hoists: tie-broken scores, per-slot SA-free times
    prio_tb = prio - idx.astype(jnp.float32) * 1e-6
    enab_static = jnp.maximum(at_sa(sa_free, -INF, jnp.max), ready)

    def body(state):
        it, t, started, finished, progress, start, finish = state
        active = started & ~finished & valid
        dep_done = jnp.where(dep < 0, True,
                             dephot @ finished.astype(jnp.bfloat16) > 0.5)
        # ---- start phase: per-SA best ready candidate on idle SAs
        sa_busy = jnp.any(active[:, None] & onehot, axis=0)
        sa_open = ~sa_busy & (sa_free <= t + _EPS)
        cand = (valid & ~started & dep_done & (ready <= t + _EPS)
                & at_sa(sa_open, False, jnp.any))
        # score: priority, tie-broken by lower slot index
        score = jnp.where(cand, prio_tb, -INF)
        best = jnp.max(jnp.where(onehot, score[:, None], -INF), axis=0)
        starts_now = (cand & (score >= at_sa(best, -INF, jnp.max) - 1e-9)
                      & (score > -INF / 2))
        # guard against float ties admitting 2 SJs on one SA: keep lowest idx
        first_idx = jnp.min(
            jnp.where(starts_now[:, None] & onehot, idx[:, None], n), axis=0)
        starts_now = starts_now & (idx == at_sa(first_idx, n, jnp.min))
        started = started | starts_now
        start = jnp.where(starts_now, t, start)
        active = active | starts_now
        # ---- next event
        # float32 event loop: tolerance scales with |t| so that finish
        # detection stays robust once remaining work drops below the
        # representable time resolution (otherwise the loop stalls).
        tol = _EPS + 4e-6 * t
        D = jnp.sum(jnp.where(active, bw, 0.0))
        rho = jnp.where(D > B, B / jnp.maximum(D, 1e-9), 1.0)
        rem = jnp.where(active,
                        jnp.maximum(cost - progress, 0.0)
                        / jnp.maximum(rho, 1e-12), INF)
        t_fin = t + jnp.maximum(jnp.min(rem), tol)   # force representable step
        pend = valid & ~started & dep_done
        enab = jnp.where(pend & (enab_static > t + _EPS), enab_static, INF)
        next_t = jnp.minimum(t_fin, jnp.min(enab))
        next_t = jnp.where(jnp.isfinite(next_t) & (next_t < INF / 2), next_t, t)
        # ---- progress update
        dt = next_t - t
        progress = jnp.where(active, progress + dt * rho, progress)
        done = active & (progress >= cost - tol)
        finish = jnp.where(done, next_t, finish)
        finished = finished | done
        return it + 1, next_t, started, finished, progress, start, finish

    stop = INF if stop_start_after is None else float(stop_start_after)

    def cond(state):
        it, t, started, finished, _, start, _ = state
        live = jnp.any(valid & ~finished)
        # past the start horizon, only early starters still owed a
        # finish keep the loop alive (stop = INF reduces to `live`)
        early_open = jnp.any(valid & started & (start < stop) & ~finished)
        return (it < max_iters) & live & ((t < stop) | early_open)

    init = (jnp.array(0), jnp.array(0.0, jnp.float32),
            jnp.zeros(n, bool), jnp.zeros(n, bool), jnp.zeros(n, jnp.float32),
            jnp.full(n, INF, jnp.float32), jnp.full(n, INF, jnp.float32))
    it, *_, start, finish = jax.lax.while_loop(cond, body, init)
    return (start, finish, it) if return_iters else (start, finish)


@functools.partial(jax.jit, static_argnames=("num_sas", "max_iters",
                                             "stop_start_after",
                                             "return_iters"))
def simulate_jax_segments(valid, assign, prio, cost, bw, dep, ready, sa_free,
                          B, *, num_sas: int, max_iters: int | None = None,
                          stop_start_after: float | None = None,
                          return_iters: bool = False):
    """Seed implementation of :func:`simulate_jax` (jax.ops.segment_*).

    Kept verbatim as (a) the "before" arm of
    ``benchmarks/rollout_throughput.py`` — XLA CPU lowers the segment
    scatters to serial per-element loops, which is exactly the
    behaviour the one-hot rewrite above removes — and (b) a third
    engine implementation for parity cross-checks in tests.  It is
    signature-compatible with :func:`simulate_jax` (callers swap the
    two), but the serving-only ``stop_start_after`` early exit is not
    implemented here — the legacy arm never serves, so any non-``None``
    value is a trace-time error rather than a silent full run.
    """
    if stop_start_after is not None:
        raise ValueError("simulate_jax_segments has no stop_start_after "
                         "early exit (legacy engine; training/benchmark "
                         "paths only)")
    n = valid.shape[0]
    M = num_sas
    if max_iters is None:
        max_iters = 3 * n + M + 16
    valid = valid.astype(bool)
    assign = assign.astype(jnp.int32)
    prio = prio.astype(jnp.float32)
    cost = cost.astype(jnp.float32)
    bw = bw.astype(jnp.float32)
    dep = dep.astype(jnp.int32)
    ready = ready.astype(jnp.float32)
    sa_free = sa_free.astype(jnp.float32)
    idx = jnp.arange(n)

    def dep_ok(finished):
        return jnp.where(dep < 0, True, finished[jnp.clip(dep, 0)])

    def body(state):
        it, t, started, finished, progress, start, finish = state
        active = started & ~finished & valid
        sa_busy = jax.ops.segment_max(active.astype(jnp.int32), assign,
                                      num_segments=M) > 0
        sa_open = ~sa_busy & (sa_free <= t + _EPS)
        cand = (valid & ~started & dep_ok(finished) & (ready <= t + _EPS)
                & sa_open[assign])
        score = jnp.where(cand, prio - idx.astype(jnp.float32) * 1e-6, -INF)
        best = jax.ops.segment_max(score, assign, num_segments=M)
        starts_now = cand & (score >= best[assign] - 1e-9) & (score > -INF / 2)
        first_idx = jax.ops.segment_min(jnp.where(starts_now, idx, n), assign,
                                        num_segments=M)
        starts_now = starts_now & (idx == first_idx[assign])
        started = started | starts_now
        start = jnp.where(starts_now, t, start)
        active = active | starts_now
        tol = _EPS + 4e-6 * t
        D = jnp.sum(jnp.where(active, bw, 0.0))
        rho = jnp.where(D > B, B / jnp.maximum(D, 1e-9), 1.0)
        rem = jnp.where(active,
                        jnp.maximum(cost - progress, 0.0)
                        / jnp.maximum(rho, 1e-12), INF)
        t_fin = t + jnp.maximum(jnp.min(rem), tol)
        pend = valid & ~started & dep_ok(finished)
        enab = jnp.where(pend, jnp.maximum(sa_free[assign], ready), INF)
        enab = jnp.where(enab > t + _EPS, enab, INF)
        next_t = jnp.minimum(t_fin, jnp.min(enab))
        next_t = jnp.where(jnp.isfinite(next_t) & (next_t < INF / 2), next_t, t)
        dt = next_t - t
        progress = jnp.where(active, progress + dt * rho, progress)
        done = active & (progress >= cost - tol)
        finish = jnp.where(done, next_t, finish)
        finished = finished | done
        return it + 1, next_t, started, finished, progress, start, finish

    def cond(state):
        it, _, _, finished, *_ = state
        return (it < max_iters) & jnp.any(valid & ~finished)

    init = (jnp.array(0), jnp.array(0.0, jnp.float32),
            jnp.zeros(n, bool), jnp.zeros(n, bool), jnp.zeros(n, jnp.float32),
            jnp.full(n, INF, jnp.float32), jnp.full(n, INF, jnp.float32))
    it, *_, start, finish = jax.lax.while_loop(cond, body, init)
    return (start, finish, it) if return_iters else (start, finish)
