"""Fleet churn as traced in-episode event schedules.

RELMAS assumes a fixed accelerator fleet for a whole episode; the
production north-star is a scheduler that survives fleet *churn* — SAs
failing, throttling, slowing down, or joining mid-episode.  This module
is the churn twin of ``repro.sim.arrivals``: a seeded scenario
generator that draws a fixed-shape **event list** per episode and
compiles it into per-period churn rows that flow into
:meth:`~repro.sim.env.SchedulingEnv.episode` as pure trace data — the
same no-recompile trick as ``bind_tables`` (the schedule is scanned
``xs``, never a shape).

Representation
--------------
Events are a dict of fixed-shape arrays (``E = max_events`` rows,
padded with ``EV_NONE``)::

    period (E,) int32   first period the event is in effect
    sa     (E,) int32   target sub-accelerator
    code   (E,) int32   EV_FAIL / EV_JOIN / EV_THROTTLE / EV_SLOWDOWN
    mag    (E,) float32 multiplier for degradation events

:func:`compile_schedule` turns them into per-period rows::

    valid    (T, M) bool     SA may accept new placements this period
    lat_mult (T, M) float32  busy-time multiplier (compute slowdown)
    bw_mult  (T, M) float32  bus-demand multiplier (memory throttle)

Event semantics (documented in ARCHITECTURE.md "Time-varying fleets"):

- ``EV_FAIL`` — fail-stop with graceful drain: the SA accepts no new
  placements from the event period onward (masked out of every policy's
  allocation; its advertised cost saturates for the heuristics), but
  work already committed finishes and is counted.
- ``EV_JOIN`` — elastic capacity: the target SA is *absent* from period
  0 and flips valid at the event period (a later JOIN also revives an
  earlier FAIL of the same SA — last event wins per period).
- ``EV_SLOWDOWN`` — compute straggler: every layer on the SA takes
  ``mag``x its characterized latency (advertised busy-times scale too,
  so deadline-aware policies route around it).
- ``EV_THROTTLE`` — memory-path degradation (MoCA-style): the SA's
  sub-jobs demand ``mag``x the shared bus bandwidth per unit of work,
  raising contention for everyone overlapping them.

One generator per event class draws the events
(:func:`failure_schedule`, :func:`join_schedule`,
:func:`slowdown_schedule`, :func:`throttle_schedule`);
:func:`churn_events` assembles them into the traced representation
(NumPy host path for eval/benchmarks, ``jax.random`` twin for the fused
training rounds).

An all-no-op schedule (:func:`no_op_schedule`, or ``compile_schedule``
of all-``EV_NONE`` events) is the **bit-exact identity**: every churn
application site is ``x * 1.0`` / ``where(True, x, _)`` — the
churn-enabled program reproduces the static-fleet episode bit-for-bit
(pinned by ``tests/test_churn.py``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

# event codes (the `code` column of the fixed-shape event arrays)
EV_NONE, EV_FAIL, EV_JOIN, EV_THROTTLE, EV_SLOWDOWN = 0, 1, 2, 3, 4

CHURN_SCENARIOS = ("none", "fail", "throttle", "slowdown", "join", "mixed")


@dataclasses.dataclass(frozen=True)
class ChurnConfig:
    """Seeded churn scenario (static under jit, like ``ArrivalConfig``).

    ``max_events`` fixes the event-array shape ``E``; ``n_events`` is
    how many real events the scenario draws (the rest pad with
    ``EV_NONE``).  ``window`` bounds event periods as fractions of the
    episode; ``magnitude`` is the lat/bw multiplier of degradation
    events.  Keep ``n_events`` well below the smallest fleet width —
    the fail draw never kills the last SA, but a schedule that degrades
    every SA at once stops being a churn scenario.
    """
    scenario: str = "none"
    max_events: int = 4
    n_events: int = 1
    magnitude: float = 4.0
    window: tuple[float, float] = (0.25, 0.75)


def churn_preset(name: str, **overrides) -> ChurnConfig:
    """Build a ChurnConfig for a named scenario (plus overrides)."""
    if name not in CHURN_SCENARIOS:
        raise ValueError(f"unknown churn scenario {name!r}; pick one of "
                         f"{CHURN_SCENARIOS}")
    defaults: dict = {"none": dict(n_events=0), "mixed": dict(n_events=3)}
    kw = {**defaults.get(name, {}), **overrides}
    return ChurnConfig(scenario=name, **kw)


def _event_plan(cfg: ChurnConfig) -> list[int]:
    """Static list of event codes the scenario draws (length <= E)."""
    if cfg.scenario == "none" or cfg.n_events <= 0:
        return []
    n = min(cfg.n_events, cfg.max_events)
    if cfg.scenario == "mixed":
        return [EV_FAIL, EV_THROTTLE, EV_JOIN, EV_SLOWDOWN][:n]
    code = {"fail": EV_FAIL, "throttle": EV_THROTTLE,
            "slowdown": EV_SLOWDOWN, "join": EV_JOIN}[cfg.scenario]
    return [code] * n


def no_op_events(max_events: int = 4) -> dict[str, np.ndarray]:
    """All-``EV_NONE`` event arrays (compiles to the identity schedule)."""
    z = np.zeros((max_events,), np.int32)
    return dict(period=z, sa=z.copy(), code=z.copy(),
                mag=np.ones((max_events,), np.float32))


def _draw(rng: np.random.Generator, n: int, periods: int, num_sas: int,
          window: tuple[float, float]) -> tuple[np.ndarray, np.ndarray]:
    """``n`` events on distinct SAs at uniform periods inside
    ``window``: (period, sa) int32 arrays."""
    lo = int(window[0] * periods)
    hi = max(lo + 1, int(window[1] * periods))
    p = rng.integers(lo, hi, size=n)
    sa = rng.choice(num_sas, size=n, replace=False)
    return p.astype(np.int32), sa.astype(np.int32)


def failure_schedule(rng: np.random.Generator, *, periods: int,
                     num_sas: int, n: int = 1,
                     window: tuple[float, float] = (0.25, 0.75)
                     ) -> tuple[np.ndarray, np.ndarray]:
    """Draw ``n`` fail-stop events: (period, sa) int32 arrays.

    Each event marks one SA as failed from that period onward.  Events
    land uniformly inside ``window`` (fractions of the episode) and
    target *distinct* SAs; ``n`` is clamped to ``num_sas - 1`` so at
    least one SA survives — a fleet with zero valid SAs has no
    meaningful schedule.
    """
    return _draw(rng, max(0, min(int(n), num_sas - 1)), periods, num_sas,
                 window)


def join_schedule(rng: np.random.Generator, *, periods: int,
                  num_sas: int, n: int = 1,
                  window: tuple[float, float] = (0.25, 0.75)
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Draw ``n`` elastic-join events: (period, sa) int32 arrays.

    Capacity appears mid-run: a join target is *absent* (invalid) from
    period 0 until its event period, then flips valid.  Distinct SAs,
    uniform periods inside ``window``.
    """
    return _draw(rng, max(0, min(int(n), num_sas)), periods, num_sas,
                 window)


def slowdown_schedule(rng: np.random.Generator, *, periods: int,
                      num_sas: int, n: int = 1,
                      window: tuple[float, float] = (0.25, 0.75),
                      magnitude: float = 4.0
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Draw ``n`` compute-straggler events: (period, sa, lat_mult).

    From each event's period onward the target SA executes every layer
    ``magnitude``x slower, and its advertised busy-times scale with it,
    so deadline-aware policies can route around it.  Distinct SAs,
    uniform periods inside ``window``.
    """
    p, sa = join_schedule(rng, periods=periods, num_sas=num_sas, n=n,
                          window=window)
    return p, sa, np.full(len(p), magnitude, np.float32)


def throttle_schedule(rng: np.random.Generator, *, periods: int,
                      num_sas: int, n: int = 1,
                      window: tuple[float, float] = (0.25, 0.75),
                      magnitude: float = 4.0
                      ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Draw ``n`` memory-path throttle events: (period, sa, bw_mult).

    A throttled SA's DRAM link degrades: its sub-jobs demand
    ``magnitude``x the bus bandwidth per unit of work (MoCA-style
    contention pressure), so overlapping SJs fleet-wide see more stall
    cycles.  Same draw scheme as :func:`slowdown_schedule`.
    """
    return slowdown_schedule(rng, periods=periods, num_sas=num_sas, n=n,
                             window=window, magnitude=magnitude)


def churn_events(cfg: ChurnConfig, periods: int, num_sas: int,
                 rng: np.random.Generator) -> dict[str, np.ndarray]:
    """Host-side (NumPy) event draw for one episode.

    Dispatches each event class to its generator above.  Fixed shape
    ``E = cfg.max_events`` regardless of scenario.
    """
    ev = no_op_events(cfg.max_events)
    plan = _event_plan(cfg)
    rows: list[tuple[int, int, int, float]] = []
    kw = dict(periods=periods, num_sas=num_sas, window=cfg.window)
    for code in (EV_FAIL, EV_JOIN, EV_THROTTLE, EV_SLOWDOWN):
        n = plan.count(code)
        if not n:
            continue
        if code == EV_FAIL:
            p, sa = failure_schedule(rng, n=n, **kw)
            mag = np.ones(len(p), np.float32)
        elif code == EV_JOIN:
            p, sa = join_schedule(rng, n=n, **kw)
            mag = np.ones(len(p), np.float32)
        elif code == EV_THROTTLE:
            p, sa, mag = throttle_schedule(rng, n=n,
                                           magnitude=cfg.magnitude, **kw)
        else:
            p, sa, mag = slowdown_schedule(rng, n=n,
                                           magnitude=cfg.magnitude, **kw)
        rows += [(int(pi), int(si), code, float(gi))
                 for pi, si, gi in zip(p, sa, mag)]
    for i, (p, s, c, g) in enumerate(rows[:cfg.max_events]):
        ev["period"][i] = p
        ev["sa"][i] = s
        ev["code"][i] = c
        ev["mag"][i] = g
    return ev


def churn_events_jax(cfg: ChurnConfig, periods: int, num_sas: int, key,
                     sa_mask=None) -> dict[str, jnp.ndarray]:
    """Traced twin of :func:`churn_events` for fused training rounds.

    ``cfg``/``periods``/``num_sas`` are static; ``key`` (and optionally
    ``sa_mask``) trace.  ``sa_mask`` restricts targets to real SAs when
    the fleet is a traced gather from a stacked ``(K, ...)`` axis (the
    multi-fleet generalist round): a uniform score per SA, penalized
    outside the mask, is argsorted so the first ``n`` entries are
    distinct valid SAs.  Parity with the NumPy path is distributional
    (different RNG), exactly like ``generate_trace_jax``.
    """
    E = cfg.max_events
    plan = _event_plan(cfg)
    code = jnp.asarray(list(plan) + [EV_NONE] * (E - len(plan)), jnp.int32)
    mag = jnp.where((code == EV_THROTTLE) | (code == EV_SLOWDOWN),
                    jnp.float32(cfg.magnitude), jnp.float32(1.0))
    kp, ks = jax.random.split(key)
    lo = int(cfg.window[0] * periods)
    hi = max(lo + 1, int(cfg.window[1] * periods))
    p = jax.random.randint(kp, (E,), lo, hi, jnp.int32)
    scores = jax.random.uniform(ks, (num_sas,))
    if sa_mask is not None:
        scores = scores + jnp.where(sa_mask, 0.0, 1e9)
    order = jnp.argsort(scores)
    sa = order[jnp.arange(E) % num_sas].astype(jnp.int32)
    return dict(period=p, sa=sa, code=code, mag=mag)


def compile_schedule(events: dict, periods: int, num_sas: int
                     ) -> dict[str, jnp.ndarray]:
    """Events -> per-period churn rows (the episode's scanned ``xs``).

    Returns ``dict(valid (T, M) bool, lat_mult (T, M) f32,
    bw_mult (T, M) f32)``.  The loop over the ``E`` event rows is
    static Python (``E`` is tiny); event *values* trace, so one
    compiled program serves every schedule of equal ``E``.  Later rows
    win per field (a JOIN after a FAIL of the same SA revives it);
    a JOIN target is invalid from period 0 until its event period.
    """
    T, M = periods, num_sas
    tt = jnp.arange(T)[:, None]
    valid = jnp.ones((T, M), bool)
    lat = jnp.ones((T, M), jnp.float32)
    bwm = jnp.ones((T, M), jnp.float32)
    for e in range(int(events["period"].shape[0])):
        p = events["period"][e]
        col = jnp.arange(M)[None, :] == events["sa"][e]
        c, g = events["code"][e], events["mag"][e]
        after, before = col & (tt >= p), col & (tt < p)
        valid = jnp.where(after & (c == EV_FAIL), False, valid)
        valid = jnp.where(before & (c == EV_JOIN), False, valid)
        valid = jnp.where(after & (c == EV_JOIN), True, valid)
        lat = jnp.where(after & (c == EV_SLOWDOWN), g, lat)
        bwm = jnp.where(after & (c == EV_THROTTLE), g, bwm)
    return dict(valid=valid, lat_mult=lat, bw_mult=bwm)


def no_op_schedule(periods: int, num_sas: int) -> dict[str, jnp.ndarray]:
    """The identity schedule: all valid, all multipliers 1.0."""
    return dict(valid=jnp.ones((periods, num_sas), bool),
                lat_mult=jnp.ones((periods, num_sas), jnp.float32),
                bw_mult=jnp.ones((periods, num_sas), jnp.float32))


def churn_schedule(cfg: ChurnConfig, periods: int, num_sas: int,
                   rng: np.random.Generator,
                   width: int | None = None) -> dict[str, jnp.ndarray]:
    """Draw + compile one episode's schedule (host-side, seeded).

    Events are drawn over the ``num_sas`` *real* SAs but the schedule
    is compiled at ``width`` columns (default ``num_sas``): a padded
    ``M_max`` env and the plain env see identical real-SA events for
    the same ``rng``, which is what makes churn cells comparable across
    the padded/unpadded benchmark rows.  Padding columns stay valid
    with unit multipliers — the policy's ``sa_mask`` already excludes
    them.
    """
    ev = churn_events(cfg, periods, num_sas, rng)
    return compile_schedule({k: jnp.asarray(v) for k, v in ev.items()},
                            periods, width or num_sas)


def churn_schedules(cfg: ChurnConfig, periods: int, num_sas: int, seeds,
                    width: int | None = None) -> dict[str, jnp.ndarray]:
    """One deterministic schedule per eval seed, stacked over ``(B,)``.

    Seeded as ``default_rng([seed, 0xC1])`` so churn draws are
    decorrelated from the arrival traces the same seeds generate, yet
    reproducible across processes/runs (the benchmark contract).
    """
    scheds = [churn_schedule(cfg, periods, num_sas,
                             np.random.default_rng([int(s), 0xC1]), width)
              for s in seeds]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *scheds)


def churn_schedules_jax(cfg: ChurnConfig, periods: int, num_sas: int,
                        keys, sa_mask=None) -> dict[str, jnp.ndarray]:
    """Traced batched schedules for the fused rounds: vmap over keys."""
    def one(k):
        return compile_schedule(
            churn_events_jax(cfg, periods, num_sas, k, sa_mask),
            periods, num_sas)
    return jax.vmap(one)(keys)
